"""Per-stage shape/dtype sweeps + property tests vs the ref.py oracles
(interpret=True executes the kernel bodies on CPU).  The FUSED pipeline the
stages compose into is covered by tests/test_expand.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels import binsearch_map, clip_cumul, make_expand_fn, \
    visited_filter
from repro.kernels import ref as R


def _cumul(rng, n_seg, max_deg):
    deg = rng.integers(0, max_deg, size=n_seg).astype(np.int32)
    return np.concatenate([[0], np.cumsum(deg)]).astype(np.int32), deg


@pytest.mark.parametrize("tile,window", [(128, 32), (256, 128), (512, 256),
                                         (128, 512)])
@pytest.mark.parametrize("n_seg", [1, 7, 100, 1000])
def test_binsearch_map_sweep(tile, window, n_seg, rng):
    cumul, _ = _cumul(rng, n_seg, 17)
    total = int(cumul[-1])
    e = max(tile, ((total + tile - 1) // tile) * tile)
    gids = jnp.arange(e, dtype=jnp.int32)
    cc = clip_cumul(jnp.asarray(cumul), jnp.int32(n_seg))
    k = np.asarray(binsearch_map(cc, gids, tile=tile, window=window,
                                 interpret=True))
    k_ref = np.asarray(R.binsearch_map_ref(jnp.asarray(cumul), gids))
    ok = np.asarray(gids) < total
    np.testing.assert_array_equal(k[ok], k_ref[ok])


@given(st.data())
@settings(max_examples=15, deadline=None)
def test_binsearch_map_property(data):
    """Monotonicity + correctness on arbitrary degree sequences, incl. runs
    of zero-degree frontier vertices (empty CSC columns)."""
    degs = data.draw(st.lists(st.integers(0, 9), min_size=1, max_size=60))
    cumul = np.concatenate([[0], np.cumsum(degs)]).astype(np.int32)
    total = int(cumul[-1])
    if total == 0:
        return
    gids = jnp.arange(128, dtype=jnp.int32)
    cc = clip_cumul(jnp.asarray(cumul), jnp.int32(len(degs)))
    k = np.asarray(binsearch_map(cc, gids, tile=64, window=16,
                                 interpret=True))
    valid = np.arange(128) < total
    k_ref = np.asarray(R.binsearch_map_ref(jnp.asarray(cumul), gids))
    np.testing.assert_array_equal(k[valid], k_ref[valid])
    assert (np.diff(k[valid]) >= 0).all()


@pytest.mark.parametrize("tile,window", [(64, 16), (128, 64)])
@pytest.mark.parametrize("n_seg", [1, 13, 64])
def test_fused_gather_stage_sweep(tile, window, n_seg, rng):
    """Stage 2 of the fused pipeline (the old gather_segments role): the
    kernel's v must equal row_idx[col_off[u] + gid - cumul[k]] -- i.e. the
    concatenation of the frontier's CSC columns -- on every valid lane."""
    from repro.kernels import expand_chunk

    ncl = n_seg
    deg = rng.integers(0, 3 * tile // n_seg + 2, size=ncl).astype(np.int32)
    col_off = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    row_idx = rng.integers(0, 10_000, size=max(int(col_off[-1]), 1)) \
        .astype(np.int32)
    front = np.arange(ncl, dtype=np.int32)          # full frontier
    cumul = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    total = int(cumul[-1])
    e = max(tile, ((total + tile - 1) // tile) * tile)
    gids = jnp.arange(e, dtype=jnp.int32)
    v, won, u = expand_chunk(
        gids, jnp.asarray(cumul), jnp.asarray(front), jnp.int32(ncl),
        jnp.asarray(col_off), jnp.asarray(row_idx),
        jnp.zeros((10_000,), bool), tile=tile, window=window,
        interpret=True)
    concat = np.concatenate(
        [row_idx[col_off[c]:col_off[c + 1]] for c in front] or
        [np.zeros(0, np.int32)])
    np.testing.assert_array_equal(np.asarray(v)[:total], concat)
    assert (np.asarray(v)[total:] == 0).all()       # masked lanes


@pytest.mark.parametrize("tile", [64, 128, 512])
@pytest.mark.parametrize("n_rows", [33, 256, 4096])
def test_visited_filter_sweep(tile, n_rows, rng):
    e = 4 * tile
    v = rng.integers(0, n_rows, size=e).astype(np.int32)
    valid = rng.random(e) < 0.7
    words = rng.integers(0, 2**32, size=(n_rows + 31) // 32,
                         dtype=np.uint64).astype(np.uint32)
    won = np.asarray(visited_filter(jnp.asarray(v), jnp.asarray(valid),
                                    jnp.asarray(words), tile=tile,
                                    interpret=True))
    for t in range(4):
        s = slice(t * tile, (t + 1) * tile)
        ref = np.asarray(R.visited_filter_ref(
            jnp.asarray(v[s]), jnp.asarray(valid[s]), jnp.asarray(words)))
        np.testing.assert_array_equal(won[s], ref)


def test_visited_filter_semantics():
    """Paper Alg. 3: only the first slot of a duplicate vertex wins, and
    already-visited vertices never win."""
    words = jnp.asarray(np.array([0b100], np.uint32))  # vertex 2 visited
    v = jnp.asarray([2, 5, 5, 7], jnp.int32)
    valid = jnp.ones(4, bool)
    won = np.asarray(visited_filter(v, valid, words, tile=4,
                                    interpret=True))
    assert won.tolist() == [False, True, False, True]


def test_expand_fn_matches_inline(rng):
    """The fused kernel-backed expand_fn must reproduce the inline jnp
    path through `expand_frontier` (the engines' integration point)."""
    from repro.core.frontier import expand_frontier
    from repro.core.types import Grid2D
    from repro.graphgen import rmat_edges
    from repro.core import partition_2d

    n = 1 << 8
    edges = np.asarray(rmat_edges(jax.random.key(2), 8, 6))
    grid = Grid2D.for_vertices(n, 1, 1)
    lg = partition_2d(edges, grid)
    co = jnp.asarray(lg.col_off[0, 0])
    ri = jnp.asarray(lg.row_idx[0, 0])
    visited = jnp.zeros((grid.n_rows_local,), bool)
    level = jnp.full((grid.n_rows_local,), -1, jnp.int32)
    pred = jnp.full((grid.n_rows_local,), -1, jnp.int32)
    front = jnp.full((grid.n_cols_local,), -1, jnp.int32).at[0].set(5)

    kw = dict(grid=grid, i=jnp.int32(0), j=jnp.int32(0), edge_chunk=256)
    a = expand_frontier(co, ri, visited, level, pred, front, jnp.int32(1),
                        jnp.int32(1), **kw)
    b = expand_frontier(co, ri, visited, level, pred, front, jnp.int32(1),
                        jnp.int32(1), expand_fn=make_expand_fn(
                            path="pallas-interpret", tile=128, window=64),
                        **kw)
    np.testing.assert_array_equal(np.asarray(a.visited), np.asarray(b.visited))
    np.testing.assert_array_equal(np.asarray(a.level), np.asarray(b.level))
    np.testing.assert_array_equal(np.asarray(a.dst_cnt), np.asarray(b.dst_cnt))
