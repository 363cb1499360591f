"""Shape sweeps + property tests of the scan's kernels in
`repro.core.frontier` against independent numpy oracles: the tiled slot
search (`edge_slots`) against `np.searchsorted`, the chunk gather against
the concatenated CSC columns, the visited test + first-claimant selection
(`winner_dedup`) against a first-occurrence rule, and the bottom-up scan's
blocked bitmap test (`test_bit_blocks`) against plain bit tests.  Whole
levels are covered by tests/test_expand.py."""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import frontier as F


def _cumul(rng, n_seg, max_deg):
    deg = rng.integers(0, max_deg, size=n_seg).astype(np.int32)
    return np.concatenate([[0], np.cumsum(deg)]).astype(np.int32), deg


@pytest.mark.parametrize("tile,window", [(128, 32), (256, 128), (512, 256),
                                         (128, 512)])
@pytest.mark.parametrize("n_seg", [1, 7, 100, 1000])
def test_binsearch_map_sweep(tile, window, n_seg, rng):
    """`edge_slots` at every tile/window gives the per-lane searchsorted
    slot on every live lane."""
    cumul, _ = _cumul(rng, n_seg, 17)
    total = int(cumul[-1])
    e = max(tile, ((total + tile - 1) // tile) * tile)
    gids = np.arange(e, dtype=np.int32)
    k = np.asarray(F.edge_slots(jnp.asarray(cumul), jnp.asarray(gids),
                                jnp.int32(total), tile=tile, window=window))
    want = np.searchsorted(cumul, gids, side="right") - 1
    ok = gids < total
    np.testing.assert_array_equal(k[ok], want[ok])


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
    gids = np.arange(128, dtype=np.int32)
    k = np.asarray(F.edge_slots(jnp.asarray(cumul), jnp.asarray(gids),
                                jnp.int32(total), tile=64, window=16))
    valid = gids < total
    want = np.searchsorted(cumul, gids, side="right") - 1
    np.testing.assert_array_equal(k[valid], want[valid])
    assert (np.diff(k[valid]) >= 0).all()


@pytest.mark.parametrize("tile,window", [(64, 16), (128, 64)])
@pytest.mark.parametrize("n_seg", [1, 13, 64])
def test_fused_gather_stage_sweep(tile, window, n_seg, rng, monkeypatch):
    """`frontier_workload` + `reference_expand_chunk`, walked in chunks of
    `tile` lanes with the slot search at this tile and window: the chunk's
    v must be the concatenation of the frontier's CSC columns on every
    live lane, and 0 on the dead tail."""
    monkeypatch.setattr(F, "edge_slots", functools.partial(
        F.edge_slots, tile=tile, window=window))
    ncl = n_seg
    deg = rng.integers(0, 3 * tile // n_seg + 2, size=ncl).astype(np.int32)
    col_off = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    row_idx = rng.integers(0, 10_000, size=max(int(col_off[-1]), 1)) \
        .astype(np.int32)
    front = np.arange(ncl, dtype=np.int32)          # full frontier
    _, total, table = F.frontier_workload(
        jnp.asarray(front), jnp.int32(ncl), jnp.asarray(col_off))
    total = int(total)
    assert total == int(col_off[-1])
    e = max(tile, ((total + tile - 1) // tile) * tile)
    v = np.concatenate([np.asarray(F.reference_expand_chunk(
        start + jnp.arange(tile, dtype=jnp.int32), jnp.int32(total), table,
        jnp.asarray(row_idx))[0]) for start in range(0, e, tile)])
    concat = np.concatenate(
        [row_idx[col_off[c]:col_off[c + 1]] for c in front] or
        [np.zeros(0, np.int32)])
    np.testing.assert_array_equal(v[:total], concat)
    assert (v[total:] == 0).all()                   # masked lanes


def _filter(v, valid, visited, method):
    """The expand's filter: the visited test, then the first claimant."""
    unvis = valid & ~visited[v]
    return np.asarray(F.winner_dedup(v, unvis, visited.shape[0],
                                     method=method))


def _first_unvisited(v, valid, visited):
    won = np.zeros(v.shape[0], bool)
    seen = set()
    for t in range(v.shape[0]):
        x = int(v[t])
        if valid[t] and not visited[x] and x not in seen:
            seen.add(x)
            won[t] = True
    return won


@pytest.mark.parametrize("tile", [64, 128, 512])
@pytest.mark.parametrize("n_rows", [33, 256, 4096])
def test_visited_filter_sweep(tile, n_rows, rng):
    """Per chunk of `tile` lanes, both `winner_dedup` methods keep exactly
    the first occurrence of each unvisited vertex."""
    e = 4 * tile
    v = rng.integers(0, n_rows, size=e).astype(np.int32)
    valid = rng.random(e) < 0.7
    visited = rng.random(n_rows) < 0.5
    for t in range(4):
        s = slice(t * tile, (t + 1) * tile)
        want = _first_unvisited(v[s], valid[s], visited)
        for method in ("scatter", "sort"):
            got = _filter(jnp.asarray(v[s]), jnp.asarray(valid[s]),
                          jnp.asarray(visited), method)
            np.testing.assert_array_equal(got, want, err_msg=method)


def test_visited_filter_semantics():
    """Paper Alg. 3: only the first slot of a duplicate vertex wins, and
    already-visited vertices never win."""
    visited = jnp.zeros(8, bool).at[2].set(True)      # vertex 2 visited
    v = jnp.asarray([2, 5, 5, 7], jnp.int32)
    valid = jnp.ones(4, bool)
    for method in ("scatter", "sort"):
        assert _filter(v, valid, visited, method).tolist() == \
            [False, True, False, True], method


@pytest.mark.parametrize("block", [64, 128, 512])
@pytest.mark.parametrize("n_rows", [33, 256, 4096])
def test_bit_blocks_sweep(block, n_rows, rng):
    """`test_bit_blocks` reads bit c of a row-gathered blocked bitmap
    (R blocks, each a frontier mask packed little-endian into 32-bit words)
    as the plain bit test of column c, at this block size and at a
    ragged one (block % 32 != 0), where each block's pad bits must not
    alias its neighbour's."""
    for b in (block, block - 3):
        R = -(-n_rows // b)
        mask = rng.random((R, b)) < 0.4
        W = -(-b // 32)
        bits = np.zeros((R, 32 * W), bool)
        bits[:, :b] = mask
        words = np.packbits(bits, axis=1, bitorder="little").view("<u4")
        words = jnp.asarray(words.reshape(-1).astype(np.uint32))
        c = np.arange(R * b, dtype=np.int32)
        got = np.asarray(F.test_bit_blocks(words, jnp.asarray(c), b))
        np.testing.assert_array_equal(got, mask.reshape(-1), err_msg=str(b))
