"""Top-down scan coverage (DESIGN.md sec. 9), against independent numpy
oracles.

  * one `expand_frontier` level on random CSC graphs (hypothesis) equals a
    host scan: winners are the first unvisited occurrence in CSC scan
    order, each with its frontier column as parent -- including empty
    frontiers, isolated vertices and full-frontier levels, with
    deterministic versions of those edge cases so the gate holds where
    hypothesis is not installed;
  * `scan_relax`'s chunk (map, payload pick, edge address) and its
    min-combined candidates equal a numpy scan;
  * BFS / CC / SSSP / multi-source BFS through the session equal the
    host references (`algos/reference.py`, a level-synchronous BFS) under
    every fold codec;
  * the tiled slot search and the chunk map against `searchsorted` and
    four per-lane gathers.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.algos import cc_reference, multi_bfs_reference, sssp_reference
from repro.algos.program import scan_relax
from repro.api import BFSConfig, DistGraph
from repro.core import frontier as F
from repro.core.types import Grid2D
from repro.graphgen import rmat_edges

SCALE, EF = 7, 8
N = 1 << SCALE
CODECS = ("list", "bitmap", "delta")
I32_MAX = int(np.iinfo(np.int32).max)


def _random_csc(rng, n, max_deg):
    deg = rng.integers(0, max_deg + 1, size=n)
    col_off = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    row_idx = rng.integers(0, n, size=max(int(col_off[-1]), 1)) \
        .astype(np.int32)
    return col_off, row_idx


def _host_level(front, cnt, col_off, row_idx, visited):
    """Winners of one level in CSC scan order, with their parents."""
    seen, verts, parents = set(), [], []
    for u in front[:cnt]:
        for e in range(col_off[u], col_off[u + 1]):
            v = int(row_idx[e])
            if not visited[v] and v not in seen:
                seen.add(v)
                verts.append(v)
                parents.append(int(u))
    scanned = sum(int(col_off[u + 1] - col_off[u]) for u in front[:cnt])
    return verts, parents, scanned


def _assert_level_matches_host(front, cnt, csc, visited, *, edge_chunk,
                               lvl=3):
    """One `expand_frontier` level on a 1x1 grid against `_host_level`:
    visited, level and pred of every row, the discovery bucket (in scan
    order) and the scanned-edge count."""
    col_off, row_idx = csc
    n = visited.shape[0]
    grid = Grid2D.for_vertices(n, 1, 1)
    level = np.where(visited, 1, -1).astype(np.int32)
    pred = np.where(visited, 0, -1).astype(np.int32)
    out = F.expand_frontier(
        jnp.asarray(col_off), jnp.asarray(row_idx), jnp.asarray(visited),
        jnp.asarray(level), jnp.asarray(pred), jnp.asarray(front),
        jnp.int32(cnt), jnp.int32(lvl), grid=grid, i=0, j=0,
        edge_chunk=edge_chunk)
    verts, parents, scanned = _host_level(front, cnt, col_off, row_idx,
                                          visited)
    vis_want, lvl_want, pred_want = visited.copy(), level.copy(), pred.copy()
    vis_want[verts] = True
    lvl_want[verts] = lvl
    pred_want[verts] = parents
    for name, want in (("visited", vis_want), ("level", lvl_want),
                       ("pred", pred_want)):
        np.testing.assert_array_equal(np.asarray(getattr(out, name)), want,
                                      err_msg=name)
    k = len(verts)
    assert int(out.dst_cnt[0]) == k
    np.testing.assert_array_equal(np.asarray(out.dst)[0, :k], verts)
    assert (np.asarray(out.dst)[0, k:] == -1).all()
    assert int(out.edges_scanned) == scanned
    return k, scanned


# ----------------------------------------------------------------------------
# One expand_frontier level against the host scan, property + deterministic
# ----------------------------------------------------------------------------

@given(st.data())
@settings(max_examples=12, deadline=None)
def test_local_expand_paths_agree_property(data):
    """Random CSC graphs, random visited sets, random frontier sizes from
    empty to full -- isolated (zero-degree) vertices arise naturally from
    the degree draw and are also forced into the frontier."""
    n = data.draw(st.integers(8, 48))
    degs = data.draw(st.lists(st.integers(0, 7), min_size=n, max_size=n))
    col_off = np.concatenate([[0], np.cumsum(degs)]).astype(np.int32)
    nnz = max(int(col_off[-1]), 1)
    row_idx = np.asarray(
        data.draw(st.lists(st.integers(0, n - 1), min_size=nnz,
                           max_size=nnz)), np.int32)
    cnt = data.draw(st.integers(0, n))           # empty ... full frontier
    ids = np.sort(np.random.default_rng(
        data.draw(st.integers(0, 2**31))).permutation(n)[:cnt]) \
        .astype(np.int32)
    front = np.full(n, -1, np.int32)
    front[:cnt] = ids
    visited = np.zeros(n, bool)
    visited[np.random.default_rng(
        data.draw(st.integers(0, 2**31))).random(n) < 0.3] = True
    _assert_level_matches_host(front, cnt, (col_off, row_idx), visited,
                               edge_chunk=32)


@pytest.mark.parametrize("kind", ["empty", "isolated", "full"])
def test_local_expand_paths_agree_edges(kind, rng):
    """Deterministic pins of the property's edge cases: an empty frontier, a
    frontier of only isolated vertices, and a full-frontier level."""
    n = 64
    col_off, row_idx = _random_csc(rng, n, 4)
    if kind == "isolated":
        col_off = np.zeros(n + 1, np.int32)      # every vertex degree 0
        row_idx = np.zeros(1, np.int32)
    cnt = 0 if kind == "empty" else n
    front = np.full(n, -1, np.int32)
    if cnt:
        front[:] = np.arange(n, dtype=np.int32)
    visited = np.zeros(n, bool)
    found, scanned = _assert_level_matches_host(
        front, cnt, (col_off, row_idx), visited, edge_chunk=64)
    if kind in ("empty", "isolated"):
        assert found == 0 and scanned == 0
    else:
        assert found > 0 and scanned == int(col_off[-1])


def test_local_expand_against_host_reference(rng):
    """A sparse level over several chunks: a partial frontier, some rows
    already visited, chunks narrower than the level."""
    n = 96
    col_off, row_idx = _random_csc(rng, n, 5)
    cnt = 17
    ids = np.sort(rng.choice(n, cnt, replace=False)).astype(np.int32)
    front = np.full(n, -1, np.int32)
    front[:cnt] = ids
    visited = np.zeros(n, bool)
    visited[rng.choice(n, 10, replace=False)] = True
    found, scanned = _assert_level_matches_host(
        front, cnt, (col_off, row_idx), visited, edge_chunk=8)
    assert found > 0 and scanned > 8


def test_value_chunk_matches_inline(rng):
    """`scan_relax`'s chunk -- the map of `reference_expand_chunk` and the
    payload of the lane's frontier slot -- against a numpy searchsorted
    map on every live lane; and its min-combined candidates against a
    numpy scan of the frontier's columns."""
    n = 80
    col_off, row_idx = _random_csc(rng, n, 6)
    cnt = 23
    ids = np.sort(rng.choice(n, cnt, replace=False)).astype(np.int32)
    front = np.full(n, -1, np.int32)
    front[:cnt] = ids
    payload = rng.integers(0, 1000, size=n).astype(np.int32)
    u_safe = np.clip(front, 0, n - 1)
    deg = col_off[u_safe + 1] - col_off[u_safe]
    deg = np.where(np.arange(n) < cnt, deg, 0)
    cumul = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    total = int(cumul[cnt])
    e = 128
    gids = jnp.arange(e, dtype=jnp.int32)
    _, total_j, table = F.frontier_workload(
        jnp.asarray(front), jnp.int32(cnt), jnp.asarray(col_off))
    v, _, k, addr, valid = F.reference_expand_chunk(
        gids, total_j, table, jnp.asarray(row_idx))
    pay = jnp.asarray(payload)[k]
    k_ref = np.clip(np.searchsorted(cumul, np.arange(e), side="right") - 1,
                    0, n - 1)
    a_ref = np.clip(col_off[u_safe[k_ref]] + np.arange(e) - cumul[k_ref],
                    0, row_idx.shape[0] - 1)
    ok = np.arange(e) < total
    assert int(total_j) == total
    np.testing.assert_array_equal(np.asarray(valid), ok)
    np.testing.assert_array_equal(np.asarray(v)[ok], row_idx[a_ref][ok])
    np.testing.assert_array_equal(np.asarray(pay)[ok], payload[k_ref][ok])
    np.testing.assert_array_equal(np.asarray(addr)[ok], a_ref[ok])

    w = rng.integers(1, 9, size=row_idx.shape[0]).astype(np.int32)
    cand, scanned = scan_relax(
        jnp.asarray(col_off), jnp.asarray(row_idx), jnp.asarray(w),
        jnp.asarray(front), jnp.asarray(payload), jnp.int32(cnt),
        lambda p, wt: p + wt, n_rows=n, edge_chunk=32)
    want = np.full(n, I32_MAX, np.int64)
    for slot, u in enumerate(ids):
        for a in range(col_off[u], col_off[u + 1]):
            x = int(row_idx[a])
            want[x] = min(want[x], int(payload[slot]) + int(w[a]))
    np.testing.assert_array_equal(np.asarray(cand), want)
    assert int(scanned) == total


# ----------------------------------------------------------------------------
# Every program through the session against the host references
# ----------------------------------------------------------------------------

def bfs_min_parent(edges, n, root):
    """Level-synchronous BFS whose parent is the SMALLEST frontier vertex
    with an edge to the child -- the parent the engine elects, since it
    scans the frontier in ascending order and the first claimant wins."""
    u, v = (np.asarray(x, np.int64) for x in edges)
    level = np.full(n, -1, np.int32)
    pred = np.full(n, -1, np.int32)
    level[root], pred[root] = 0, root
    front, lvl = np.asarray([root]), 1
    while front.size:
        m = np.isin(u, front) & (level[v] < 0)
        best = np.full(n, I32_MAX, np.int64)
        np.minimum.at(best, v[m], u[m])
        new = np.flatnonzero(best < I32_MAX)
        level[new], pred[new] = lvl, best[new]
        front, lvl = new, lvl + 1
    return level, pred


@pytest.fixture(scope="module")
def graphs():
    edges = np.asarray(rmat_edges(jax.random.key(3), SCALE, EF))
    w = np.random.default_rng(1).integers(1, 256, size=edges.shape[1]) \
        .astype(np.uint8)
    graph = DistGraph.from_edges(
        edges, BFSConfig(grid=(1, 1), edge_chunk=256), n=N, weights=w)
    return edges, w, graph


@pytest.mark.parametrize("codec", CODECS)
def test_engine_parity_all_programs(graphs, codec):
    """BFS levels, parents and scanned edges; CC labels; SSSP distances;
    multi-source levels and sources -- each against its host reference."""
    edges, w, graph = graphs
    deg = np.bincount(edges[0], minlength=N)
    roots = np.flatnonzero(deg > 0)[[0, 3, 11]]
    s = graph.session(BFSConfig(grid=(1, 1), edge_chunk=256,
                                fold_codec=codec))
    out = s.bfs(roots)                            # batched sweep
    for b, root in enumerate(roots):
        level, pred = bfs_min_parent(edges, N, int(root))
        np.testing.assert_array_equal(np.asarray(out.level)[b, :N], level)
        np.testing.assert_array_equal(np.asarray(out.pred)[b, :N], pred)
        # top-down scans every out-edge of every reached vertex once
        assert out.edges_scanned[b] == int(deg[level >= 0].sum())
    cc = s.connected_components(fold_codec=codec)
    np.testing.assert_array_equal(np.asarray(cc.labels)[:N],
                                  cc_reference(edges, N))
    d = s.sssp(int(roots[1]), fold_codec=codec)
    np.testing.assert_array_equal(np.asarray(d.dist)[:N],
                                  sssp_reference(edges, w, N, int(roots[1])))
    m = s.multi_bfs(roots, fold_codec=codec)
    lref, sref = multi_bfs_reference(edges, N, roots)
    np.testing.assert_array_equal(np.asarray(m.level)[:N], lref)
    np.testing.assert_array_equal(np.asarray(m.src)[:N], sref)
    assert m.edges_scanned == int(deg[lref >= 0].sum())


def test_pick_tile_always_divides_chunk():
    """The kernel grid needs tile | chunk; the fallback must shrink to a
    divisor, never widen to one e-wide tile (the stage-3 dedup is a dense
    (tile, tile) compare -- e-wide would be quadratic in the chunk)."""
    from repro.core.frontier import pick_tile

    for e, tile in [(8192, 512), (100_000, 512), (64, 512), (97, 64),
                    (513, 512)]:
        t = pick_tile(e, tile)
        assert e % t == 0 and t <= max(tile, 1) and t >= 1
    assert pick_tile(8192, 512) == 512
    assert pick_tile(100_000, 512) == 500


@pytest.mark.parametrize("n_vals", [0, 2], ids=["search", "picks"])
@pytest.mark.parametrize("e,tile,window", [(100, 512, 256), (512, 512, 256),
                                           (8192, 512, 256), (96, 32, 24)])
def test_edge_slots_match_searchsorted(e, tile, window, n_vals, rng):
    """The reference path's tiled slot search gives exactly the per-lane
    `searchsorted` slot on every live lane, through zero-degree runs (2D
    blocks, visited rows) and a dead tail (the last chunk of a level).
    Searching a `slot_table`, it also picks each live lane's payload at
    that slot; the small tile and window put spans wider than the tile
    and windows that start anywhere, the last one clipped to cumul's end."""
    from repro.core.frontier import edge_slots, exclusive_cumsum, slot_table

    slots = jax.jit(functools.partial(edge_slots, n_vals=n_vals, tile=tile,
                                      window=window))
    for _ in range(20):
        n = int(rng.integers(1, 3000))
        deg = rng.integers(0, 6, n) * (rng.random(n) < rng.random())
        deg[int(rng.integers(0, n + 1)):] = 0
        cumul = np.asarray(exclusive_cumsum(jnp.asarray(deg, jnp.int32)))
        total = int(cumul[-1])
        gids = int(rng.integers(0, max(total, 1))) + np.arange(
            e, dtype=np.int32)
        live = gids < total
        want = np.searchsorted(cumul, gids, side="right") - 1
        if n_vals:
            payload = rng.integers(-2**31, 2**31, (n_vals, n + 1),
                                   dtype=np.int64).astype(np.int32)
            k, vals = slots(slot_table(jnp.asarray(cumul), jnp.int32(total),
                                       jnp.asarray(payload)),
                            jnp.asarray(gids), jnp.int32(total))
            np.testing.assert_array_equal(np.asarray(vals)[:, live],
                                          payload[:, want[live]])
        else:
            k = slots(jnp.asarray(cumul), jnp.asarray(gids),
                      jnp.int32(total))
        k = np.asarray(k)
        np.testing.assert_array_equal(k[live], want[live])
        assert ((k >= 0) & (k < cumul.shape[0])).all()


def four_gather_chunk(gids, cumul, all_front, front_total, col_off,
                      row_idx):
    """The oracle: the chunk map as four per-lane gathers (front[k],
    col_off[u], cumul[k], row_idx[addr]) after a bare slot search."""
    from repro.core.frontier import edge_slots

    ncl = all_front.shape[0]
    nnz_cap = row_idx.shape[0]
    total = cumul[front_total]
    k = jnp.clip(edge_slots(cumul, gids, total), 0, ncl - 1)
    u = jnp.clip(all_front, 0, ncl - 1)[k]
    addr = jnp.clip(col_off[u] + gids - cumul[k], 0, nnz_cap - 1)
    valid = gids < total
    v = jnp.where(valid, row_idx[addr], 0)
    return v, u, k, addr, valid


def _random_block(rng, ncl, n_rows):
    """A CSC block with runs of zero-degree columns and a random frontier
    (ascending, as the engines keep it, padded -1)."""
    deg = rng.integers(0, 9, ncl) * (rng.random(ncl) < 0.6)
    deg[rng.integers(0, ncl, 3)] = 0
    col_off = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    row_idx = rng.integers(0, n_rows, max(int(col_off[-1]), 1)) \
        .astype(np.int32)
    cnt = int(rng.integers(1, ncl + 1))
    front = np.full(ncl, -1, np.int32)
    front[:cnt] = np.sort(rng.choice(ncl, cnt, replace=False))
    return col_off, row_idx, front, cnt


def test_expand_chunk_matches_four_gathers(rng):
    """`reference_expand_chunk`, its slot values picked in the slot search,
    is bit-identical to the four-gather oracle on every lane of every
    chunk of a level, the dead tail included."""
    from repro.core import frontier as F

    ref = jax.jit(F.reference_expand_chunk)
    oracle = jax.jit(four_gather_chunk)
    for ncl in (40, 700, 3000):
        col_off, row_idx, front, cnt = _random_block(rng, ncl, 500)
        cumul, total, table = F.frontier_workload(
            jnp.asarray(front), jnp.int32(cnt), jnp.asarray(col_off))
        for start in range(0, int(total), 512):
            gids = start + jnp.arange(512, dtype=jnp.int32)
            got = ref(gids, total, table, jnp.asarray(row_idx))
            want = oracle(gids, cumul, jnp.asarray(front), jnp.int32(cnt),
                          jnp.asarray(col_off), jnp.asarray(row_idx))
            for name, a, b in zip(("v", "u", "k", "addr", "valid"), got,
                                  want):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=name)


def test_expand_frontier_level_matches_four_gathers(rng, monkeypatch):
    """One `expand_frontier` level gives the same visited / level / pred /
    dst as the same level with the four-gather oracle as its map."""
    from repro.core import frontier as F
    from repro.core.types import Grid2D

    grid = Grid2D.for_vertices(4096, 2, 2)
    ncl, n_rows = grid.n_cols_local, grid.n_rows_local
    col_off, row_idx, front, cnt = _random_block(rng, ncl, n_rows)
    visited = rng.random(n_rows) < 0.2
    level = np.where(visited, 1, -1).astype(np.int32)
    pred = np.where(visited, 7, -1).astype(np.int32)
    args = (jnp.asarray(col_off), jnp.asarray(row_idx), jnp.asarray(visited),
            jnp.asarray(level), jnp.asarray(pred), jnp.asarray(front),
            jnp.int32(cnt), jnp.int32(2))

    def level_out():
        return F.expand_frontier(*args, grid=grid, i=1, j=0, edge_chunk=256)

    got = level_out()
    cumul = F.frontier_workload(args[5], args[6], args[0])[0]
    monkeypatch.setattr(
        F, "reference_expand_chunk",
        lambda gids, total, table, row_idx: four_gather_chunk(
            gids, cumul, args[5], args[6], args[0], row_idx))
    want = level_out()
    assert int(got.edges_scanned) == int(cumul[cnt]) > 0
    for name in ("visited", "level", "pred", "dst", "dst_cnt"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=name)
