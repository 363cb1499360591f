"""Direction-optimised traversal coverage (DESIGN.md sec. 11).

  * BFS / CC / SSSP / multi-source BFS through the session are bit-identical
    between direction=False, "adaptive" and "bottomup" under every fold
    codec (levels, preds, labels, dists, sources and n_levels; NOT
    edges_scanned -- bottom-up legitimately scans a different edge set);
  * the bottom-up chunk formulas (plain + value-carrying) in frontier.py
    equal a numpy parent search on random inputs, including empty/full
    frontier bitmaps and a block size not divisible by 32;
  * a hypothesis property drives whole searches on random n=37 graphs
    (S % 32 != 0) through all three modes -- plus deterministic star /
    path / isolated-root versions so the gate holds without hypothesis;
  * the adaptive switch lives INSIDE the compiled loop: one trace for a
    64-root sweep, and the per-level direction trace shows both a top-down
    and a bottom-up level on RMAT (the alpha/beta crossover);
  * engine-cache keying by direction mode, alpha and beta;
  * the deprecated `BFS2DDirection` shim warns and matches the session.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st

from repro.api import BFSConfig, DistGraph
from repro.api.session import GraphSession, build_engine
from repro.core import Grid2D, bfs_reference_py, validate_bfs
from repro.core.frontier import (exclusive_cumsum, reference_bottomup_chunk,
                                 reference_bottomup_values_chunk)
from repro.core.partition import partition_2d, partition_2d_csr
from repro.core.types import LocalGraph2D
from repro.dist.topology import Topology
from repro.graphgen import rmat_edges, build_csc
from repro.obs.trace import RECORDED_LEVELS

SCALE, EF = 8, 8
N = 1 << SCALE
CODECS = ("list", "bitmap", "delta")


@pytest.fixture(scope="module")
def graph_data():
    edges = rmat_edges(jax.random.key(7), SCALE, EF)
    edges_np = np.asarray(edges)
    co, ri = build_csc(edges, N)
    w = np.random.default_rng(3).integers(
        1, 256, size=edges_np.shape[1]).astype(np.uint8)
    deg = np.bincount(edges_np[0], minlength=N)
    roots = np.random.default_rng(4).choice(np.flatnonzero(deg > 0), 64,
                                            replace=False)
    return edges_np, co, ri, w, roots


def _graph(edges_np, w, codec="list", direction=False):
    cfg = BFSConfig(grid=(1, 1), fold_codec=codec, edge_chunk=512,
                    direction=direction)
    return DistGraph.from_edges(edges_np, cfg, n=N, weights=w)


# ----------------------------------------------------------------------------
# Session-level bit-identity: every program x codec x mode
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("codec", CODECS)
def test_all_programs_bitexact_across_modes(graph_data, codec):
    """Per-level direction choice must be an implementation detail: levels,
    preds, labels, dists and sources identical to pure top-down.  (Edge
    counts are NOT compared -- bottom-up scans unvisited rows' in-edges.)"""
    edges_np, co, ri, w, roots = graph_data
    root = int(roots[0])
    base = _graph(edges_np, w, codec=codec).session()
    ref_bfs = base.bfs(root)
    ref_cc = base.connected_components()
    ref_sssp = base.sssp(root)
    ref_mb = base.multi_bfs(roots[:3])
    for mode in ("adaptive", "bottomup"):
        sess = _graph(edges_np, w, codec=codec, direction=mode).session()
        out = sess.bfs(root)
        np.testing.assert_array_equal(np.asarray(out.level),
                                      np.asarray(ref_bfs.level))
        np.testing.assert_array_equal(np.asarray(out.pred),
                                      np.asarray(ref_bfs.pred))
        assert int(out.n_levels) == int(ref_bfs.n_levels)
        validate_bfs(edges_np, np.asarray(out.level)[:N],
                     np.asarray(out.pred)[:N], root)
        cc = sess.connected_components()
        np.testing.assert_array_equal(np.asarray(cc.labels),
                                      np.asarray(ref_cc.labels))
        sp = sess.sssp(root)
        np.testing.assert_array_equal(np.asarray(sp.dist),
                                      np.asarray(ref_sssp.dist))
        mb = sess.multi_bfs(roots[:3])
        np.testing.assert_array_equal(np.asarray(mb.level),
                                      np.asarray(ref_mb.level))
        np.testing.assert_array_equal(np.asarray(mb.src),
                                      np.asarray(ref_mb.src))


def test_adaptive_switch_in_loop_one_trace(graph_data):
    """The alpha/beta switch is a lax.cond INSIDE the while_loop: a 64-root
    sweep traces once, and on dense RMAT the trace records at least one
    top-down AND one bottom-up level (the crossover actually fires)."""
    edges_np, _, _, w, roots = graph_data
    sess = _graph(edges_np, w, direction=True).session()
    assert sess.engine.trace_count == 0
    out = sess.bfs(roots)
    assert sess.engine.trace_count == 1, "sweep must trace exactly once"
    sess.bfs(roots[::-1].copy())
    assert sess.engine.trace_count == 1, "second sweep must hit the cache"
    dirs = np.asarray(out.directions)
    assert dirs.shape == (64, RECORDED_LEVELS)
    d0 = dirs[0][dirs[0] >= 0]
    assert (d0 == 0).any() and (d0 == 1).any(), \
        f"adaptive must use both directions on RMAT, got {d0}"
    # one live entry per executed step (n_levels - 1 of them), tail stays -1
    assert (dirs[0][:int(out.n_levels[0]) - 1] >= 0).all()
    assert (dirs[0][int(out.n_levels[0]) - 1:] == -1).all()


def test_directions_trace_per_mode(graph_data):
    edges_np, _, _, w, roots = graph_data
    root = int(roots[0])
    td = _graph(edges_np, w).session().bfs(root)
    assert td.directions is None, "top-down engine reports no direction trace"
    bu = _graph(edges_np, w, direction="bottomup").session().bfs(root)
    d = np.asarray(bu.directions)
    live = d[d >= 0]
    # st.lvl exits one past the executed steps: live entries = n_levels - 1
    assert live.size == int(bu.n_levels) - 1 and (live == 1).all(), \
        "mode='bottomup' must run every level bottom-up"


# ----------------------------------------------------------------------------
# Chunk-level: frontier.py's bottom-up formulas vs a numpy parent search
# ----------------------------------------------------------------------------

def _bottomup_inputs(rng, nrl, ncl, block, e_max, frontier_frac):
    """Random CSR + frontier bitmap + a MASKED-degree workload (some rows
    'visited', their degree zeroed -- so cumul genuinely diverges from
    row_off and the addr arithmetic is exercised).  Also returns the
    frontier mask the bitmap packs."""
    deg = rng.integers(0, 6, size=nrl)
    row_off = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    col_idx = rng.integers(0, ncl, size=max(e_max, 1)).astype(np.int32)
    mask = rng.random(ncl) < frontier_frac
    W = (block + 31) // 32
    words = np.zeros(((ncl + block - 1) // block) * W, np.uint32)
    for c in np.flatnonzero(mask):
        blk, off = c // block, c % block
        words[blk * W + (off >> 5)] |= np.uint32(1) << np.uint32(off & 31)
    visited = rng.random(nrl) < 0.3
    cumul = np.asarray(exclusive_cumsum(
        jnp.asarray(np.where(visited, 0, deg).astype(np.int32))))
    total = np.int32(cumul[-1])
    return (jnp.asarray(row_off), jnp.asarray(col_idx), jnp.asarray(words),
            jnp.asarray(cumul), total, mask)


def _np_parent_search(gids, cumul, row_off, col_idx, mask):
    """Per lane: the row whose masked-degree run holds the lane's edge id
    (searchsorted), that edge's CSR address and column, and whether the
    column is in the frontier."""
    cumul, row_off, col_idx = (np.asarray(x) for x in (cumul, row_off,
                                                       col_idx))
    g = np.asarray(gids)
    r = np.searchsorted(cumul, g, side="right") - 1
    r = np.clip(r, 0, cumul.shape[0] - 2)
    addr = np.clip(row_off[r] + g - cumul[r], 0, col_idx.shape[0] - 1)
    c = col_idx[addr]
    hit = (g < cumul[-1]) & mask[c]
    return r, c, addr, hit


@pytest.mark.parametrize("block", [37, 64])
@pytest.mark.parametrize("frontier_frac", [0.0, 0.4, 1.0])
def test_bottomup_chunk_paths_agree(block, frontier_frac):
    """`reference_bottomup_chunk` equals the numpy parent search, incl.
    empty and full bitmaps and S % 32 != 0 (the ragged last word of each
    block)."""
    rng = np.random.default_rng(block * 10 + int(frontier_frac * 10))
    nrl = ncl = 2 * block
    row_off, col_idx, words, cumul, total, mask = _bottomup_inputs(
        rng, nrl, ncl, block, e_max=6 * nrl, frontier_frac=frontier_frac)
    gids = jnp.arange(128, dtype=jnp.int32)
    got = reference_bottomup_chunk(gids, cumul, total, row_off, col_idx,
                                   words, block=block)
    r, c, _, hit = _np_parent_search(gids, cumul, row_off, col_idx, mask)
    _assert_chunks_match(gids, total, got, (r, c, hit))


def _assert_chunks_match(gids, total, got, want):
    """hit must match lane-for-lane; the other outputs are only specified on
    live lanes (gid < total) -- out-of-workload lanes carry don't-care row
    indices."""
    live = np.asarray(gids) < int(total)
    np.testing.assert_array_equal(np.asarray(got[-1]), np.asarray(want[-1]))
    for name, x, y in zip(("r", "c/pay", "addr"), got[:-1], want[:-1]):
        np.testing.assert_array_equal(np.where(live, np.asarray(x), 0),
                                      np.where(live, np.asarray(y), 0),
                                      err_msg=name)


def test_bottomup_values_chunk_paths_agree():
    """`reference_bottomup_values_chunk` equals the numpy parent search,
    with the payload of the lane's column and its CSR edge address."""
    rng = np.random.default_rng(11)
    block = 37
    nrl = ncl = 74
    row_off, col_idx, words, cumul, total, mask = _bottomup_inputs(
        rng, nrl, ncl, block, e_max=6 * nrl, frontier_frac=0.5)
    pay = rng.integers(0, 1000, size=ncl).astype(np.int32)
    gids = jnp.arange(96, dtype=jnp.int32)
    got = reference_bottomup_values_chunk(gids, cumul, total, row_off,
                                          col_idx, words, jnp.asarray(pay),
                                          block=block)
    r, c, addr, hit = _np_parent_search(gids, cumul, row_off, col_idx, mask)
    assert int(total) > 0 and hit.any()
    _assert_chunks_match(gids, total, got, (r, pay[c], addr, hit))


# ----------------------------------------------------------------------------
# Whole-search property: random n=37 graphs, all three modes agree
# ----------------------------------------------------------------------------

N_SMALL = 37           # 1x1 grid -> S = 37, so S % 32 != 0
E_HALF = 96            # fixed shape: AOT caches absorb repeat examples


class _ModeRunner:
    """One engine + one AOT cache per mode, shared across examples."""

    def __init__(self):
        self.grid = Grid2D.for_vertices(N_SMALL, 1, 1)
        self.topo = Topology.for_grid(self.grid)
        self.compiled = {}
        self.sessions = {}
        for mode in (False, "adaptive", "bottomup"):
            cfg = BFSConfig(grid=self.grid, edge_chunk=64, max_levels=40,
                            direction=mode)
            self.sessions[mode] = (cfg, build_engine(self.topo, cfg), {})

    def run(self, edges_np, root):
        lg = partition_2d(edges_np, self.grid, pad_to=2 * E_HALF)
        csc = LocalGraph2D(jnp.asarray(lg.col_off), jnp.asarray(lg.row_idx),
                          jnp.asarray(lg.nnz))
        csr = {k: jnp.asarray(v) for k, v in partition_2d_csr(
            edges_np, self.grid, pad_to=2 * E_HALF).items()}
        outs = {}
        for mode, (cfg, engine, cache) in self.sessions.items():
            dg = DistGraph(self.topo, csc, csr=csr, config=cfg)
            dg._compiled = cache
            outs[mode] = GraphSession(dg, cfg, engine=engine).bfs(root)
        return outs


@pytest.fixture(scope="module")
def mode_runner():
    return _ModeRunner()


def _assert_modes_agree(mode_runner, edges_np, root):
    outs = mode_runner.run(edges_np, root)
    ref = outs[False]
    co, ri = build_csc(jnp.asarray(edges_np), N_SMALL)
    lvl_ref, _ = bfs_reference_py(co, ri, root, N_SMALL)
    assert (np.asarray(ref.level)[:N_SMALL] == lvl_ref).all()
    for mode in ("adaptive", "bottomup"):
        out = outs[mode]
        np.testing.assert_array_equal(np.asarray(out.level),
                                      np.asarray(ref.level), err_msg=mode)
        np.testing.assert_array_equal(np.asarray(out.pred),
                                      np.asarray(ref.pred), err_msg=mode)
        assert int(out.n_levels) == int(ref.n_levels), mode


@settings(max_examples=12, deadline=None)
@given(st.data())
def test_modes_agree_random_graphs(mode_runner, data):
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    half = rng.integers(0, N_SMALL, size=(2, E_HALF))
    edges_np = np.concatenate([half, half[::-1]], axis=1)
    root = int(rng.integers(0, N_SMALL))
    _assert_modes_agree(mode_runner, edges_np, root)


def test_modes_agree_edge_cases(mode_runner):
    """Deterministic versions of the hypothesis edge cases: an isolated
    root (empty frontier after level 0), a star (full frontier -> bottom-up
    with everything visited next level), and a long path (many tiny
    frontiers; adaptive must stay top-down and still agree)."""
    # star centred at 0, vertex 36 isolated; pad with self-loops at 0
    hub = np.stack([np.zeros(36, np.int64), np.arange(36, dtype=np.int64)])
    pad = np.zeros((2, E_HALF - hub.shape[1]), np.int64)
    star = np.concatenate([hub, pad], axis=1)
    star = np.concatenate([star, star[::-1]], axis=1)
    _assert_modes_agree(mode_runner, star, 0)       # full-frontier level
    _assert_modes_agree(mode_runner, star, 5)       # leaf root
    # root 36 isolated: BFS is a single vertex, empty frontier immediately
    _assert_modes_agree(mode_runner, star, 36)
    # path 0-1-...-36
    u = np.arange(36, dtype=np.int64)
    path = np.stack([u, u + 1])
    pad = np.zeros((2, E_HALF - path.shape[1]), np.int64)
    path = np.concatenate([path, pad], axis=1)
    path = np.concatenate([path, path[::-1]], axis=1)
    _assert_modes_agree(mode_runner, path, 0)
    _assert_modes_agree(mode_runner, path, 18)


# ----------------------------------------------------------------------------
# Cache keying + the deprecated shim
# ----------------------------------------------------------------------------

def test_engine_keys_cover_direction_knobs():
    td = BFSConfig()
    ad = BFSConfig(direction=True)
    assert td.engine_key != ad.engine_key
    assert ad.engine_key == BFSConfig(direction="adaptive").engine_key
    assert ad.engine_key != BFSConfig(direction="bottomup").engine_key
    assert ad.engine_key != BFSConfig(direction=True, alpha=12).engine_key
    assert ad.engine_key != BFSConfig(direction=True, beta=128).engine_key
    with pytest.raises(ValueError, match="direction="):
        BFSConfig(direction="sideways").direction_mode


def test_direction_program_key_distinguishes_inner():
    from repro.algos import BFSLevelsProgram, DirectionProgram
    from repro.algos.cc import ConnectedComponentsProgram

    a = DirectionProgram(BFSLevelsProgram())
    b = DirectionProgram(ConnectedComponentsProgram())
    assert a.key != b.key
    assert a.n_extra == 2            # inner 0 + CSR (row_off, col_idx)
    assert DirectionProgram(BFSLevelsProgram(), mode="bottomup").key != a.key
    with pytest.raises(ValueError, match="mode"):
        DirectionProgram(BFSLevelsProgram(), mode="downhill")


def test_bfs2d_direction_shim_warns_and_matches(graph_data):
    """The deprecated driver is a veneer over BFSConfig(direction=True)."""
    from repro.core.direction import BFS2DDirection
    from repro.dist.compat import make_mesh

    edges_np, co, ri, _, roots = graph_data
    root = int(roots[1])
    grid = Grid2D.for_vertices(N, 1, 1)
    lg = partition_2d(edges_np, grid)
    g = LocalGraph2D(jnp.asarray(lg.col_off), jnp.asarray(lg.row_idx),
                     jnp.asarray(lg.nnz))
    csr = {k: jnp.asarray(v)
           for k, v in partition_2d_csr(edges_np, grid).items()}
    mesh = make_mesh((1, 1), ("r", "c"))
    with pytest.warns(DeprecationWarning, match="BFS2DDirection"):
        drv = BFS2DDirection(grid, mesh, edge_chunk=512)
    out = drv.run(g, csr, root)
    ref, _ = bfs_reference_py(co, ri, root, N)
    assert (np.asarray(out.level)[:N] == ref).all()
    dirs = np.asarray(out.directions)
    assert dirs[dirs >= 0].size == int(out.n_levels) - 1
    drv.run(g, csr, root)
    assert drv.engine.trace_count == 1, "shim reruns must hit the AOT cache"
