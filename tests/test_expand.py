"""Fused local-expand pipeline coverage (DESIGN.md sec. 9).

  * `local_expand` reference vs pallas-interpret agree BIT-EXACTLY on random
    CSC graphs (hypothesis), including empty frontiers, isolated vertices
    and full-frontier levels -- plus deterministic versions of those edge
    cases so the gate holds where hypothesis is not installed;
  * the value-carrying chunk kernel matches `scan_relax`'s inline formulas;
  * BFS / CC / SSSP / multi-source BFS through the session are bit-identical
    between expand="reference" and expand="pallas-interpret" under every
    fold codec (the acceptance gate of the pallas-interpret CI leg);
  * the selection rules: "auto" resolution, the REPRO_EXPAND override, and
    engine-cache keying by the RESOLVED path;
  * `import repro.kernels` stays lazy (no Pallas modules loaded until a
    kernel symbol is touched).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.api import BFSConfig, DistGraph
from repro.graphgen import rmat_edges
from repro.kernels import expand_chunk_values, local_expand
from repro.kernels.select import EXPAND_ENV, resolve_expand_path

SCALE, EF = 7, 8
N = 1 << SCALE
CODECS = ("list", "bitmap", "delta")
OUT_FIELDS = ("verts", "parents", "count", "visited", "edges_scanned")


def _random_csc(rng, n, max_deg):
    deg = rng.integers(0, max_deg + 1, size=n)
    col_off = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    row_idx = rng.integers(0, n, size=max(int(col_off[-1]), 1)) \
        .astype(np.int32)
    return col_off, row_idx


def _assert_paths_agree(front, cnt, csc, visited, **kw):
    a = local_expand((front, cnt), csc, visited, path="reference", **kw)
    b = local_expand((front, cnt), csc, visited, path="pallas-interpret",
                     **kw)
    for f in OUT_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)
    return a


# ----------------------------------------------------------------------------
# local_expand: reference vs pallas-interpret, property + deterministic
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
    _assert_paths_agree(front, cnt, (col_off, row_idx), visited,
                        edge_chunk=32, tile=16, window=8)


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
    out = _assert_paths_agree(front, cnt, (col_off, row_idx), visited,
                              edge_chunk=64, tile=32, window=16)
    if kind in ("empty", "isolated"):
        assert int(out.count) == 0 and int(out.edges_scanned) == 0


def test_local_expand_against_host_reference(rng):
    """Winners = first unvisited occurrence in CSC scan order, compacted
    ascending: check against a plain-python scan."""
    n = 96
    col_off, row_idx = _random_csc(rng, n, 5)
    cnt = 17
    ids = np.sort(rng.choice(n, cnt, replace=False)).astype(np.int32)
    front = np.full(n, -1, np.int32)
    front[:cnt] = ids
    visited = np.zeros(n, bool)
    visited[rng.choice(n, 10, replace=False)] = True
    out = _assert_paths_agree(front, cnt, (col_off, row_idx), visited,
                              edge_chunk=32, tile=16, window=8)
    seen, host = set(), {}
    for u in ids:
        for e in range(col_off[u], col_off[u + 1]):
            v = int(row_idx[e])
            if not visited[v] and v not in seen:
                seen.add(v)
                host[v] = int(u)
    verts = sorted(host)
    np.testing.assert_array_equal(np.asarray(out.verts)[:len(verts)], verts)
    np.testing.assert_array_equal(
        np.asarray(out.parents)[:len(verts)], [host[v] for v in verts])
    assert int(out.count) == len(verts)
    assert int(out.edges_scanned) == sum(
        int(col_off[u + 1] - col_off[u]) for u in ids)


def test_value_chunk_matches_inline(rng):
    """The value-carrying kernel must reproduce scan_relax's inline
    map/gather on every valid lane."""
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
    v, pay, addr, valid = expand_chunk_values(
        gids, jnp.asarray(cumul), jnp.asarray(front), jnp.asarray(payload),
        jnp.int32(cnt), jnp.asarray(col_off), jnp.asarray(row_idx),
        tile=32, window=16, interpret=True)
    k = np.clip(np.searchsorted(cumul, np.arange(e), side="right") - 1,
                0, n - 1)
    a_ref = np.clip(col_off[u_safe[k]] + np.arange(e) - cumul[k],
                    0, row_idx.shape[0] - 1)
    ok = np.arange(e) < total
    np.testing.assert_array_equal(np.asarray(valid), ok)
    np.testing.assert_array_equal(np.asarray(v)[ok], row_idx[a_ref][ok])
    np.testing.assert_array_equal(np.asarray(pay)[ok], payload[k][ok])
    np.testing.assert_array_equal(np.asarray(addr)[ok], a_ref[ok])


# ----------------------------------------------------------------------------
# Engine-level parity: every program, every codec (the CI-leg gate)
# ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def graphs():
    edges = np.asarray(rmat_edges(jax.random.key(3), SCALE, EF))
    w = np.random.default_rng(1).integers(1, 256, size=edges.shape[1]) \
        .astype(np.uint8)
    out = {}
    for path in ("reference", "pallas-interpret"):
        out[path] = DistGraph.from_edges(
            edges, BFSConfig(grid=(1, 1), edge_chunk=256, expand=path),
            n=N, weights=w)
    return edges, out


@pytest.mark.parametrize("codec", CODECS)
def test_engine_parity_all_programs(graphs, codec):
    edges, gs = graphs
    deg = np.bincount(edges[0], minlength=N)
    roots = np.flatnonzero(deg > 0)[[0, 3, 11]]
    sr = gs["reference"].session(
        BFSConfig(grid=(1, 1), edge_chunk=256, fold_codec=codec,
                  expand="reference"))
    sp = gs["pallas-interpret"].session(
        BFSConfig(grid=(1, 1), edge_chunk=256, fold_codec=codec,
                  expand="pallas-interpret"))
    a, b = sr.bfs(roots), sp.bfs(roots)           # batched sweep parity
    np.testing.assert_array_equal(np.asarray(a.level), np.asarray(b.level))
    np.testing.assert_array_equal(np.asarray(a.pred), np.asarray(b.pred))
    assert a.edges_scanned == b.edges_scanned
    ca, cb = (s.connected_components(fold_codec=codec) for s in (sr, sp))
    np.testing.assert_array_equal(np.asarray(ca.labels),
                                  np.asarray(cb.labels))
    assert ca.edges_scanned == cb.edges_scanned
    da, db = (s.sssp(int(roots[1]), fold_codec=codec) for s in (sr, sp))
    np.testing.assert_array_equal(np.asarray(da.dist), np.asarray(db.dist))
    assert da.edges_scanned == db.edges_scanned
    ma, mb = (s.multi_bfs(roots, fold_codec=codec) for s in (sr, sp))
    np.testing.assert_array_equal(np.asarray(ma.level), np.asarray(mb.level))
    np.testing.assert_array_equal(np.asarray(ma.src), np.asarray(mb.src))
    assert ma.edges_scanned == mb.edges_scanned


# ----------------------------------------------------------------------------
# Selection rules + cache keying + lazy import
# ----------------------------------------------------------------------------

def test_resolve_expand_path_rules(monkeypatch):
    monkeypatch.delenv(EXPAND_ENV, raising=False)
    assert resolve_expand_path("reference") == "reference"
    assert resolve_expand_path("pallas-interpret") == "pallas-interpret"
    assert resolve_expand_path("auto", platform="cpu") == "reference"
    # the TPU compiler refuses the kernels: auto takes the jnp scan there,
    # an explicit "pallas" is passed through to the compiler, and the
    # interpreter is refused off CPU
    assert resolve_expand_path("auto", platform="tpu") == "reference"
    assert resolve_expand_path("pallas", platform="tpu") == "pallas"
    with pytest.raises(ValueError, match="CPU only"):
        resolve_expand_path("pallas-interpret", platform="tpu")
    assert resolve_expand_path(None, platform="gpu") == "pallas"
    monkeypatch.setenv(EXPAND_ENV, "pallas-interpret")
    assert resolve_expand_path("auto", platform="cpu") == "pallas-interpret"
    with pytest.raises(ValueError, match=EXPAND_ENV):
        resolve_expand_path("auto", platform="tpu")
    # explicit spellings are NOT overridden by the environment
    assert resolve_expand_path("reference") == "reference"
    monkeypatch.setenv(EXPAND_ENV, "nonsense")
    with pytest.raises(ValueError, match="REPRO_EXPAND"):
        resolve_expand_path("auto")
    monkeypatch.delenv(EXPAND_ENV)
    with pytest.raises(ValueError, match="expand="):
        resolve_expand_path("cuda-graphs")


def test_config_keys_use_resolved_path(monkeypatch):
    monkeypatch.delenv(EXPAND_ENV, raising=False)
    ref = BFSConfig(expand="reference")
    pal = BFSConfig(expand="pallas-interpret")
    auto = BFSConfig()
    assert ref.engine_key != pal.engine_key
    # "auto" resolves against the ambient backend (cpu -> reference, an
    # accelerator -> pallas); the key must equal the matching explicit one
    expected = resolve_expand_path("auto")
    assert auto.expand_path == expected
    if expected == "reference":
        assert auto.engine_key == ref.engine_key  # same resolved engine
    monkeypatch.setenv(EXPAND_ENV, "pallas-interpret")
    assert auto.expand_path == "pallas-interpret"
    assert auto.engine_key == pal.engine_key      # env re-keys "auto"
    k1 = auto.algo_engine_key(("cc",), "bitmap", 10)
    monkeypatch.delenv(EXPAND_ENV)
    assert auto.algo_engine_key(("cc",), "bitmap", 10) != k1


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


@pytest.mark.parametrize("e", [100, 512, 8192])
def test_edge_slots_match_searchsorted(e, rng):
    """The reference path's tiled slot search gives exactly the per-lane
    `searchsorted` slot on every live lane, through zero-degree runs (2D
    blocks, visited rows) and a dead tail (the last chunk of a level)."""
    from repro.core.frontier import edge_slots, exclusive_cumsum

    slots = jax.jit(edge_slots)
    for _ in range(20):
        n = int(rng.integers(1, 3000))
        deg = rng.integers(0, 6, n) * (rng.random(n) < rng.random())
        deg[int(rng.integers(0, n + 1)):] = 0
        cumul = np.asarray(exclusive_cumsum(jnp.asarray(deg, jnp.int32)))
        total = int(cumul[-1])
        gids = int(rng.integers(0, max(total, 1))) + np.arange(
            e, dtype=np.int32)
        k = np.asarray(slots(jnp.asarray(cumul), jnp.asarray(gids),
                             jnp.int32(total)))
        live = gids < total
        want = np.searchsorted(cumul, gids, side="right") - 1
        np.testing.assert_array_equal(k[live], want[live])
        assert ((k >= 0) & (k < cumul.shape[0])).all()


def test_algo_engines_honor_custom_expand_fn(graphs):
    """config.expand_fn wins over `expand` for ALGO engines too (the
    documented precedence); value scans then fall back to reference."""
    from repro.algos import ConnectedComponentsProgram

    _, gs = graphs

    def marker(*a, **k):                          # never called
        raise AssertionError

    cfg = BFSConfig(grid=(1, 1), edge_chunk=256, expand_fn=marker)
    sess = gs["reference"].session(cfg)
    eng, key = sess._algo_engine(ConnectedComponentsProgram(), None, 10)
    assert eng.expand_path == "custom" and eng.expand_fn is marker
    assert eng.value_expand_fn is None
    # and the cache key must distinguish custom-fn configs
    k2 = BFSConfig(grid=(1, 1), edge_chunk=256) \
        .algo_engine_key(("cc",), "bitmap", 10)
    assert cfg.algo_engine_key(("cc",), "bitmap", 10) != k2


def test_engine_uses_fused_path(graphs):
    _, gs = graphs
    eng_p = gs["pallas-interpret"].engine_for(
        BFSConfig(grid=(1, 1), edge_chunk=256, expand="pallas-interpret"))
    assert eng_p.expand_path == "pallas-interpret"
    assert eng_p.expand_fn is not None and eng_p.value_expand_fn is not None
    eng_r = gs["reference"].engine_for(
        BFSConfig(grid=(1, 1), edge_chunk=256, expand="reference"))
    assert eng_r.expand_path == "reference"
    assert eng_r.expand_fn is None and eng_r.value_expand_fn is None


def test_kernels_import_is_lazy():
    """`import repro.kernels` must not pull Pallas; only touching a kernel
    symbol may (the guard that keeps `import repro` working without it)."""
    code = (
        "import sys, repro, repro.kernels\n"
        "assert 'repro.kernels.expand' not in sys.modules\n"
        "assert 'jax.experimental.pallas' not in sys.modules\n"
        "from repro.kernels import resolve_expand_path\n"
        "assert resolve_expand_path('reference') == 'reference'\n"
        "assert 'jax.experimental.pallas' not in sys.modules\n"
        "from repro.kernels import local_expand\n"
        "assert 'repro.kernels.expand' in sys.modules\n")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
