"""Telemetry subsystem coverage (DESIGN.md sec. 13).

  * registry units: get-or-create, kind/label mismatch, counter
    monotonicity, gauge/histogram semantics;
  * Prometheus exposition pinned GOLDEN (the text format is the contract a
    scraper parses) + collector samples + the JSONL event log;
  * LevelTrace: telemetry on/off BIT-IDENTITY per program x codec, every
    trace channel cross-checked against an independent recomputation
    (np.bincount of the output levels, the codec's static wire formulas,
    the 64-bit edges_scanned total, the engine's own directions output);
  * trace discipline: telemetry costs no retrace on repeat sweeps;
  * request tracing: span lifecycle order + tiling, per-tenant retry
    attribution, reset-safety across GraphServer restarts, and the
    deprecated stats surfaces warning + agreeing with the new ones.
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import BFSConfig, DistGraph
from repro.obs import (PHASES, EventLog, LevelTrace, MetricsRegistry,
                       request_trace, to_prometheus)
from repro.runtime.fault import FaultInjector, StepRunner
from repro.serve import GraphServer, ServeConfig

SCALE, EF = 7, 8
N = 1 << SCALE
CODECS = ("list", "bitmap", "delta")


@pytest.fixture(scope="module")
def graph_data():
    from repro.graphgen import rmat_edges

    edges = np.asarray(rmat_edges(jax.random.key(0), SCALE, EF))
    w = (np.abs(edges[0] * 31 + edges[1]) % 255 + 1).astype(np.uint8)
    cfg = BFSConfig(grid=(1, 1), edge_chunk=256)
    g = DistGraph.from_edges(edges, cfg, n=N, weights=w)
    deg = np.bincount(edges[0], minlength=N)
    roots = np.random.default_rng(1).choice(np.flatnonzero(deg > 0), 8,
                                            replace=False).astype(np.int32)
    return g, roots


def _cfg(codec="list", telemetry=True, direction=False):
    return BFSConfig(grid=(1, 1), fold_codec=codec, edge_chunk=256,
                     telemetry=telemetry, direction=direction)


# ---------------------------------------------------------------------------
# Registry units
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_mismatches():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "help", labelnames=("tenant",))
    assert reg.counter("x_total", labelnames=("tenant",)) is c1
    with pytest.raises(ValueError):        # kind changed
        reg.gauge("x_total", labelnames=("tenant",))
    with pytest.raises(ValueError):        # label set changed
        reg.counter("x_total", labelnames=("graph",))
    with pytest.raises(ValueError):        # wrong labels at bind time
        c1.labels(graph="g").inc()


def test_counter_monotone_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    c.inc(); c.inc(2)
    assert c.value == 3 and isinstance(c.value, int)
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(5); g.dec()
    assert g.value == 4
    h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    plain = h.series()[()]
    assert plain["count"] == 3 and plain["sum"] == pytest.approx(5.55)
    assert list(plain["buckets"].values()) == [1, 2, 3]  # cumulative


def test_prometheus_golden():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "Total requests",
                    labelnames=("tenant",))
    c.labels(tenant="alice").inc()
    c.labels(tenant="bob").inc(2)
    reg.gauge("pending", "Pending").set(3)
    h = reg.histogram("lat_seconds", "Latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert to_prometheus(reg) == """\
# HELP lat_seconds Latency
# TYPE lat_seconds histogram
lat_seconds_bucket{le="0.1"} 1
lat_seconds_bucket{le="1.0"} 2
lat_seconds_bucket{le="+Inf"} 3
lat_seconds_sum 5.55
lat_seconds_count 3
# HELP pending Pending
# TYPE pending gauge
pending 3
# HELP requests_total Total requests
# TYPE requests_total counter
requests_total{tenant="alice"} 1
requests_total{tenant="bob"} 2
"""


def test_collector_samples_in_exposition_and_snapshot():
    reg = MetricsRegistry()
    reg.register_collector(
        lambda: [("cache_size", "gauge", "AOT cache", {"graph": "g"}, 7)])
    assert 'cache_size{graph="g"} 7' in to_prometheus(reg)
    assert reg.snapshot()["cache_size"]["series"] == {"graph=g": 7}


def test_event_log_jsonl(tmp_path):
    path = tmp_path / "events.jsonl"
    log = EventLog(path)
    log.emit("batch", live=3)
    log.emit("retry", tenants=["a"])
    assert len(log) == 2
    rows = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["kind"] for r in rows] == ["batch", "retry"]
    assert [r["seq"] for r in rows] == [0, 1] and rows[0]["live"] == 3
    log.close()


def test_request_trace_builder_tiles():
    tr = request_trace(3, "g", "bfs", t_admit=1.0, t_dispatch=1.5,
                      t_exec_start=1.6, t_exec_end=2.0, t_done=2.1, live=4)
    assert [s.name for s in tr.spans] == list(PHASES)
    for a, b in zip(tr.spans, tr.spans[1:]):
        assert a.t1 == b.t0
    assert tr.total_s == pytest.approx(1.1)
    assert tr.span("execute").attrs == {"live": 4}


# ---------------------------------------------------------------------------
# LevelTrace: bit-identity, agreement, trace discipline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", CODECS)
def test_bitexact_on_off(graph_data, codec):
    g, roots = graph_data
    on, off = g.session(_cfg(codec)), g.session(_cfg(codec, telemetry=False))
    for arg in (int(roots[0]), roots[:4]):
        a, b = on.bfs(arg), off.bfs(arg)
        assert (np.asarray(a.level) == np.asarray(b.level)).all()
        assert (np.asarray(a.pred) == np.asarray(b.pred)).all()
        assert b.trace is None
    assert off.last_trace() is None


@pytest.mark.parametrize("codec", CODECS)
def test_trace_agrees_with_recomputation(graph_data, codec):
    g, roots = graph_data
    sess = g.session(_cfg(codec))
    out = sess.bfs(int(roots[0]))
    tr = sess.last_trace()
    assert isinstance(tr, LevelTrace) and out.trace is tr
    assert tr.codec == codec and tr.grid == (1, 1)
    level = np.asarray(out.level)[:N]
    bc = np.bincount(level[level >= 0])
    assert tr.n_levels == len(bc)
    assert [int(f) for f in tr.frontier] == [int(x) for x in bc]
    assert tr.total_scanned == out.edges_scanned
    wb = sess.engine.codec.wire_bytes(g.grid)   # P = 1: global == per-device
    assert all(int(w) == wb for w in tr.wire_bytes)
    assert (tr.direction == 0).all()            # pure top-down session
    assert tr.frontier_dev.shape == (1, tr.n_levels)
    assert (tr.folded >= 0).all() and tr.folded_dev.shape == \
        (1, tr.n_levels)


def test_batched_trace_per_root(graph_data):
    g, roots = graph_data
    sess = g.session(_cfg())
    out = sess.bfs(roots[:4])
    traces = sess.last_trace()
    assert isinstance(traces, tuple) and len(traces) == 4
    assert out.trace is traces
    levels = np.asarray(out.level)
    for b, tr in enumerate(traces):
        lv = levels[b][:N]
        bc = np.bincount(lv[lv >= 0])
        assert [int(f) for f in tr.frontier] == [int(x) for x in bc]


def test_no_retrace_on_repeat_sweeps(graph_data):
    g, roots = graph_data
    sess = g.session(_cfg())
    sess.bfs(roots[:4])
    count = sess.engine.trace_count
    sess.bfs(roots[4:])                # same B: AOT cache hit
    sess.bfs(roots[:4])
    assert sess.engine.trace_count == count


def test_direction_trace_matches_directions_output(graph_data):
    g, roots = graph_data
    sess = g.session(_cfg(direction=True))
    out = sess.bfs(int(roots[0]))
    tr = sess.last_trace()
    dirs = np.asarray(out.directions)
    assert [int(d) for d in tr.direction] == \
        [int(d) for d in dirs[:tr.n_levels]]


def _np_map_passes(front, col_off, ncl, chunk):
    """One top-down level's slot-search window passes, counted by running
    the window loop of each tile in numpy: per chunk the widest tile's
    count (what the vmapped loop runs), summed over the level's chunks."""
    from repro.core.frontier import TILE, WINDOW, pick_tile

    deg = col_off[front + 1] - col_off[front]
    cumul = np.zeros(ncl + 1, np.int64)
    cumul[1:len(front) + 1] = np.cumsum(deg)
    cumul[len(front) + 1:] = cumul[len(front)]
    total = int(cumul[-1])
    cc = np.where(cumul < total, cumul, np.iinfo(np.int32).max)
    tile, window = pick_tile(chunk, TILE), min(WINDOW, ncl + 1)
    passes = 0
    for start in range(0, total, chunk):
        widest = 0
        for g0 in range(start, start + chunk, tile):
            k0 = max(int(np.searchsorted(cc, g0, side="right")) - 1, 0)
            at, n = k0 + 1, 0
            while at < ncl + 1 and cc[at] <= g0 + tile - 1:
                at, n = at + window, n + 1
            widest = max(widest, n)
        passes += widest
    return passes


@pytest.mark.parametrize("direction", [False, True],
                         ids=["topdown", "direction"])
def test_map_passes_counts_the_slot_search(direction):
    """`map_passes` is the top-down map's window passes per level, as a
    numpy run of the window loop counts them, 0 on bottom-up levels; with
    telemetry off the level's chunk loop carries no counter."""
    from repro.core import frontier as F
    from repro.core.types import Grid2D
    from repro.graphgen import rmat_edges

    scale, chunk = 10, 512
    edges = np.asarray(rmat_edges(jax.random.key(4), scale, 4))
    cfg = BFSConfig(grid=(1, 1), edge_chunk=chunk, telemetry=True,
                    direction=direction)
    g = DistGraph.from_edges(edges, cfg, n=1 << scale)
    root = int(np.flatnonzero(np.bincount(edges[0]) > 0)[0])
    out = g.session(cfg).bfs(root)
    tr = out.trace
    col_off = np.asarray(g.csc.col_off)[0, 0]
    ncl = g.grid.n_cols_local
    level = np.asarray(out.level)
    want = [0 if d else _np_map_passes(np.flatnonzero(level == k), col_off,
                                       ncl, chunk)
            for k, d in enumerate(tr.direction)]
    assert [int(p) for p in tr.map_passes] == want
    if direction:
        assert tr.direction.any()           # some levels ran bottom-up
    else:
        assert max(want) >= 2               # a chunk took two passes
    assert tr.levels()[1]["map_passes"] == want[1]

    grid = Grid2D.for_vertices(64, 1, 1)
    args = (jnp.zeros(65, jnp.int32), jnp.zeros(8, jnp.int32),
            jnp.zeros(64, bool), jnp.zeros(64, jnp.int32),
            jnp.zeros(64, jnp.int32), jnp.zeros(64, jnp.int32),
            jnp.int32(1), jnp.int32(1))

    def carry(count):
        jaxpr = jax.make_jaxpr(lambda *a: F.expand_frontier(
            *a, grid=grid, i=0, j=0, edge_chunk=64,
            count_passes=count))(*args)
        loop, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "while"]
        return len(loop.outvars)

    assert carry(True) == carry(False) + 1


@pytest.mark.parametrize("src_grid", [(1, 1), (2, 2)],
                         ids=["same_grid", "regrid"])
def test_import_trace_blanks_channels_the_snapshot_lacks(graph_data,
                                                         src_grid):
    """A telemetry snapshot written before `map_passes` existed resumes:
    the channel starts at zero, the others import as before (same grid:
    as saved; a smaller grid: work sums on device (0, 0))."""
    from repro.obs import TRACE_CHANNELS

    g, _ = graph_data
    eng = g.engine_for(_cfg())
    L = int(eng.max_levels)
    rng = np.random.default_rng(0)
    old = {c: rng.integers(0, 9, src_grid + (L,)).astype(
               np.uint32 if c in ("scanned", "wire") else np.int32)
           for c in TRACE_CHANNELS if c != "map_passes"}
    old["k"] = np.full(src_grid, 3, np.int32)
    tr = eng._import_trace(old, None, 3)
    assert set(tr) == set(TRACE_CHANNELS) | {"k"}
    assert tr["map_passes"].shape == (1, 1, L)
    assert tr["map_passes"].dtype == np.int32 and not tr["map_passes"].any()
    np.testing.assert_array_equal(tr["frontier"][0, 0], old["frontier"][0, 0])
    np.testing.assert_array_equal(
        tr["scanned"][0, 0], old["scanned"].sum(axis=(0, 1), dtype=np.int64))
    assert int(tr["k"][0, 0]) == 3


def test_value_fold_traces_for_algos(graph_data):
    """cc / sssp / multi_bfs fold VALUES: per-level wire bytes follow the
    count-proportional formula wb + 4*folded (P = 1)."""
    from repro.dist.exchange import FOLD_CODECS

    g, roots = graph_data
    sess = g.session(_cfg())
    for out in (sess.connected_components(),   # NB: cc hints codec "bitmap"
                sess.sssp(int(roots[0])),
                sess.multi_bfs(roots[:3], k=2)):
        tr = out.trace
        assert isinstance(tr, LevelTrace) and tr.n_levels >= 1
        wb = FOLD_CODECS[tr.codec](g.grid).wire_bytes(g.grid)
        assert all(int(w) == wb + 4 * int(f)
                   for w, f in zip(tr.wire_bytes, tr.folded))


# ---------------------------------------------------------------------------
# Serve layer: spans, per-tenant fault attribution, reset-safety, shims
# ---------------------------------------------------------------------------

def _server(g, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("window_s", 0.01)
    return GraphServer({"g": g}, ServeConfig(**kw))


def test_serve_request_trace_spans(graph_data):
    g, roots = graph_data
    with _server(g) as srv:
        tickets = [srv.bfs("g", int(r), tenant="alice") for r in roots[:3]]
        results = [t.result(timeout=120) for t in tickets]
    for res in results:
        assert res.ok
        tr = res.trace
        assert [s.name for s in tr.spans] == list(PHASES)
        for a, b in zip(tr.spans, tr.spans[1:]):
            assert a.t1 == b.t0                  # spans tile wall-to-wall
        assert res.queued_s == pytest.approx(
            tr.dur_s("queue") + tr.dur_s("coalesce"))
        assert tr.span("execute").attrs["live"] >= 1


def test_serve_per_tenant_retry_attribution(graph_data):
    g, roots = graph_data
    with _server(g) as srv:
        t = srv.bfs("g", int(roots[0]), tenant="alice",
                    injector=FaultInjector({0: RuntimeError}))
        assert t.result(timeout=120).ok        # transient: retry absorbed
        runner = srv._workers["g"].runner
        assert runner.retries_by.get("alice", 0) >= 1
        retry_c = srv.metrics.counter("fault_retries_total",
                                      labelnames=("graph", "tenant"))
        assert retry_c.value_for(("g", "alice")) >= 1
        snap = srv.metrics_snapshot()
        assert snap["runners"]["g"]["retries_by_tenant"]["alice"] >= 1
        assert any(e["kind"] == "retry" for e in srv.events.to_list())


def test_serve_metrics_reset_safe_across_restarts(graph_data):
    """A new GraphServer over the same resident graph starts with clean
    counters (per-server registry), and reset_metrics() re-zeroes a live
    one -- including the runner's retry attribution."""
    g, roots = graph_data
    with _server(g) as srv:
        srv.bfs("g", int(roots[0]), tenant="alice",
                injector=FaultInjector({0: RuntimeError})).result(timeout=120)
        assert srv.accounting.tenants["alice"].queries == 1
        srv.reset_metrics()
        assert srv.accounting.tenants == {}
        assert srv._workers["g"].runner.retries_by == {}
        assert "serve_admitted_total" not in srv.prometheus()
    with _server(g) as srv2:
        assert srv2.accounting.tenants == {}
        assert srv2.metrics_snapshot()["runners"]["g"]["retries"] == 0
        t = srv2.bfs("g", int(roots[1]), tenant="bob")
        assert t.result(timeout=120).ok
        assert set(srv2.accounting.tenants) == {"bob"}
        assert 'serve_admitted_total{tenant="bob"} 1' in srv2.prometheus()


def test_deprecated_stats_surfaces_warn_and_agree(graph_data):
    g, roots = graph_data
    with warnings.catch_warnings():
        warnings.simplefilter("error")         # no warning at call-free use
        with _server(g) as srv:
            srv.bfs("g", int(roots[0])).result(timeout=120)
            srv.drain()
    with pytest.warns(DeprecationWarning, match="metrics_snapshot"):
        legacy = srv.stats()
    assert legacy == srv.metrics_snapshot()
    with pytest.warns(DeprecationWarning, match="cache_stats"):
        legacy_cache = g.aot_cache_stats()
    assert legacy_cache == g.cache_stats()


def test_step_runner_reset_stats():
    runner = StepRunner(lambda st, b: (st, None),
                        injector=FaultInjector({0: RuntimeError}))
    runner.run(0, [None, None], labels=("alice",))
    assert runner.retries == 1 and runner.retries_by == {"alice": 1}
    runner.reset_stats()
    assert runner.retries == 0 and runner.retries_by == {}
    assert runner.watchdog.lat == []
