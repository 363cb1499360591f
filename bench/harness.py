"""One run of one benchmark cell: set up, measure a window, check, report.

Everything particular to a cell is found by name: the cell in
`BENCHMARK.json` names a configuration file and a traffic file, the
configuration names its graph generator (`graphs/<generator>.py`), and each
metric the cell reports is read by `metrics/<metric>.py`.  A new cell, graph,
traffic mix or metric is a new file here; this module stays as it is.

The program is driven only through its user entry points:
`DistGraph.from_edges` plans the graph, `GraphSession.bfs` searches it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import graph500  # noqa: E402
from reference.bfs import bfs_levels, host_graph  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------------
# The cell, from BENCHMARK.json and the files it names
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: dict        # "end_to_end" / "per_layer" -> [metric entries]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    metrics = {kind: [m for m in bench[kind] if _reports(m, name)]
               for kind in ("end_to_end", "per_layer")}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, metrics=metrics)


def chip_devices(chips: int) -> list:
    """The cell's TPU devices; NoChip where JAX has none or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def device_peaks(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def use_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every compile cached, so that only a cell's first run compiles."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compiles (cache misses) from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.compiles += 1


# ----------------------------------------------------------------------------
# Set-up: graph, keys, plan, warm-up
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class Setup:
    edges: np.ndarray
    n: int
    keys: np.ndarray        # the traffic's search keys, in order
    warm_keys: np.ndarray   # keys only the warm-up uses
    graph: object           # repro DistGraph
    session: object         # repro GraphSession


def plan(cell: Cell, devices: list, seed: int) -> Setup:
    from repro.api import BFSConfig, DistGraph
    from repro.dist.compat import make_mesh

    cfg, traffic = cell.config, cell.traffic
    gen = load_module(os.path.join(BENCH, "graphs", cfg["generator"] + ".py"),
                      "bench_graph_" + cfg["generator"])
    t0 = time.perf_counter()
    n_keys, n_warm = int(traffic["keys"]), int(traffic["warmup_keys"])
    edges, n, keys = gen.generate(cfg, seed, n_keys + n_warm)
    log(f"generated n={n}, {edges.shape[1]} directed edges, "
        f"{n_keys} + {n_warm} keys in {time.perf_counter() - t0:.4f}s")
    session_cfg = dict(cfg["session"])
    grid = tuple(session_cfg.pop("grid"))
    if grid[0] * grid[1] != len(devices):
        raise ValueError(f"grid {grid} does not span the cell's "
                         f"{len(devices)} chips")
    config = BFSConfig(grid=grid, **session_cfg)
    mesh = make_mesh(grid, ("r", "c"), devices=devices)
    t0 = time.perf_counter()
    graph = DistGraph.from_edges(edges, config, n=n, mesh=mesh)
    session = graph.session()
    import jax
    jax.block_until_ready(graph.csc.row_idx)
    log(f"planned {grid[0]}x{grid[1]} in {time.perf_counter() - t0:.4f}s "
        f"(expand={config.expand_path}, fold={config.fold_path}, "
        f"exchange={session.config.exchange})")
    return Setup(edges=edges, n=n, keys=keys[:n_keys],
                 warm_keys=keys[n_keys:], graph=graph, session=session)


# ----------------------------------------------------------------------------
# The window: a closed loop of searches
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class Search:
    roots: np.ndarray       # (B,) roots of one call
    level: np.ndarray       # (B, n) as copied out
    pred: np.ndarray
    scanned: tuple          # the program's edges_scanned, one per root
    t_start: float
    t_done: float           # search finished on the device
    t_end: float            # answers on the host


def run_query(session, roots: np.ndarray):
    """One call of the traffic's query, answered on the host."""
    import jax
    from jax.profiler import TraceAnnotation

    t0 = time.perf_counter()
    with TraceAnnotation("bench/search"):
        out = session.bfs(int(roots[0]) if roots.size == 1 else roots)
        jax.block_until_ready((out.level, out.pred))
    t1 = time.perf_counter()
    with TraceAnnotation("bench/copy_out"):
        level = np.asarray(out.level).reshape(roots.size, -1)
        pred = np.asarray(out.pred).reshape(roots.size, -1)
    scanned = out.edges_scanned
    scanned = (scanned,) if roots.size == 1 else tuple(scanned)
    return Search(roots=roots, level=level, pred=pred, scanned=scanned,
                  t_start=t0, t_done=t1, t_end=time.perf_counter())


def key_batches(keys: np.ndarray, batch: int):
    """The traffic's keys in order, `batch` to a call, wrapping round."""
    i = 0
    while True:
        yield np.take(keys, np.arange(i, i + batch), mode="wrap")
        i = (i + batch) % keys.size


@dataclasses.dataclass
class Window:
    searches: list
    t_start: float
    t_end: float
    failed: int = 0
    error: str = ""


def run_window(setup: Setup, traffic: dict, seconds: float,
               max_calls: int | None = None) -> Window:
    """Closed loop, one call outstanding, until `seconds` have passed (or
    `max_calls` calls have been answered)."""
    from jax.profiler import TraceAnnotation

    if traffic.get("loop") != "closed" or traffic.get("outstanding") != 1:
        raise ValueError(f"unsupported traffic {traffic}")
    batches = key_batches(setup.keys, int(traffic.get("batch", 1)))
    searches = []
    failed, error = 0, ""
    with TraceAnnotation("bench/window"):
        t0 = time.perf_counter()
        while (time.perf_counter() - t0 < seconds
               and (max_calls is None or len(searches) < max_calls)):
            roots = next(batches)
            try:
                searches.append(run_query(setup.session, roots))
            except Exception:   # an answer that never comes fails the run
                failed, error = roots.size, traceback.format_exc()
                break
    t_end = searches[-1].t_end if searches else time.perf_counter()
    return Window(searches=searches, t_start=t0, t_end=t_end, failed=failed,
                  error=error)


# ----------------------------------------------------------------------------
# The check against the plain reference, and per-search counts
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class Checked:
    checks: dict                 # name -> {"value", "limit"}
    per_root: list               # dicts: root, input/directed edges, bytes
    seconds: float


# A traced run's window: the profiler's trace of one search at scale 20 holds
# some 360,000 device events (20 MB), so a traced run answers this many calls
# and stops.
TRACED_CALLS = 2

LIMITS = {"level_mismatch": 0, "pred_violations": 0, "unanswered": 0}


def check_window(edges: np.ndarray, n: int, window: Window) -> Checked:
    """Every answer of the window against the reference search."""
    t0 = time.perf_counter()
    g = host_graph(edges, n)
    totals = {"level_mismatch": 0, "pred_violations": 0}
    per_root = []
    for s in window.searches:
        for b, root in enumerate(s.roots):
            ref = bfs_levels(g, int(root))
            got = graph500.check_answer(g, ref, s.level[b], s.pred[b],
                                        int(root))
            for k in totals:
                totals[k] += got[k]
            per_root.append({
                "root": int(root), "scanned": s.scanned[b],
                "input_edges": graph500.component_input_edges(g, ref),
                "directed_edges": graph500.component_directed_edges(g, ref),
                "topdown_bytes": graph500.topdown_bytes(g, ref),
                **got})
    totals["unanswered"] = window.failed
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in totals.items()}
    return Checked(checks=checks, per_root=per_root,
                   seconds=time.perf_counter() - t0)


def is_correct(window: Window, checked: Checked) -> bool:
    return bool(window.searches) and all(
        c["value"] <= c["limit"] for c in checked.checks.values())


# ----------------------------------------------------------------------------
# What the metric readers see
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class RunView:
    """Everything a reader in metrics/ may use."""
    cell: Cell
    chips: int
    peaks: dict
    setup_s: float
    window: Window
    per_root: list
    trace: object = None     # trace_reduce.TraceSummary in a traced run


def read_metrics(view: RunView, entries: list) -> dict:
    out = {}
    for m in entries:
        mod = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_info(devices: list) -> dict:
    """The device as JAX reports it, with the peak memory of the fullest
    chip."""
    import jax

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


# ----------------------------------------------------------------------------
# One run
# ----------------------------------------------------------------------------

def run_cell(cell: Cell, devices: list, seed: int, seconds: float,
             traced: bool, t_process: float) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax

    counter = CompileCounter()
    peaks = device_peaks(devices[0].device_kind)
    setup = plan(cell, devices, seed)
    batch = int(cell.traffic.get("batch", 1))
    t0 = time.perf_counter()
    run_query(setup.session, next(key_batches(setup.warm_keys, batch)))
    log(f"warm-up search {time.perf_counter() - t0:.4f}s")
    compiles_setup = counter.compiles
    trace_dir = programs = None
    if traced:
        import trace_reduce
        name, ops = trace_reduce.op_names(
            setup.session.compiled_for(batch).as_text())
        programs = {name: ops}
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_process
    window = run_window(setup, cell.traffic, seconds,
                        TRACED_CALLS if traced else None)
    if traced:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace written in {time.perf_counter() - t0:.4f}s")
    log(f"compiles: {compiles_setup} in set-up, "
        f"{counter.compiles - compiles_setup} in the window")
    info = device_info(devices)
    edges, n = setup.edges, setup.n
    del setup              # the program's state goes before the reference
    summary = None
    if traced:
        t0 = time.perf_counter()
        summary = trace_reduce.reduce(trace_reduce.load_dir(trace_dir),
                                      [d.id for d in devices], programs)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t0:.4f}s")
        info["busy_s"], info["window_s"] = summary.busy_s, summary.window_s
    if window.error:
        log(f"a search failed:\n{window.error}")
    checked = check_window(edges, n, window)
    log(f"reference check of {len(checked.per_root)} answers: "
        f"{checked.seconds:.4f}s")
    for s in window.searches:
        log(f"search {s.roots.tolist()}: {s.t_done - s.t_start:.6f}s on "
            f"device, {s.t_end - s.t_start:.6f}s with copy-out")
    view = RunView(cell=cell, chips=len(devices), peaks=peaks,
                   setup_s=setup_s, window=window, per_root=checked.per_root,
                   trace=summary)
    kind = "per_layer" if traced else "end_to_end"
    metrics = read_metrics(view, cell.metrics[kind])
    if not traced:
        log(f"graph500 harmonic-mean TEPS {harmonic_teps(view)!r} over "
            f"{len(checked.per_root)} searches")
    result = {"correct": is_correct(window, checked),
              "attempted": sum(s.roots.size for s in window.searches)
              + window.failed,
              "failed": window.failed + sum(
                  1 for r in checked.per_root
                  if r["level_mismatch"] or r["pred_violations"]),
              "metrics": metrics, "device": info}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = checked.checks
    return result


def harmonic_teps(view: RunView) -> float:
    """Graph500's statistic: the harmonic mean of the per-search rates."""
    per_root_s = [(s.t_done - s.t_start) / s.roots.size
                  for s in view.window.searches for _ in s.roots]
    rates = np.array([r["input_edges"] / t
                      for r, t in zip(view.per_root, per_root_s)])
    return float(rates.size / np.sum(1.0 / rates)) if rates.size else 0.0


def report(result: dict) -> None:
    """The compared numbers last on stderr, the result last on stdout."""
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
