"""The per-layer readers of the program's own scopes and spans, on traces
recorded on TPU v5e chips with them by `record_cell_trace.py`, each cell
cut to scale 10, two searches: `data/trace_do_s10.xplane.pb` (the
g500-s20-do.bfs cell, one chip) and `data/trace_2x2_s10.xplane.pb.gz`
(g500-s20-2x2.bfs, four chips, gzipped).  The older
`data/trace_s10.xplane.pb` was recorded before the program had
`repro/loop`, `repro/expand/bottomup` or any host span: there the new
readers read nothing, and do not raise.
"""
from __future__ import annotations

import gzip
import importlib.util
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import program_spans as PS  # noqa: E402
import trace_reduce as T  # noqa: E402

DATA = os.path.join(HERE, "data")
SESSION = "repro/session/"


def reader(name):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def view(workload, summary, searches=2):
    return harness.RunView(cell=harness.load_cell(workload), chips=1,
                           peaks={"hbm_bytes_per_s": 819e9}, setup_s=0.0,
                           window=None, per_root=[{}] * searches,
                           trace=summary)


def recorded(tag, path=None):
    path = path or os.path.join(DATA, f"trace_{tag}.xplane.pb")
    with open(os.path.join(DATA, f"programs_{tag}.json")) as f:
        programs = json.load(f)
    trace = T.load(path)
    return (trace, T.reduce(trace, sorted(trace.devices), programs),
            PS.program_spans(path))


@pytest.fixture(scope="module")
def do_trace():
    return recorded("do_s10")


@pytest.fixture(scope="module")
def grid_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "trace_2x2_s10.xplane.pb"
    with gzip.open(os.path.join(DATA, "trace_2x2_s10.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return recorded("2x2_s10", str(path))


@pytest.fixture(scope="module")
def old_summary():
    with open(os.path.join(DATA, "programs.json")) as f:
        programs = json.load(f)
    trace = T.load(os.path.join(DATA, "trace_s10.xplane.pb"))
    return T.reduce(trace, [0], programs)


def test_session_spans_nest_in_each_search(do_trace):
    trace, _, spans = do_trace
    searches = [(s, e) for s, e, n in trace.spans if n == "bench/search"]
    calls = [(s, e) for s, e, n in spans if n == SESSION + "bfs"]
    assert len(searches) == len(calls) == 2
    for (s, e), (cs, ce) in zip(searches, calls):
        assert s <= cs and ce <= e
        inside = [n for a, b, n in spans if cs <= a and b <= ce
                  and n != SESSION + "bfs"]
        # the program was warmed up before the window: no compile
        assert inside == [SESSION + "dispatch", SESSION + "assemble"]


def test_new_readers_read_the_program_scopes(do_trace):
    _, summary, _ = do_trace
    v = view("g500-s20-do.bfs", summary)
    loop, bottomup = reader("loop_ms")(v), reader("bottomup_ms")(v)
    expand = reader("expand_ms")(v)
    assert loop > 0 and bottomup > 0
    assert bottomup < expand
    assert loop == pytest.approx(1e3 * summary.scope_s["repro/loop"] / 2)
    # the bottom-up step's post-fold merge is the update's
    assert any(path.startswith("repro/update/") and "take_along_axis" in path
               for path in summary.op_s)


def test_expand_sub_scopes_partition_the_expand(do_trace):
    _, summary, _ = do_trace
    subs = {}
    for path, sec in summary.op_s.items():
        if path.startswith("repro/expand"):
            part = path.split("/")[2] if path.count("/") >= 2 else ""
            subs[part] = subs.get(part, 0.0) + sec
    assert {"bottomup", "exchange", "map", "filter", "mark"} <= set(subs)
    assert sum(subs.values()) == pytest.approx(
        summary.scope_s["repro/expand"], rel=1e-9)


def test_new_readers_read_nothing_without_the_scopes(old_summary):
    for name in ("loop_ms", "bottomup_ms"):
        assert reader(name)(view("g500-s20.bfs", old_summary)) is None
    assert reader("expand_ms")(view("g500-s20.bfs", old_summary)) > 0
    assert reader("loop_ms")(view("g500-s20.bfs", None)) is None


def test_span_idle_against_brute_force(do_trace):
    trace, summary, spans = do_trace
    idle = PS.span_idle_s(trace, spans, [0])
    lo, hi = [(a, b) for a, b, n in trace.spans if n == T.WINDOW_SPAN][0]
    d = trace.devices[0]
    keep = (d.ends > lo) & (d.starts < hi)
    us = np.zeros(int((hi - lo) / 1e3) + 2, bool)
    for a, b in zip(d.starts[keep], d.ends[keep]):
        us[int((max(a, lo) - lo) / 1e3):int(np.ceil((min(b, hi) - lo)
                                                     / 1e3))] = True
    want = 0.0
    for a, b, n in spans:
        if n == SESSION + "bfs":
            i, j = int((a - lo) / 1e3), int((b - lo) / 1e3)
            want += (j - i - us[i:j].sum()) / 1e6
    assert idle[SESSION + "bfs"] == pytest.approx(want, rel=0.01)
    # children lie inside the call, the call inside the window
    assert idle[SESSION + "dispatch"] + idle[SESSION + "assemble"] <= \
        idle[SESSION + "bfs"] + 1e-9
    assert idle[SESSION + "bfs"] <= summary.window_s - summary.busy_s


def test_idle_gaps_inside_a_search_carry_session_labels(do_trace):
    trace, _, spans = do_trace
    gaps = PS.idle_gaps(trace, spans, [0])
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    labels = {name for name, _ in gaps}
    assert labels & {SESSION + "dispatch", SESSION + "assemble"}
    # a gap is labelled by the innermost span: none of those inside a
    # search call falls back to the benchmark's own span
    searches = [(s, e) for s, e, n in trace.spans if n == "bench/search"]
    calls = [(s, e) for s, e, n in spans if n == SESSION + "bfs"]
    assert all(cs >= s and ce <= e
               for (s, e), (cs, ce) in zip(searches, calls))
    assert not any(name == SESSION + "bfs" for name, _ in gaps)


def test_exchange_readers_on_one_chip(do_trace, old_summary):
    _, summary, _ = do_trace
    v = view("g500-s20-2x2.bfs", summary)
    # on one chip the fold only packs its blocks; nothing is collective
    assert reader("fold_ms")(v) == pytest.approx(
        1e3 * summary.scope_s["repro/fold"] / 2)
    assert reader("collective_exposed_ms")(v) is None
    for name in ("fold_ms", "collective_exposed_ms"):
        assert reader(name)(view("g500-s20.bfs", old_summary)) is None
        assert reader(name)(view("g500-s20-2x2.bfs", None)) is None


def test_grid_readers_read_the_exchange(grid_trace):
    trace, summary, spans = grid_trace
    assert sorted(trace.devices) == [0, 1, 2, 3]
    v = view("g500-s20-2x2.bfs", summary)
    fold, loop = reader("fold_ms")(v), reader("loop_ms")(v)
    coll = reader("collective_exposed_ms")(v)
    assert fold == pytest.approx(1e3 * summary.scope_s["repro/fold"] / 2)
    assert loop == pytest.approx(1e3 * summary.scope_s["repro/loop"] / 2)
    # the fold's all-to-all and the frontier total's psum are the bulk of
    # the collectives, and of their scopes
    a2a = summary.op_s["repro/fold/all_to_all"]
    psum = summary.op_s["repro/loop/psum"]
    assert a2a > 0.5 * summary.scope_s["repro/fold"]
    assert psum > 0.5 * summary.scope_s["repro/loop"]
    assert coll >= 1e3 * (a2a + psum) / 2
    assert coll < fold + loop + reader("expand_ms")(v)
    # what the summary's own count misses on the chip's names
    assert 1e3 * summary.collective_exposed_s / 2 < 0.01 * coll
    calls = [n for _, _, n in spans if n == SESSION + "bfs"]
    assert len(calls) == 2


@pytest.mark.parametrize("path,collective", [
    ("repro/fold/all_to_all", True),
    ("repro/loop/psum", True),
    ("repro/expand/exchange/all_gather", True),
    ("jit_counted:all-reduce", True),
    ("jit_counted:all-gather-start", True),
    ("repro/fold/jit(take_along_axis)/gather", False),
    ("repro/update/scatter", False),
    ("jit_counted:fusion", False),
])
def test_collective_exposed_reads_what_no_op_hides(path, collective):
    summary = T.TraceSummary(window_s=1.0, busy_s=0.9, scope_s={},
                             op_s={path: 0.004, "repro/expand/gather": 0.5},
                             collective_s=0.0, collective_exposed_s=0.0,
                             gaps=[])
    got = reader("collective_exposed_ms")(view("g500-s20-2x2.bfs", summary))
    assert got == (pytest.approx(2.0) if collective else None)


def test_each_cell_reads_the_layers_it_runs():
    def per_layer(workload):
        cell = harness.load_cell(workload)
        return cell, {m["name"] for m in cell.metrics["per_layer"]}

    grid, names = per_layer("g500-s20-2x2.bfs")
    assert grid.chips == 4 and grid.config["session"]["grid"] == [2, 2]
    assert {"fold_ms", "collective_exposed_ms", "loop_ms", "expand_ms",
            "device_idle_share"} <= names
    assert "bottomup_ms" not in names
    for workload in ("g500-s20.bfs", "g500-s20-do.bfs"):
        cell, names = per_layer(workload)
        assert cell.chips == 1
        assert not names & {"fold_ms", "collective_exposed_ms", "loop_ms"}
        assert ("bottomup_ms" in names) == cell.config["session"]["direction"]
