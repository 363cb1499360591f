"""The reduction from a profiler trace to the per-layer numbers.

The interval arithmetic is checked against brute force on random intervals;
the whole reduction against a small trace recorded on one TPU v5e chip
(`data/trace_s10.xplane.pb`, made by `record_trace.py`: the g500-s20.bfs
cell cut to scale 10, two searches).
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce as T  # noqa: E402

DATA = os.path.join(HERE, "data")


def random_intervals(seed, k=60, span=1000):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, span, k).astype(np.float64)
    return s, s + rng.integers(0, 80, k)


def brute_cover(s, e, span=1100):
    grid = np.zeros(span, bool)
    for a, b in zip(s, e):
        grid[int(a):int(b)] = True
    return grid


@pytest.mark.parametrize("seed", range(4))
def test_union_and_cover(seed):
    s, e = random_intervals(seed)
    u = T.union(s, e)
    assert (np.diff(u[:, 0]) > 0).all() and (u[1:, 0] > u[:-1, 1]).all()
    grid = brute_cover(s, e)
    assert T.covered(u, 0, 1100) == grid.sum()
    assert T.covered(u, 200, 700) == grid[200:700].sum()


@pytest.mark.parametrize("seed", range(4))
def test_overlap_each(seed):
    s, e = random_intervals(seed)
    qs, qe = random_intervals(seed + 100, k=20)
    grid = brute_cover(s, e)
    got = T.overlap_each(qs, qe, T.union(s, e))
    want = [grid[int(a):int(b)].sum() for a, b in zip(qs, qe)]
    np.testing.assert_array_equal(got, want)


def test_self_times_of_nested_events():
    # a loop [0, 100) holding ops [10, 30) and [40, 90), the second holding
    # [50, 60); then a lone op [100, 110)
    starts = np.array([0, 10, 40, 50, 100], np.float64)
    ends = np.array([100, 30, 90, 60, 110], np.float64)
    np.testing.assert_array_equal(T.self_times(starts, ends),
                                  [30, 20, 40, 10, 10])


def test_names_and_scopes():
    hlo = ("HloModule jit_counted, is_scheduled=true\n"
           "  %fusion.3 = s32[8]{0} fusion(%p), kind=kLoop, "
           "metadata={op_name=\"jit(counted)/while/body/closed_call/"
           "repro/expand/while/body/gather\" stack_frame_id=3}\n"
           "  ROOT %all-to-all.1 = s32[4]{0} all-to-all(%x), "
           "metadata={op_name=\"jit(counted)/repro/fold/all_to_all\"}\n")
    module, names = T.op_names(hlo)
    assert module == "jit_counted"
    assert names == {
        "fusion.3": "jit(counted)/while/body/closed_call/repro/expand/"
                    "while/body/gather",
        "all-to-all.1": "jit(counted)/repro/fold/all_to_all"}
    assert T.scope_of(names["fusion.3"]) == "repro/expand"
    assert T.op_path(names["fusion.3"]) == "repro/expand/gather"
    assert T.is_collective("all-to-all.1")
    assert T.is_collective("all-gather-start.2")
    assert not T.is_collective("fusion.3")


@pytest.fixture(scope="module")
def fixture_trace():
    path = os.path.join(DATA, "trace_s10.xplane.pb")
    with open(os.path.join(DATA, "programs.json")) as f:
        programs = json.load(f)
    return T.load(path), programs


def test_recorded_trace_has_what_the_reduction_reads(fixture_trace):
    trace, programs = fixture_trace
    assert 0 in trace.devices
    names = [s[2] for s in trace.spans]
    assert names.count("bench/window") == 1
    assert names.count("bench/search") == 2
    assert names.count("bench/copy_out") == 2
    d = trace.devices[0]
    assert d.starts.size > 1000 and (np.diff(d.starts) >= 0).all()
    (module,) = programs
    assert module in set(d.module)


def test_recorded_trace_reduces(fixture_trace):
    trace, programs = fixture_trace
    s = T.reduce(trace, [0], programs)
    lo, hi = [(a, b) for a, b, n in trace.spans if n == "bench/window"][0]
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    assert 0 < s.busy_s < s.window_s
    # busy by brute force over the op intervals, in microseconds
    d = trace.devices[0]
    keep = (d.ends > lo) & (d.starts < hi)
    us = np.zeros(int((hi - lo) / 1e3) + 2, bool)
    for a, b in zip(d.starts[keep], d.ends[keep]):
        us[int((max(a, lo) - lo) / 1e3):int(np.ceil((min(b, hi) - lo)
                                                     / 1e3))] = True
    assert s.busy_s == pytest.approx(us.sum() / 1e6, rel=0.05)
    # self times partition the busy time: scopes add up to it
    assert sum(s.scope_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    assert s.scope_s["repro/expand"] > 0
    assert s.collective_s == 0 and s.collective_exposed_s == 0
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(name.startswith(("bench/", "between"))
               for name, _ in b["idle_gaps"])
    ops = [sec for _, sec in b["device_ops"]]
    assert ops == sorted(ops, reverse=True)
