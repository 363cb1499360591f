"""The DIMACS10 random geometric graph generator, the `rgg-n17.bfs` cell run
small on the CPU, and the readers of its per-layer metrics."""
from __future__ import annotations

import os
import sys
import time
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
from reference.bfs import bfs_levels, host_graph  # noqa: E402

rgg = harness.load_module(os.path.join(BENCH, "graphs", "rgg.py"),
                          "bench_graph_rgg")

CELL = "rgg-n17.bfs"


def config(scale: int, structure_seed: int = 1) -> dict:
    return {"scale": scale, "structure_seed": structure_seed,
            "radius_coefficient": 0.55}


@pytest.mark.parametrize("structure_seed", [1, 2])
def test_pairs_equal_brute_force(structure_seed):
    n = 1 << 10
    xy = np.random.default_rng(structure_seed).random((n, 2))
    r = rgg.radius(n, 0.55)
    d = xy[:, None, :] - xy[None, :, :]
    near = d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1] < r * r
    want = np.stack(np.nonzero(np.triu(near, 1)))
    got = rgg.geometric_pairs(xy, r)
    order = np.lexsort(got[::-1])
    np.testing.assert_array_equal(got[:, order], want)
    _, pairs, _ = rgg.structure(config(10, structure_seed), 4)
    np.testing.assert_array_equal(np.unique(pairs, axis=1), want)


def test_edges_symmetric_without_self_loops():
    edges, n, keys = rgg.generate(config(12), 2**31 + 77, 65)
    assert edges.dtype == np.int32 and edges.shape[0] == 2
    assert n == 1 << 12 and keys.shape == (65,)
    assert not (edges[0] == edges[1]).any()
    fwd = set(zip(edges[0].tolist(), edges[1].tolist()))
    assert fwd == set(zip(edges[1].tolist(), edges[0].tolist()))
    assert len(fwd) == edges.shape[1]               # each edge once a way
    assert len(set(keys.tolist())) == keys.size
    deg = np.bincount(edges[0], minlength=n)
    assert (deg[keys] > 0).all()


def test_each_seed_same_structure_other_labels():
    cfg = config(12)
    a_edges, n, a_keys = rgg.generate(cfg, 5, 65)
    b_edges, _, b_keys = rgg.generate(cfg, 2**33 + 5, 65)
    again, _, again_keys = rgg.generate(cfg, 5, 65)
    np.testing.assert_array_equal(a_edges, again)
    np.testing.assert_array_equal(a_keys, again_keys)
    assert not np.array_equal(a_edges, b_edges)
    assert a_edges.shape == b_edges.shape
    ga, gb = host_graph(a_edges, n), host_graph(b_edges, n)
    np.testing.assert_array_equal(np.sort(ga.degree), np.sort(gb.degree))
    np.testing.assert_array_equal(ga.degree[a_keys], gb.degree[b_keys])
    for ka, kb in zip(a_keys[:4], b_keys[:4]):
        la, lb = bfs_levels(ga, int(ka)), bfs_levels(gb, int(kb))
        np.testing.assert_array_equal(np.bincount(la + 1),
                                      np.bincount(lb + 1))
    with pytest.raises(ValueError):
        rgg.generate(cfg, -1, 65)


def test_cell_graph_at_its_size():
    """The cell's configuration: n = 2^17, ~730k undirected edges, one
    giant component, every key hundreds of levels deep."""
    cell = harness.load_cell(CELL)
    assert cell.config["scale"] == 17
    assert cell.config["session"]["max_levels"] is None     # no bound
    edges, n, keys = rgg.generate(cell.config, 3, 65)
    assert n == 131072
    assert 700_000 < edges.shape[1] // 2 < 760_000
    level = bfs_levels(host_graph(edges, n), int(keys[0]))
    assert (level >= 0).sum() >= n - 8
    assert level.max() > 200


@pytest.fixture
def cpu(monkeypatch):
    import jax

    monkeypatch.setattr(harness, "device_peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    return jax.devices()[:1]


def test_deep_cell_runs_correct_on_the_cpu(cpu):
    """The cell cut to 2^13 points (every key 66-96 levels deep, past the
    64 a search once stopped at) answers every search correctly."""
    cell = harness.load_cell(CELL)
    cell.config = dict(cell.config, scale=13)
    cell.config["session"] = dict(cell.config["session"], edge_chunk=1024)
    result = harness.run_cell(cell, cpu, 2**31 + 11, 0.5, False,
                              time.perf_counter())
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"teps", "setup_s"}


def _view(levels, op_s, busy_s=2.0):
    searches = [types.SimpleNamespace(level=np.asarray(lv)) for lv in levels]
    trace = types.SimpleNamespace(busy_s=busy_s, op_s=op_s)
    per_root = [{} for lv in levels for _ in lv]
    return types.SimpleNamespace(
        trace=trace, per_root=per_root,
        window=types.SimpleNamespace(searches=searches))


def read(metric, view):
    mod = harness.load_module(os.path.join(BENCH, "metrics", metric + ".py"),
                              "bench_metric_" + metric)
    return mod.read(view)


def test_level_ms_is_busy_over_loop_iterations():
    # two searches, deepest levels 3 and 5: 4 + 6 iterations
    view = _view([[[0, 1, 2, 3, -1]], [[5, 4, 0, 1, 2]]], {})
    assert read("level_ms", view) == pytest.approx(2000.0 / 10)
    assert read("level_ms", _view([[[0, 1]]], {}, busy_s=0.0)) is None
    untraced = _view([[[0, 1]]], {})
    untraced.trace = None
    assert read("level_ms", untraced) is None


def test_workload_ms_reads_its_scope_only():
    ops = {"repro/expand/workload/cumsum": 0.004,
           "repro/expand/workload/transpose": 0.002,
           "repro/expand/workloads": 1.0,
           "repro/expand/map/gather": 1.0}
    view = _view([[[0, 1]], [[1, 0]]], ops)
    assert read("workload_ms", view) == pytest.approx(3.0)
    # a program without the scope (the parent of this metric) reads nothing
    assert read("workload_ms", _view([[[0, 1]]],
                                     {"repro/expand/map/gather": 1.0})) \
        is None
