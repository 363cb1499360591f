"""The harness driven on the CPU at a tiny size: a sound run is correct, and
the check fails a run whose timed path is broken underneath.

The look for a chip is steered here: the tests hand `run_cell` the CPU
device and a stand-in row of peaks.  `bench/run.py` itself refuses the CPU,
which the last tests check.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import control  # noqa: E402
import harness  # noqa: E402

TINY_PEAKS = {"hbm_bytes_per_s": 819e9}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def tiny(workload: str, scale: int = 8) -> harness.Cell:
    cell = harness.load_cell(workload)
    cell.config = dict(cell.config, scale=scale)
    cell.config["session"] = dict(cell.config["session"], edge_chunk=1024)
    return cell


@pytest.fixture
def cpu(monkeypatch):
    import jax

    monkeypatch.setattr(harness, "device_peaks", lambda kind: TINY_PEAKS)
    return jax.devices()[:1]


def run(cell, devices, seed=3, seconds=0.5):
    return harness.run_cell(cell, devices, seed, seconds, False,
                            time.perf_counter())


@pytest.mark.parametrize("workload", ["g500-s20.bfs", "g500-s20-do.bfs"])
def test_sound_run_is_correct(workload, cpu, capsys):
    result = run(tiny(workload), cpu, seed=2**31 + 77)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"teps", "setup_s"}
    assert result["metrics"]["teps"]["value"] > 0
    assert result["metrics"]["teps"]["unit"] == "edges/s"
    assert list(result)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in result["checks"].values())
    harness.report(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["device"]["platform"] == "cpu"
    assert err.strip().splitlines()[-1] == "check unanswered: 0 (limit 0)"


def test_batched_traffic_is_checked_root_by_root(cpu):
    cell = tiny("g500-s20.bfs")
    cell.traffic = dict(cell.traffic, batch=2)
    result = run(cell, cpu)
    assert result["correct"] is True
    assert result["attempted"] % 2 == 0 and result["attempted"] > 0


def test_same_seed_same_work(cpu):
    cell = tiny("g500-s20.bfs")
    a = harness.plan(cell, cpu, 5)
    b = harness.plan(cell, cpu, 5)
    np.testing.assert_array_equal(a.edges, b.edges)
    np.testing.assert_array_equal(a.keys, b.keys)
    assert a.warm_keys[0] not in set(a.keys.tolist())


def test_windows_wrap_round_the_keys():
    keys = np.arange(5)
    gen = harness.key_batches(keys, 2)
    got = [next(gen).tolist() for _ in range(4)]
    assert got == [[0, 1], [2, 3], [4, 0], [1, 2]]


def test_fault_state_unchanged(cpu, monkeypatch):
    """A level step that returns its state unchanged ends every search at
    its root."""
    import jax.numpy as jnp
    from repro.algos import bfs

    def stuck(engine, graph, st, *, i, j):
        return st, jnp.int32(0), jnp.int32(0), {}

    monkeypatch.setattr(bfs, "topdown_step", stuck)
    result = run(tiny("g500-s20.bfs"), cpu)
    assert result["correct"] is False
    assert result["checks"]["level_mismatch"]["value"] > 0


def test_fault_answer_altered(cpu, monkeypatch):
    """One level changed where the program produces it."""
    from repro.algos.bfs import BFSLevelsProgram

    finalize = BFSLevelsProgram.finalize

    def altered(self, engine, st, i, j):
        level, pred, lvl = finalize(self, engine, st, i, j)
        return level.at[1].add(1), pred, lvl

    monkeypatch.setattr(BFSLevelsProgram, "finalize", altered)
    result = run(tiny("g500-s20.bfs"), cpu)
    assert result["correct"] is False
    assert result["checks"]["level_mismatch"]["value"] >= \
        result["attempted"]


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_control_is_not_correct(seed):
    out = control.control_checks(tiny("g500-s20.bfs", scale=9), seed, 4)
    assert out["correct"] is False
    assert out["checks"]["level_mismatch"]["value"] > 0
    assert out["checks"]["pred_violations"]["value"] > 0


def test_fault_exchange_left_out():
    """On a 2x2 grid of host devices, a fold that never leaves its chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, os.path.join(HERE,
                                                        "fault_2x2.py")],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "exchange_left_out": False}


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "g500-s20.bfs", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_needs_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s20.bfs",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_benchmark_file_names_what_exists():
    """Every name in BENCHMARK.json is well formed and finds its files."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg["reduced"]
        assert os.path.exists(os.path.join(BENCH, "graphs",
                                           cfg["generator"] + ".py"))
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert w["chips"] == json.load(open(os.path.join(
            ROOT, configs[w["config"]]["file"])))["chips"]
    names = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        assert set(m.get("workloads", names)) <= set(names)
