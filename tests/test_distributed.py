"""Multi-device integration tests.

These spawn subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count
so the main pytest process keeps seeing exactly one device (required by the
smoke tests and benches).
"""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)


def _run(script, *args, timeout=600):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "dist", script), *map(str, args)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert r.stdout.strip().endswith("OK"), r.stdout


@pytest.mark.slow
def test_bfs2d_grid_2x4():
    _run("run_bfs2d.py", 2, 4)


@pytest.mark.slow
def test_bfs2d_grid_4x2_bitmap_fold():
    _run("run_bfs2d.py", 4, 2, 9, 8, "bitmap")


@pytest.mark.slow
def test_bfs2d_grid_2x2_delta_fold():
    _run("run_bfs2d.py", 2, 2, 9, 8, "delta")


@pytest.mark.slow
def test_dist_suite_1d_direction_spmm():
    _run("run_dist_suite.py", 2, 4)


@pytest.mark.slow
def test_session_api_grid_2x2():
    _run("run_session.py", 2, 2)


def test_single_process_placement_is_sharded():
    _run("run_placement.py")
