"""The frontier a BFS level hands on is the owned rows at that level.

After every level, on every grid, fold codec and direction, each device's
carried frontier equals its owned rows with `level == lvl`, as local cols,
ascending, and the levels equal the plain reference's
(`tests/dist/run_frontier_levels.py`).  The 1x1 grid runs in this process;
the others run once per grid in a subprocess of forced host devices, so
this process keeps its one device.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SCRIPT = os.path.join(HERE, "dist", "run_frontier_levels.py")
GRIDS = [(1, 1), (1, 2), (2, 1), (2, 2)]


def _script():
    spec = importlib.util.spec_from_file_location("run_frontier_levels",
                                                  SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(R: int, C: int) -> dict:
    """{case: levels checked} of every case on an R x C grid."""
    if R * C == 1:
        return _script().check(R, C)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, SCRIPT, str(R), str(C)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-3000:]}"
    lines = r.stdout.strip().splitlines()
    assert lines[-1] == "OK", r.stdout
    return json.loads(lines[-2])


_RESULTS = {}


def _checked(R: int, C: int) -> dict:
    """`_run` once per grid; its failure fails every case of the grid."""
    if (R, C) not in _RESULTS:
        try:
            _RESULTS[R, C] = _run(R, C)
        except Exception as e:          # noqa: BLE001 -- re-raised per case
            _RESULTS[R, C] = e
    res = _RESULTS[R, C]
    if isinstance(res, Exception):
        raise res
    return res


@pytest.mark.parametrize("mode", ["topdown", "adaptive", "bottomup"])
@pytest.mark.parametrize("codec", ["list", "bitmap"])
@pytest.mark.parametrize("graph", ["rmat", "strip"])
@pytest.mark.parametrize("grid", GRIDS, ids=[f"{r}x{c}" for r, c in GRIDS])
def test_frontier_is_owned_level_ascending(grid, graph, codec, mode):
    levels = _checked(*grid)[f"{graph}/{codec}/{mode}"]
    if graph == "strip":
        from repro.obs.trace import RECORDED_LEVELS

        assert levels > RECORDED_LEVELS
    else:
        assert levels > 2
