"""Searches to completion on a graph deeper than the per-level record.

A default `BFSConfig` sets no level bound: the session searches a long
grid strip (`tests/dist/run_deep_bfs.py`, over 64 levels from its end) to
its last level, top-down and direction-optimised, on one device and on a
2x2 grid of forced host devices (in a subprocess, so this process keeps
its one device).  The per-level records hold `RECORDED_LEVELS` levels
whatever the bound.
"""
import importlib.util
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SCRIPT = os.path.join(HERE, "dist", "run_deep_bfs.py")


def _deep_bfs():
    spec = importlib.util.spec_from_file_location("run_deep_bfs", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("grid", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
@pytest.mark.parametrize("mode", ["topdown", "direction"])
def test_default_config_searches_to_completion(grid, mode):
    R, C = grid
    if R * C == 1:
        _deep_bfs().check(R, C, mode == "direction")
        return
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, SCRIPT, str(R), str(C), mode],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-3000:]}"
    assert r.stdout.strip().endswith("OK"), r.stdout


def test_strip_is_deeper_than_the_record():
    import numpy as np

    from repro.algos.reference import multi_bfs_reference
    from repro.obs.trace import RECORDED_LEVELS

    mod = _deep_bfs()
    edges, n, roots = mod.strip_graph()
    assert n == mod.WIDTH * mod.LENGTH
    assert not (edges[0] == edges[1]).any()
    level = multi_bfs_reference(edges, n, [int(roots[0])])[0]
    assert (level >= 0).all()
    assert level.max() > RECORDED_LEVELS


def test_explicit_bound_keeps_its_meaning():
    """max_levels=k still stops the search after k levels."""
    import numpy as np

    from repro.algos.reference import multi_bfs_reference
    from repro.api import BFSConfig, DistGraph

    edges, n, roots = _deep_bfs().strip_graph()
    k = 10
    graph = DistGraph.from_edges(
        edges, BFSConfig(grid=(1, 1), edge_chunk=64, max_levels=k), n=n)
    level = np.asarray(graph.session().bfs(int(roots[0])).level)[:n]
    ref = multi_bfs_reference(edges, n, [int(roots[0])], max_levels=k)[0]
    np.testing.assert_array_equal(level, ref)
    assert level.max() == k
