"""The session's host spans and counters (DESIGN.md sec. 13).

  * `GraphSession.bfs` runs under the profiler span `repro/session/bfs`,
    with `dispatch` and `assemble` inside it on every call and `compile`
    only on an executable cache miss;
  * `DistGraph.stats()` holds host planning seconds by phase and the AOT
    cache's counters, and `compiles` / `compile_s` move on a miss only.
"""
import glob

import jax
import numpy as np
import pytest

from repro.api import BFSConfig, DistGraph
from repro.graphgen import rmat_edges

SCALE, EF = 8, 8
N = 1 << SCALE
PREFIX = "repro/session/"


@pytest.fixture(scope="module")
def edges():
    return np.asarray(rmat_edges(jax.random.key(0), SCALE, EF))


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    return sorted(spans)


def test_bfs_spans(edges, tmp_path):
    sess = DistGraph.from_edges(
        edges, BFSConfig(grid=(1, 1), edge_chunk=512), n=N).session()
    root = int(np.flatnonzero(np.bincount(edges[0], minlength=N))[0])
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            jax.block_until_ready(sess.bfs(root).level)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    calls = [s for s in spans if s[2] == PREFIX + "bfs"]
    assert len(calls) == 2

    def inside(call):
        return [n for s, e, n in spans
                if n != PREFIX + "bfs" and call[0] <= s and e <= call[1]]

    first, second = inside(calls[0]), inside(calls[1])
    assert first == [PREFIX + "dispatch", PREFIX + "compile",
                     PREFIX + "assemble"]
    assert second == [PREFIX + "dispatch", PREFIX + "assemble"]
    # every child span lies inside a search call
    assert len(spans) == 2 + len(first) + len(second)


def test_plan_and_compile_counters(edges):
    graph = DistGraph.from_edges(
        edges, BFSConfig(grid=(1, 1), edge_chunk=512), n=N)
    st = graph.stats()
    assert set(st["plan"]) == {"csc", "place"}
    assert all(v > 0 for v in st["plan"].values())
    assert st["aot"]["compiles"] == 0 and st["aot"]["compile_s"] == 0

    sess = graph.session()
    sess.bfs(0)                                  # miss: lowers + compiles
    aot = graph.stats()["aot"]
    assert aot["misses"] == 1 and aot["compiles"] == 1
    assert aot["compile_s"] > 0
    sess.bfs(1)                                  # hit
    again = graph.stats()["aot"]
    assert again["hits"] == 1
    assert (again["compiles"], again["compile_s"]) == \
        (aot["compiles"], aot["compile_s"])

    graph.session(BFSConfig(grid=(1, 1), edge_chunk=512, direction=True))
    assert graph.stats()["plan"]["csr"] > 0      # the twin, planned lazily
