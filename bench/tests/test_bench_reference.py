"""The benchmark's own generator, reference search and Graph500 rules,
checked against brute force on small seeded graphs (CPU)."""
from __future__ import annotations

import collections
import importlib.util
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from reference import graph500  # noqa: E402
from reference.bfs import bfs_levels, bfs_parents, host_graph  # noqa: E402

SMALL = {"scale": 8, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
         "structure_seed": 1}


@pytest.fixture(scope="module")
def rmat():
    spec = importlib.util.spec_from_file_location(
        "bench_graph_rmat_test", os.path.join(BENCH, "graphs", "rmat.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def brute_levels(edges, n, root):
    adj = collections.defaultdict(list)
    for u, v in edges.T:
        adj[int(u)].append(int(v))
    level = [-1] * n
    level[root] = 0
    queue = collections.deque([root])
    while queue:
        u = queue.popleft()
        for v in adj[u]:
            if level[v] < 0:
                level[v] = level[u] + 1
                queue.append(v)
    return np.array(level, np.int32)


def random_graph(seed, n=200, m=300):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, (2, m)).astype(np.int32)
    return np.concatenate([e, e[::-1]], axis=1), n


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_reference_levels_match_brute_force(seed, rmat):
    edges, n = random_graph(seed)
    g = host_graph(edges, n)
    for root in np.random.default_rng(seed).choice(n, 5, replace=False):
        np.testing.assert_array_equal(bfs_levels(g, int(root)),
                                      brute_levels(edges, n, int(root)))


@pytest.mark.parametrize("seed", [5, 2**33 + 5])
def test_reference_on_rmat_matches_brute_force(seed, rmat):
    edges, n, keys = rmat.generate(SMALL, seed, 4)
    g = host_graph(edges, n)
    for root in keys:
        ref = bfs_levels(g, int(root))
        np.testing.assert_array_equal(ref, brute_levels(edges, n, int(root)))
        pred = bfs_parents(g, ref, int(root))
        assert graph500.check_answer(g, ref, ref, pred, int(root)) == {
            "level_mismatch": 0, "pred_violations": 0}


def test_max_levels_stops_short():
    edges, n = random_graph(7)
    g = host_graph(edges, n)
    full = bfs_levels(g, 0)
    depth = int(full.max())
    cut = bfs_levels(g, 0, max_levels=depth - 1)
    assert int(cut.max()) == depth - 1
    np.testing.assert_array_equal(cut[full < depth], full[full < depth])
    assert (cut[full == depth] == -1).all()


def test_generation_is_deterministic_per_seed(rmat):
    a, n, _ = rmat.generate(SMALL, 11, 0)
    b, _, _ = rmat.generate(SMALL, 11, 0)
    c, _, _ = rmat.generate(SMALL, 12, 0)
    d, _, _ = rmat.generate(SMALL, 11 + 2**32, 0)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert not np.array_equal(a, d)     # high bits of a large seed count
    assert a.shape == (2, 2 * 16 * n) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < n
    # symmetrised: each edge and its reverse
    np.testing.assert_array_equal(a[:, : a.shape[1] // 2][::-1],
                                  a[:, a.shape[1] // 2:])


@pytest.mark.parametrize("seed", [12, 2**31 + 9])
def test_seeds_relabel_one_structure(rmat, seed):
    """Another seed gives the same graph and keys under other labels: the
    searches, level by level, are the same size."""
    a, n, ka = rmat.generate(SMALL, 11, 6)
    b, _, kb = rmat.generate(SMALL, seed, 6)
    assert not np.array_equal(ka, kb)
    ga, gb = host_graph(a, n), host_graph(b, n)
    np.testing.assert_array_equal(np.sort(ga.degree), np.sort(gb.degree))
    for ra, rb in zip(ka, kb):
        la, lb = bfs_levels(ga, int(ra)), bfs_levels(gb, int(rb))
        np.testing.assert_array_equal(np.bincount(la + 1), np.bincount(lb + 1))
        assert graph500.component_input_edges(ga, la) == \
            graph500.component_input_edges(gb, lb)


def test_kronecker_quadrant_shares(rmat):
    """The top bit of src is 1 with probability C + D = 0.24."""
    cfg = dict(SMALL, scale=12)
    edges, n, _ = rmat.generate(cfg, 3, 0)
    m = edges.shape[1] // 2
    # labels are permuted, so look at degree skew instead of bits: the
    # busiest 1% of vertices hold far more than 1% of the edges
    deg = np.sort(np.bincount(edges[0, :m], minlength=n))[::-1]
    assert deg[: n // 100].sum() > 0.1 * m


def test_search_keys(rmat):
    edges, n, keys = rmat.generate(SMALL, 4, 65)
    assert len(set(keys.tolist())) == 65
    src, dst = edges
    for k in keys:
        assert ((src == k) & (dst != k)).any()
    np.testing.assert_array_equal(keys, rmat.generate(SMALL, 4, 65)[2])


def test_check_answer_counts_faults():
    edges, n = random_graph(9)
    g = host_graph(edges, n)
    root = int(edges[0, 0])
    ref = bfs_levels(g, root)
    pred = bfs_parents(g, ref, root)
    level = ref.copy()
    reached = np.flatnonzero((ref > 0))
    level[reached[:3]] += 1
    got = graph500.check_answer(g, ref, level, pred, root)
    assert got["level_mismatch"] == 3 and got["pred_violations"] == 0
    bad = pred.copy()
    bad[reached[0]] = reached[0]            # its own parent
    bad[root] = -1
    got = graph500.check_answer(g, ref, ref, bad, root)
    assert got == {"level_mismatch": 0, "pred_violations": 2}
    far = np.flatnonzero(ref < 0)
    if far.size:
        bad = pred.copy()
        bad[far[0]] = root                  # parent for an unreached vertex
        assert graph500.check_answer(g, ref, ref, bad, root)[
            "pred_violations"] == 1


def test_counts_match_brute_force():
    edges, n = random_graph(10)
    edges = np.concatenate([edges, [[3, 4], [3, 4]]], axis=1)  # a self-loop
    g = host_graph(edges, n)
    root = 3
    ref = bfs_levels(g, root)
    inside = (ref[edges[0]] >= 0) & (ref[edges[1]] >= 0)
    assert graph500.component_input_edges(g, ref) == int(inside.sum()) // 2
    assert graph500.component_directed_edges(g, ref) == int(inside.sum())
    reached = int((ref >= 0).sum())
    assert graph500.topdown_bytes(g, ref) == (
        graph500.BYTES_PER_VERTEX * reached
        + graph500.BYTES_PER_EDGE * int(inside.sum()))
