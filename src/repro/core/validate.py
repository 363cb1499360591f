"""Graph500-style BFS output validation + TEPS accounting (paper sec. 4).

Checks (on the global (level, pred) result and the input edge list):
  1. root: level[root] == 0 and pred[root] == root;
  2. reachability consistency: level[v] >= 0  <=>  pred[v] >= 0;
  3. tree: for every visited v != root, pred[v] is visited and
     level[v] == level[pred[v]] + 1;
  4. tree edges exist in the graph;
  5. every input edge (u, v) with both endpoints visited satisfies
     |level[u] - level[v]| <= 1, and no edge joins visited to unvisited
     (the component is fully explored).

TEPS = (# input edge tuples within the traversed component) / time, with the
harmonic mean across the 64 random roots, as in the paper.
"""
from __future__ import annotations

import numpy as np


def _edge_set(edges):
    u = np.asarray(edges[0], dtype=np.int64)
    v = np.asarray(edges[1], dtype=np.int64)
    return u, v


def edge_keys(edges, n: int) -> np.ndarray:
    """Sorted int64 keys u * (n + 1) + v of the edge list: rule 4's lookup
    table.  Sort once and pass it to every `validate_bfs` of one graph."""
    u, v = _edge_set(edges)
    key = u * (n + 1) + v
    key.sort()
    return key


def validate_bfs(edges, level, pred, root: int, keys=None) -> None:
    """Raise AssertionError with a message on any rule violation.

    keys: `edge_keys(edges, len(level))`, sorted once per graph; computed
    here when omitted."""
    level = np.asarray(level)
    pred = np.asarray(pred)
    u, v = _edge_set(edges)

    assert level[root] == 0, f"level[root]={level[root]}"
    assert pred[root] == root, f"pred[root]={pred[root]}"

    vis = level >= 0
    assert ((pred >= 0) == vis).all(), "pred/level visited sets differ"

    w = np.flatnonzero(vis)
    w = w[w != root]
    p = pred[w]
    assert (level[p] >= 0).all(), "parent not visited"
    assert (level[w] == level[p] + 1).all(), "tree edge not level+1"

    # tree edges must exist in the graph (directed edge p -> w or w -> p;
    # the input is symmetrised so checking one direction suffices)
    key = edge_keys(edges, level.shape[0]) if keys is None else keys
    tkey = p.astype(np.int64) * (level.shape[0] + 1) + w
    pos = np.searchsorted(key, tkey)
    pos = np.clip(pos, 0, key.shape[0] - 1)
    assert (key[pos] == tkey).all(), "tree edge not in graph"

    both = vis[u] & vis[v]
    assert (np.abs(level[u[both]] - level[v[both]]) <= 1).all(), \
        "graph edge spans > 1 level"
    cross = vis[u] ^ vis[v]
    assert not cross.any(), "edge joins visited and unvisited (incomplete BFS)"


def count_component_edges(edges, level) -> int:
    """# directed input edge tuples with endpoints inside the component.
    Graph500 counts undirected input edges; our edge list is symmetrised, so
    divide by 2."""
    level = np.asarray(level)
    u, v = _edge_set(edges)
    return int(((level[u] >= 0) & (level[v] >= 0)).sum()) // 2


def teps(edges, level, seconds: float) -> float:
    return count_component_edges(edges, level) / max(seconds, 1e-12)


def harmonic_mean(xs) -> float:
    xs = np.asarray(xs, dtype=np.float64)
    return float(len(xs) / np.sum(1.0 / np.maximum(xs, 1e-30)))
