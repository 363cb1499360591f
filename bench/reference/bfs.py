"""A plain breadth-first search on the host, independent of the program.

The graph is held as a CSR built from one sort of the edge keys
`src * n + dst`; the same sorted keys answer "is (u, v) an edge?" for the
Graph500 parent check (`reference.graph500`).  The search is level
synchronous: gather the neighbours of the frontier, keep the unvisited ones,
give them the next level.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class HostGraph:
    n: int
    off: np.ndarray      # (n + 1,) int64 row offsets
    adj: np.ndarray      # (E,) int32 neighbours, sorted within each row
    keys: np.ndarray     # (E,) int64 sorted src * n + dst

    @property
    def degree(self) -> np.ndarray:
        return np.diff(self.off)


def host_graph(edges: np.ndarray, n: int) -> HostGraph:
    keys = edges[0].astype(np.int64) * n + edges[1]
    keys.sort()
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(keys // n, minlength=n), out=off[1:])
    return HostGraph(n=n, off=off, adj=(keys % n).astype(np.int32), keys=keys)


def bfs_levels(g: HostGraph, root: int, max_levels: int | None = None
               ) -> np.ndarray:
    """Hop distance of every vertex from `root` (-1 where unreached).

    max_levels: stop after this many levels (the control's broken search);
    None searches to the end."""
    level = np.full(g.n, -1, np.int32)
    level[root] = 0
    front = np.array([root], np.int64)
    mark = np.zeros(g.n, bool)
    d = 0
    while front.size and (max_levels is None or d < max_levels):
        start, cnt = g.off[front], g.off[front + 1] - g.off[front]
        first = np.cumsum(cnt) - cnt
        idx = np.repeat(start - first, cnt) + np.arange(cnt.sum())
        mark[g.adj[idx]] = True
        mark &= level < 0
        front = np.flatnonzero(mark)
        mark[front] = False
        d += 1
        level[front] = d
    return level


def bfs_parents(g: HostGraph, level: np.ndarray, root: int) -> np.ndarray:
    """A parent for every reached vertex: its smallest neighbour one level
    above it (-1 where unreached; the root is its own parent)."""
    row = np.repeat(np.arange(g.n), g.degree)
    up = (level[row] > 0) & (level[g.adj] == level[row] - 1)
    rows, first = np.unique(row[up], return_index=True)
    pred = np.full(g.n, -1, np.int32)
    pred[rows] = g.adj[np.flatnonzero(up)[first]]
    pred[root] = root
    return pred
