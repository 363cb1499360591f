"""DIMACS10 random geometric graph (`rgg_n_2_<scale>`) and search keys,
from a seed.

The benchmark's own copy of the recipe of the 10th DIMACS Implementation
Challenge's `rgg` family (Holtgrewe, Sanders, Schulz, IPDPS 2010):
n = 2**scale points drawn uniformly in the unit square, and an edge between
two points whose distance is below `radius_coefficient * sqrt(ln n / n)`
(0.55 in the family).  The points are binned into square cells of side at
least that radius, so only points of neighbouring cells are compared.  The
list is symmetrised (each edge and its reverse) with no self-loops, the
input the BFS programs take.

Search keys follow Graph500's Kernel 2, as `graphs/rmat.py` draws them:
distinct vertices with an edge other than a self-loop, in the order drawn.

The configuration's `structure_seed` draws the points and then the keys; the
run's seed draws a permutation of the vertex labels.  So every seed gives
the same graph and the same searches under other labels.  (The published
files number the vertices by the points' position; labels drawn at random
keep the CSC layout from following the geometry.)
"""
from __future__ import annotations

import math

import numpy as np

# (dx, dy) cell offsets that meet every pair of neighbouring cells once;
# the pairs inside one cell come from (0, 0)
HALF_NEIGHBOURHOOD = ((0, 0), (0, 1), (1, -1), (1, 0), (1, 1))


def radius(n: int, coefficient: float) -> float:
    return coefficient * math.sqrt(math.log(n) / n)


def geometric_pairs(xy: np.ndarray, r: float) -> np.ndarray:
    """(2, m) int64 pairs i < j of the rows of `xy` ((n, 2) in the unit
    square) whose distance is below r, each pair once."""
    n = xy.shape[0]
    side = max(1, int(1.0 / r))          # cells of side 1 / side >= r
    cx, cy = (np.minimum((xy * side).astype(np.int64), side - 1)).T
    order = np.argsort(cx * side + cy, kind="stable")
    cx, cy, pts = cx[order], cy[order], xy[order]
    start = np.searchsorted(cx * side + cy, np.arange(side * side + 1))
    pos = np.arange(n)
    found = []
    for dx, dy in HALF_NEIGHBOURHOOD:
        nx, ny = cx + dx, cy + dy
        ok = (nx < side) & (ny >= 0) & (ny < side)
        p = pos[ok]
        cell = nx[ok] * side + ny[ok]
        lo, cnt = start[cell], start[cell + 1] - start[cell]
        first = np.cumsum(cnt) - cnt
        q = np.repeat(lo - first, cnt) + np.arange(cnt.sum())
        p = np.repeat(p, cnt)
        if (dx, dy) == (0, 0):
            keep = q > p
            p, q = p[keep], q[keep]
        d = pts[p] - pts[q]
        near = d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1] < r * r
        found.append(np.stack([order[p[near]], order[q[near]]]))
    pairs = np.concatenate(found, axis=1)
    return np.sort(pairs, axis=0)


def structure(config: dict, n_keys: int):
    """(n, (2, m) undirected pairs i < j, the keys as structure ids)."""
    n = 1 << int(config["scale"])
    rng = np.random.default_rng(int(config["structure_seed"]))
    xy = rng.random((n, 2))
    pairs = geometric_pairs(xy, radius(n, float(config["radius_coefficient"])))
    deg = np.bincount(pairs.reshape(-1), minlength=n)
    cand = np.flatnonzero(deg > 0)
    if cand.size < n_keys:
        raise ValueError(f"only {cand.size} vertices have an edge; "
                         f"{n_keys} search keys asked for")
    return n, pairs, rng.choice(cand, n_keys, replace=False)


def generate(config: dict, seed: int, n_keys: int
             ) -> tuple[np.ndarray, int, np.ndarray]:
    """(edges (2, 2m) int32, n, the first `n_keys` search keys in the order
    drawn), under labels permuted by `seed`."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    n, pairs, keys = structure(config, n_keys)
    label = np.random.default_rng(seed).permutation(n).astype(np.int32)
    u, v = label[pairs]
    edges = np.stack([np.concatenate([u, v]), np.concatenate([v, u])])
    return edges, n, label[keys]
