"""Graph500's rules for a BFS answer, and the counts the metrics divide by.

`check_answer` compares one search's (level, pred) with the reference levels
and counts what breaks the rules of Graph500 specification 2.1, Kernel 2
validation: the root is its own parent at level 0; a vertex has a parent
exactly when it is reached; each parent is reached one level above its child
and joined to it by an input edge; and, since the reference levels are exact,
every level equals the reference's.

The counts work from the input edge list and the reference levels alone, so
they read the same whatever program produced the answer.
"""
from __future__ import annotations

import numpy as np

from reference.bfs import HostGraph

# Bytes a top-down search must move, per reached vertex and per directed edge
# out of a reached vertex: the vertex's pair of column offsets (2 x int32),
# and its level and parent written once (2 x int32); for each edge the
# neighbour's row id (int32) and one read of the neighbour's visited state
# (one byte).
BYTES_PER_VERTEX = 8 + 8
BYTES_PER_EDGE = 4 + 1


def check_answer(g: HostGraph, ref_level: np.ndarray, level: np.ndarray,
                 pred: np.ndarray, root: int) -> dict:
    """{"level_mismatch": vertices whose level differs from the reference,
    "pred_violations": vertices whose parent breaks a Graph500 rule}."""
    n = g.n
    level = np.asarray(level)[:n]
    pred = np.asarray(pred)[:n].astype(np.int64)
    reached = ref_level >= 0
    bad = reached != (pred >= 0)
    bad[root] = pred[root] != root
    child = np.flatnonzero(reached)
    child = child[child != root]
    p = pred[child]
    ok = (p >= 0) & (p < n)
    pc = np.where(ok, p, 0)
    ok &= ref_level[pc] == ref_level[child] - 1
    key = pc * n + child
    pos = np.minimum(np.searchsorted(g.keys, key), g.keys.size - 1)
    ok &= g.keys[pos] == key
    bad[child[~ok]] = True
    return {"level_mismatch": int(np.count_nonzero(level != ref_level)),
            "pred_violations": int(np.count_nonzero(bad))}


def component_input_edges(g: HostGraph, ref_level: np.ndarray) -> int:
    """Graph500's TEPS numerator: input edge tuples inside the searched
    component.  The list is symmetrised, so the directed edges out of the
    reached vertices are halved."""
    return component_directed_edges(g, ref_level) // 2


def component_directed_edges(g: HostGraph, ref_level: np.ndarray) -> int:
    return int(g.degree[ref_level >= 0].sum())


def topdown_bytes(g: HostGraph, ref_level: np.ndarray) -> int:
    """Bytes a top-down search from this root needs to move at least."""
    reached = ref_level >= 0
    return (BYTES_PER_VERTEX * int(np.count_nonzero(reached))
            + BYTES_PER_EDGE * int(g.degree[reached].sum()))
