"""BFS to completion on a graph deeper than the per-level record.

    python tests/dist/run_deep_bfs.py R C {topdown|direction}

Plans a seeded long grid strip, more than `RECORDED_LEVELS` levels deep from
its end, on an R x C grid (forced host devices when R * C > 1) and checks a
session opened with the default `BFSConfig` (no `max_levels`):

  * every root's levels equal the plain reference's, and its parents pass
    Graph500's rules;
  * the per-level records keep their fixed length on that search: the
    `LevelTrace` channels, the direction program's `directions`, and the
    trace carry the segmented loop exports, whose counter `k` still counts
    every level.

Prints OK.  `tests/test_deep_bfs.py` runs the 1x1 cases in its own process
through `check` and the 2x2 cases through this script.
"""
from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np

WIDTH, LENGTH, SEED = 3, 100, 7


def strip_graph(width: int = WIDTH, length: int = LENGTH, seed: int = SEED):
    """A width x length lattice with seeded diagonals between neighbouring
    columns, under seeded labels.  Returns (symmetrised (2, E) int32 edges,
    n, roots): the roots are a vertex of the first column and one of the
    middle column, at least `length - 1` and `length // 2` levels from the
    far end."""
    rng = np.random.default_rng(seed)
    n = width * length
    ids = np.arange(n).reshape(length, width)          # ids[x, y]
    pairs = [np.stack([ids[:-1].ravel(), ids[1:].ravel()]),
             np.stack([ids[:, :-1].ravel(), ids[:, 1:].ravel()])]
    for dy in (1, -1):
        a = ids[:-1, max(0, -dy):width - max(0, dy)].ravel()
        b = ids[1:, max(0, dy):width - max(0, -dy)].ravel()
        keep = rng.random(a.size) < 0.3
        pairs.append(np.stack([a[keep], b[keep]]))
    src, dst = np.concatenate(pairs, axis=1)
    label = rng.permutation(n).astype(np.int32)
    src, dst = label[src], label[dst]
    edges = np.stack([np.concatenate([src, dst]), np.concatenate([dst, src])])
    roots = np.array([label[ids[0, 0]], label[ids[length // 2, width - 1]]],
                     np.int32)
    return edges.astype(np.int32), n, roots


def check(R: int, C: int, direction: bool) -> None:
    from repro.algos.reference import multi_bfs_reference
    from repro.api import BFSConfig, DistGraph
    from repro.core.validate import edge_keys, validate_bfs
    from repro.dist import multihost
    from repro.obs.trace import RECORDED_LEVELS, TRACE_CHANNELS

    edges, n, roots = strip_graph()
    # adaptive, with thresholds that a strip's few-vertex frontiers cross:
    # bottom-up above n / 120 (3 vertices), back below n / 360 (1)
    cfg = BFSConfig(grid=(R, C), edge_chunk=64, direction=direction,
                    alpha=120, beta=360)
    assert cfg.max_levels is None
    graph = DistGraph.from_edges(edges, cfg, n=n)
    keys = edge_keys(edges, n)
    refs = [multi_bfs_reference(edges, n, [int(r)])[0] for r in roots]
    depth = int(refs[0].max())
    assert depth > RECORDED_LEVELS, depth

    out = graph.session().bfs(roots)
    level, pred = np.asarray(out.level), np.asarray(out.pred)
    for b, root in enumerate(roots):
        np.testing.assert_array_equal(level[b][:n], refs[b])
        validate_bfs(edges, level[b][:n], pred[b][:n], int(root), keys)
        assert int(out.n_levels[b]) == int(refs[b].max()) + 2

    # the records: fixed length, levels past it in the last slot
    tsess = graph.session(dataclasses.replace(cfg, telemetry=True))
    tout = tsess.bfs(int(roots[0]))
    np.testing.assert_array_equal(np.asarray(tout.level)[:n], refs[0])
    tr = tout.trace
    assert tr.n_levels == RECORDED_LEVELS
    for name in ("frontier", "scanned", "folded", "wire_bytes", "msgs",
                 "direction", "map_passes"):
        assert getattr(tr, name).shape == (RECORDED_LEVELS,), name
    assert tr.frontier_dev.shape == (R * C, RECORDED_LEVELS)
    # the last slot holds the last level run: the deepest vertices' own
    assert tr.frontier[-1] == np.count_nonzero(refs[0] == depth)
    if direction:
        dirs = np.asarray(tout.directions)
        assert dirs.shape == (RECORDED_LEVELS,)
        assert set(dirs.tolist()) <= {0, 1}, dirs     # every slot written
        np.testing.assert_array_equal(dirs, tr.direction)
    else:
        assert tout.directions is None

    # the segmented loop's exported trace carry: fixed length, k counts
    # every level
    fcfg = dataclasses.replace(cfg, telemetry=True, fault_tolerance=True,
                               ckpt_every=RECORDED_LEVELS)
    fsess = graph.session(fcfg)
    eng, csc = fsess.engine, graph.csc
    arg = multihost.put_replicated(roots[:1], graph.mesh)
    carry = eng.ft_start(csc, arg, *fsess._extra, batched=True)
    while eng.ft_active(carry):
        carry = eng.ft_segment(csc, carry, *fsess._extra, batched=True)
    snap = eng.export_carry(carry, n=n, B=1)
    traw = snap["arrays"]["trace"]
    for c in TRACE_CHANNELS:
        assert traw[c].shape == (R, C, 1, RECORDED_LEVELS), c
    assert (traw["k"] == depth + 1).all(), traw["k"]
    resumed = eng.import_carry(snap, B=1)
    assert np.asarray(resumed["trace"]["frontier"]).shape \
        == (R, C, 1, RECORDED_LEVELS)
    fout = eng.ft_finish(carry, B=1)
    np.testing.assert_array_equal(np.asarray(fout.level)[0][:n], refs[0])


if __name__ == "__main__":
    R, C = int(sys.argv[1]), int(sys.argv[2])
    mode = sys.argv[3] if len(sys.argv) > 3 else "topdown"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count"
                               f"={R * C}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                    "src"))
    import jax

    assert jax.device_count() >= R * C, jax.devices()
    check(R, C, mode == "direction")
    print("OK")
