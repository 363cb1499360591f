"""Warm program times and the edge-slot search, on one chip.

    python benchmarks/chip_slots.py --scale 18                 # this tree
    python benchmarks/chip_slots.py --src OTHER/src --scale 18 # another tree

Two readings, one process, one chip:

* programs (any tree): plans a Graph500 R-MAT graph (edge factor 16, seed
  1, grid 1x1, every kernel knob "reference", edge_chunk 65536 as
  `chip_smoke.py`) and times warm top-down BFS, direction-optimised BFS and
  connected components after one call that compiles.  Level and label
  checksums let two trees' runs be compared for identical outputs.
* slots (trees with `repro.core.frontier.edge_slots`): for every level of
  the first root's search, the first 65536-lane chunk of that level's
  top-down cumul (frontier columns) and bottom-up cumul (unvisited rows'
  degrees, zero-width runs included), mapped by `edge_slots` and by the
  per-lane `searchsorted` it replaced.  Beside each time: the level's edge
  count, its chunks, and the window-loop passes of the first and of the
  widest chunk (host arithmetic), which set `edge_slots`' cost.

Run `--src` trees one at a time (a parent holding the chip starves a
child).  Each line of stdout is one JSON record; the last is the summary.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

EF, SEED, N_ROOTS, EDGE_CHUNK = 16, 1, 8, 1 << 16
TILE, WINDOW = 512, 256          # edge_slots' defaults
REPS = 5


def emit(rec) -> None:
    print(json.dumps(rec), flush=True)


def warm_times(jax, fn, reps):
    jax.block_until_ready(fn())                 # compiles
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t0)
    return out


def programs(jax, np, graph, roots, n):
    import dataclasses

    config = graph.config
    td = graph.session()
    dr = graph.session(dataclasses.replace(config, direction=True))
    r = int(roots[0])
    recs = {
        "top-down bfs": warm_times(jax, lambda: td.bfs(r).level, 3),
        "direction bfs": warm_times(jax, lambda: dr.bfs(r).level, 3),
        "connected components": warm_times(
            jax, lambda: td.connected_components().labels, 2),
    }
    for name, ts in recs.items():
        emit({"program": name, "warm_s": ts})
    level = np.asarray(td.bfs(r).level)[:n]
    check = {"level_sum": int(level.astype(np.int64).sum()),
             "direction_level_equal": bool(np.array_equal(
                 np.asarray(dr.bfs(r).level)[:n], level)),
             "cc_label_sum": int(np.asarray(
                 td.connected_components().labels)[:n].astype(np.int64)
                 .sum())}
    emit(check)
    return recs, check, level


def window_passes(np, cumul, total, lo, hi):
    """Window-loop passes per 512-edge tile for gids [lo, hi): 1 + the
    entries (k0, k_last] a tile spans, over the window (host arithmetic
    mirroring `map_workload_tile`)."""
    g = np.arange(lo, min(hi, total), dtype=np.int64)
    if g.size == 0:
        return 0
    pad = (-g.size) % TILE
    g = np.concatenate([g, np.full(pad, g[-1])]).reshape(-1, TILE)
    k0 = np.searchsorted(cumul, g[:, 0], side="right") - 1
    kl = np.searchsorted(cumul, g[:, -1], side="right") - 1
    return int(np.max((kl - k0) // WINDOW + 1))


def slots(jax, np, frontier, level, deg, n):
    jnp = jax.numpy
    e = EDGE_CHUNK
    es = jax.jit(frontier.edge_slots)
    ss = jax.jit(lambda c, g: jnp.searchsorted(c, g, side="right")
                 .astype(jnp.int32) - 1)
    gids = jnp.arange(e, dtype=jnp.int32)
    out = []
    for lvl in range(1, int(level.max()) + 1):
        front = np.flatnonzero(level == lvl - 1)
        unvis = (level < 0) | (level >= lvl)
        for mode, d in (("top-down", np.pad(deg[front], (0, n - front.size))),
                        ("bottom-up", np.where(unvis, deg, 0))):
            cumul = np.concatenate([[0], np.cumsum(d)]).astype(np.int32)
            total = int(cumul[-1])
            if total == 0:
                continue
            c = jax.device_put(cumul)
            t = jnp.int32(total)
            k_es = np.asarray(es(c, gids, t))
            k_ss = np.asarray(ss(c, gids))
            live = np.arange(e) < total
            if not np.array_equal(k_es[live], k_ss[live]):
                raise RuntimeError(f"edge_slots differs, level {lvl} {mode}")
            chunks = -(-total // e)
            rec = {"level": lvl, "mode": mode, "edges": total,
                   "chunks": chunks,
                   "passes_first": window_passes(np, cumul, total, 0, e),
                   "passes_max": max(window_passes(np, cumul, total, s, s + e)
                                     for s in range(0, total, e)),
                   "edge_slots_s": warm_times(jax, lambda: es(c, gids, t),
                                              REPS),
                   "searchsorted_s": warm_times(jax, lambda: ss(c, gids),
                                                REPS)}
            emit(rec)
            out.append(rec)
    return out


def main() -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--src", default=os.path.join(here, "src"),
                    help="directory holding the repro package to measure")
    ap.add_argument("--scale", type=int, default=18)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.src))

    import jax
    import numpy as np

    from repro.api import BFSConfig, DistGraph
    from repro.core import frontier
    from repro.graphgen import rmat_edges
    try:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
    except ImportError:         # a tree from before the helper
        pass

    dev = jax.devices()[0]
    n = 1 << args.scale
    edges = np.asarray(rmat_edges(jax.random.key(SEED), args.scale, EF))
    deg = np.bincount(edges[0], minlength=n)
    roots = np.random.default_rng(SEED).choice(
        np.flatnonzero(deg > 0), N_ROOTS, replace=False).astype(np.int32)
    config = BFSConfig(grid=(1, 1), edge_chunk=EDGE_CHUNK)
    graph = DistGraph.from_edges(edges, config, n=n)
    _, check, level = programs(jax, np, graph, roots, n)
    has_slots = hasattr(frontier, "edge_slots")
    layer = slots(jax, np, frontier, level, deg, n) if has_slots else []
    emit({"src": os.path.abspath(args.src), "scale": args.scale,
          "edge_slots": has_slots, "slot_records": len(layer), **check,
          "device": {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
