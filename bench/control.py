"""The control of the check: the reference in the program's place, with one
guarantee broken, must come out as not correct.

The configurations state exact hop levels and a valid parent tree.  The
control answers each search with the plain reference stopped one level
short (the deepest level left unreached) and parents that fit those levels:
the step a change to the level loop's exit test would tempt.  It goes through
the same check as a run of the program, on the graph and keys of the cell at
its own size, and prints each compared number beside its limit.

    python bench/control.py --workload g500-s20.bfs --seeds 1 2 3 --calls 7

Needs one chip, on which the graph is generated as in a run; the program
is not run, so a cell on four chips is controlled on one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
from reference.bfs import bfs_levels, bfs_parents, host_graph  # noqa: E402


def control_window(edges: np.ndarray, n: int, keys: np.ndarray,
                   calls: int) -> harness.Window:
    """A window of `calls` searches answered by the broken reference."""
    g = host_graph(edges, n)
    searches = []
    for root in keys[:calls]:
        root = int(root)
        depth = int(bfs_levels(g, root).max())
        level = bfs_levels(g, root, max_levels=depth - 1)
        pred = bfs_parents(g, level, root)
        searches.append(harness.Search(
            roots=np.array([root], np.int32), level=level[None],
            pred=pred[None], scanned=(None,), t_start=0.0, t_done=0.0,
            t_end=0.0))
    return harness.Window(searches=searches, t_start=0.0, t_end=1.0)


def control_checks(cell: harness.Cell, seed: int, calls: int) -> dict:
    cfg = cell.config
    gen = harness.load_module(
        os.path.join(HERE, "graphs", cfg["generator"] + ".py"),
        "bench_graph_" + cfg["generator"])
    edges, n, keys = gen.generate(cfg, seed, int(cell.traffic["keys"]))
    window = control_window(edges, n, keys, calls)
    checked = harness.check_window(edges, n, window)
    return {"seed": seed, "correct": harness.is_correct(window, checked),
            "checks": checked.checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--calls", type=int, required=True,
                    help="searches to answer, as many as a run's window")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.chip_devices(1)
    except harness.NoChip as e:
        harness.log(f"control: {e}")
        return 3
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = control_checks(cell, seed, args.calls)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
