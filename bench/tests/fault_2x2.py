"""A tiny run of the `g500-s20-2x2` configuration (the paper's 2x2 grid) on
four host devices, sound and then with the fold's exchange left out; prints
`{"sound": ..., "exchange_left_out": ...}` (each run's `correct`).  Started
by `test_bench_harness.py` with
`XLA_FLAGS=--xla_force_host_platform_device_count=4`."""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402

sys.path.insert(0, os.path.join(harness.ROOT, "src"))


def main() -> int:
    import jax
    from repro.dist import strategy

    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"needs 4 host devices, found {len(devices)}")
    harness.device_peaks = lambda kind: {"hbm_bytes_per_s": 819e9}
    cell = harness.load_cell("g500-s20.bfs")
    path = os.path.join(harness.BENCH, "configs", "g500-s20-2x2.json")
    with open(path) as f:
        cell.config = dict(json.load(f), scale=8)
    cell.config["session"] = dict(cell.config["session"], edge_chunk=1024)
    cell.chips = cell.config["chips"]
    out = {}
    for name in ("sound", "exchange_left_out"):
        if name == "exchange_left_out":
            strategy.FlatExchange.all_to_all = lambda self, x, topo: x
        res = harness.run_cell(cell, devices, 9, 0.5, False,
                               time.perf_counter())
        out[name] = res["correct"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
