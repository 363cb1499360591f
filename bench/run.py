"""Run one cell of the chip benchmark once.

    python bench/run.py --workload g500-s20.bfs --seed 7 --seconds 30 --trace 0

The cells, their configurations, traffic and metrics are listed in
`BENCHMARK.json` at the root of the checkout.  The last line of stdout is one
JSON object: `correct`, `attempted`, `failed`, `metrics` (the cell's
end-to-end metrics with `--trace 0`, its per-layer metrics with `--trace 1`),
`device`, with `--trace 1` a `breakdown`, and last the `checks`: each number
compared with the reference beside its limit.  Those numbers are also the last
lines of stderr.

Without a TPU, with fewer chips than the cell asks for, or without the
program (`src/repro`) beside it, the run exits non-zero and prints no result.
It never falls back to the CPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    import harness

    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        harness.log(f"bench: the program (src/repro) is not in this "
                    f"checkout: {e}")
        return 2
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.chip_devices(cell.chips)
    except harness.NoChip as e:
        harness.log(f"bench: {e}")
        return 3
    harness.log(f"devices: {devices}; compile cache "
                f"{harness.use_compile_cache()}")
    result = harness.run_cell(cell, devices, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
