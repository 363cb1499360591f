"""Run a cell several times and report each metric's spread, as the bounds
in `BENCHMARK.json` are set from it.

    python bench/spread.py --workload g500-s20.bfs --seeds 5 6 7 8 9 10 \\
        --sets 2 --seconds 30 --out chiprun_out/g500-s20

Runs `bench/run.py` once per seed and set, one process at a time (this
parent never imports JAX), and keeps each run's stdout and stderr under
`--out`.  For each set and metric it prints the median and the spread: the
distance between the first and third quartiles of
`statistics.quantiles(values, n=4)`, as a share of the median.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in args.seeds:
            tag = f"{args.workload}.t{args.trace}.set{k}.{seed}"
            t0 = time.perf_counter()
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"), "--workload",
                 args.workload, "--seed", str(seed), "--seconds",
                 str(args.seconds), "--trace", str(args.trace)],
                capture_output=True, text=True)
            wall = time.perf_counter() - t0
            for ext, text in (("out", proc.stdout), ("err", proc.stderr)):
                with open(os.path.join(args.out, f"{tag}.{ext}"), "w") as f:
                    f.write(text)
            lines = proc.stdout.strip().splitlines()
            try:
                res = json.loads(lines[-1])
            except (IndexError, json.JSONDecodeError):
                print(f"{tag}: rc {proc.returncode}, no result, "
                      f"{wall:.1f}s\n{proc.stderr[-2000:]}", flush=True)
                continue
            vals = {m: v["value"] for m, v in res["metrics"].items()}
            print(f"{tag}: rc {proc.returncode} correct {res['correct']} "
                  f"attempted {res['attempted']} failed {res['failed']} "
                  f"wall {wall:.1f}s {json.dumps(vals)} "
                  f"peak {res['device'].get('memory_peak_bytes')} "
                  f"checks {json.dumps(res['checks'])}", flush=True)
            if "breakdown" in res:
                print(f"{tag}: device {json.dumps(res['device'])} "
                      f"breakdown {json.dumps(res['breakdown'])}",
                      flush=True)
            runs.append(vals)
        sets.append(runs)
    for k, runs in enumerate(sets):
        for m in sorted({m for r in runs for m in r}):
            vs = [r[m] for r in runs if m in r]
            if len(vs) >= 2:
                print(f"set {k} {m}: n {len(vs)} median "
                      f"{statistics.median(vs)!r} spread {spread(vs)!r} "
                      f"values {vs}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
