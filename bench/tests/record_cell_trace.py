"""Record a small TPU trace of one cell, for the tests that reduce it.

    python bench/tests/record_cell_trace.py --workload g500-s20-do.bfs \
        --scale 10 --tag do_s10 --out bench/tests/data

On the cell's chips: the cell cut to `--scale`, a traced window of two
searches, exactly as a traced run makes it, with the program's own host
spans (`repro/session/...`) beside the benchmark's.  Writes
`trace_<tag>.xplane.pb` and `programs_<tag>.json` (the search program's op
names, for the instructions the trace holds).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402
import trace_reduce  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scale", type=int, required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import jax

    cell = harness.load_cell(args.workload)
    devices = harness.chip_devices(cell.chips)
    cell.config = dict(cell.config, scale=args.scale)
    setup = harness.plan(cell, devices, seed=1)
    harness.run_query(setup.session, setup.warm_keys[:1])
    name, ops = trace_reduce.op_names(
        setup.session.compiled_for(1).as_text())
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(tmp)
    harness.run_window(setup, cell.traffic, 60.0, max_calls=2)
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(path, os.path.join(args.out, f"trace_{args.tag}.xplane.pb"))
    trace = trace_reduce.load(path)
    seen = {i for d in trace.devices.values() for i in d.instr}
    with open(os.path.join(args.out, f"programs_{args.tag}.json"), "w") as f:
        json.dump({name: {i: o for i, o in ops.items() if i in seen}}, f,
                  indent=0, sort_keys=True)
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
