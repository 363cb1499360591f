"""Worker: run one benchmark suite in a process of its own.

`benchmarks/run.py` starts every suite through this worker, so the
orchestrator never imports JAX.  A chip belongs to one process at a time:
a suite that computes in-process (algos_sweep, the expansion-variants
half of bfs_expansion_variants) holds it here, and a suite that starts
workers of its own leaves it to them.

Usage: suite_worker.py MODULE FUNCTION   (e.g. algos_sweep main)
"""
import importlib
import os
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()
getattr(importlib.import_module(f"benchmarks.{sys.argv[1]}"), sys.argv[2])()
