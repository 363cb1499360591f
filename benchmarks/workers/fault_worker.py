"""Worker: run the standard fault-drill matrix (repro.scenarios) on forced
host devices and print tagged result lines (parsed by
benchmarks/fault_drill.py):

  DRILL,{json DrillResult row}
  NORETRACE,{json no-retrace proof}

The drill matrix is the acceptance grid of DESIGN.md sec. 15: transient
loss absorbed by the segment retry (every program x codec, plus the
fold-phase variant), persistent loss -> elastic shrink-and-resume (every
program x codec), repeated loss (two shrinks), and a GraphServer batch
draining through recovery.  The NORETRACE line proves the feature is free
when off: a `fault_tolerance=False` session builds ZERO segmented programs,
its outputs are bit-identical to the FT session's, and repeat sweeps leave
its trace count untouched.

Usage: fault_worker.py SCALE EF R C
"""
import json
import os
import sys

SCALE, EF = int(sys.argv[1]), int(sys.argv[2])
R, C = int(sys.argv[3]), int(sys.argv[4])

os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={R * C}")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import numpy as np

from repro.api import BFSConfig, DistGraph
from repro.graphgen import rmat_edges
from repro.launch.compile_cache import use_compile_cache
from repro.scenarios import run_matrix, standard_matrix

use_compile_cache()

N = 1 << SCALE
edges = np.asarray(rmat_edges(jax.random.key(42), SCALE, EF))
weights = ((np.abs(edges[0] * 31 + edges[1]) % 254) + 1).astype(np.uint8)
config = BFSConfig(grid=(R, C), edge_chunk=4096, ckpt_every=1)

for res in run_matrix(edges, config, weights=weights, n=N,
                      scenarios=standard_matrix()):
    print(f"DRILL,{json.dumps(res.to_row(), sort_keys=True)}", flush=True)

# ---- no-retrace proof ---------------------------------------------------
roots = np.random.default_rng(0).choice(
    np.flatnonzero(np.bincount(edges[0], minlength=N) > 0), 4,
    replace=False).astype(np.int32)

off = DistGraph.from_edges(edges, config, n=N, weights=weights).session()
out_off1 = off.bfs(roots)
traces_after_first = off.engine.trace_count
out_off2 = off.bfs(roots)
traces_after_second = off.engine.trace_count

ft_cfg = BFSConfig(grid=(R, C), edge_chunk=4096, ckpt_every=1,
                   fault_tolerance=True)
on = DistGraph.from_edges(edges, ft_cfg, n=N, weights=weights).session()
out_on = on.bfs(roots)

bitexact = ((np.asarray(out_on.level) == np.asarray(out_off1.level)).all()
            and (np.asarray(out_on.pred) == np.asarray(out_off1.pred)).all()
            and tuple(out_on.edges_scanned)
            == tuple(out_off1.edges_scanned))
repeat_ok = ((np.asarray(out_off2.level)
              == np.asarray(out_off1.level)).all()
             and (np.asarray(out_off2.pred)
                  == np.asarray(out_off1.pred)).all())
print("NORETRACE," + json.dumps({
    "ft_off_segmented_programs": len(off.engine._ft_progs),
    "after_first_sweep": traces_after_first,
    "after_second_sweep": traces_after_second,
    "ft_on_off_bitexact": bool(bitexact and repeat_ok),
}, sort_keys=True), flush=True)
