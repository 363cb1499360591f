"""Worker: run distributed BFS (2D / 1D / direction-optimised) on forced host
devices through the session API and print one CSV row:

  variant,R,C,scale,ef,roots,harmonic_TEPS,mean_s,levels,fold,
  fold_bytes_per_edge,batched_sweep_s,amortised_TEPS,
  batched_harmonic_TEPS,lvl_sum,pred_sum

  (the column order is benchmarks/common.py BFS_WORKER_HEADER)

The graph is planned ONCE (`DistGraph.from_edges`); the roots then run twice:
sequentially (per-root wall times -> harmonic TEPS, the paper's metric) and
as ONE batched compiled program (`session.bfs(roots)` -> batched_sweep_s,
amortised_TEPS = component edges summed over roots / sweep wall time, and
batched_harmonic_TEPS = the harmonic mean of per-root TEPS with the SAME
count_component_edges numerators as the sequential column over the
amortised per-root time sweep_s / n_roots -- the Graph500 amortised view
the session API exists for, in the paper's headline metric shape).

fold_bytes_per_edge = measured fold-exchange traffic (codec wire bytes *
devices * fold exchanges, summed over roots) / input edges in the searched
components -- the paper's bytes-per-edge communication metric.  Blank for
the `dir` variant: bottom-up levels exchange raw int32 parents instead of
the fold codec and the per-level split is not visible host-side.  lvl_sum /
pred_sum checksum the LAST root's output so benchmarks/bfs_fold_codecs.py
can assert codec equivalence across separate worker processes.

Usage: bfs_worker.py VARIANT R C SCALE EF N_ROOTS [fold]
  VARIANT in {2d, 1d, dir};  fold in {list, bitmap, delta}
"""
import os
import sys
import time

VARIANT, R, C = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
SCALE, EF, N_ROOTS = int(sys.argv[4]), int(sys.argv[5]), int(sys.argv[6])
FOLD = sys.argv[7] if len(sys.argv) > 7 else "list"

os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={R * C}")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import numpy as np

from repro.api import BFSConfig, DistGraph
from repro.core.validate import count_component_edges, harmonic_mean
from repro.dist.compat import make_mesh
from repro.graphgen import rmat_edges
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

n = 1 << SCALE
edges_np = np.asarray(rmat_edges(jax.random.key(42), SCALE, EF))

if VARIANT == "1d":
    mesh = make_mesh((R * C,), ("p",))
    config = BFSConfig(grid=(1, R * C), row_axes=(), col_axes=("p",),
                       edge_chunk=16384, fold_codec=FOLD)
else:
    mesh = make_mesh((R, C), ("r", "c"))
    config = BFSConfig(grid=(R, C), edge_chunk=16384, fold_codec=FOLD,
                       direction=(VARIANT == "dir"))

graph = DistGraph.from_edges(edges_np, config, mesh=mesh, n=n)
session = graph.session()

fold_wire = session.engine.codec.wire_bytes(graph.grid)  # per dev per level

rng = np.random.default_rng(0)
# pick roots from non-isolated vertices
deg = np.bincount(edges_np[0], minlength=n)
cand = np.flatnonzero(deg > 0)
roots = rng.choice(cand, size=N_ROOTS, replace=False)

out = session.bfs(int(roots[0]))  # compile warmup (B=1 program)
jax.block_until_ready(out.level)

teps, times, levels, comp_m = [], [], [], []
fold_bytes, comp_edges = 0, 0
for root in roots:
    t0 = time.perf_counter()
    out = session.bfs(int(root))
    jax.block_until_ready(out.level)
    dt = time.perf_counter() - t0
    m = count_component_edges(edges_np, np.asarray(out.level)[:n])
    comp_m.append(m)
    teps.append(m / dt)
    times.append(dt)
    levels.append(int(out.n_levels))
    # the engine exits with lvl = iterations + 1 -> n_levels - 1 folds/search
    # (dir is excluded: its bottom-up levels bypass the fold codec entirely)
    if VARIANT != "dir":
        fold_bytes += fold_wire * graph.grid.P * (int(out.n_levels) - 1)
    comp_edges += m

# the same roots as ONE compiled program (amortised Graph500 sweep)
jax.block_until_ready(session.bfs(roots).level)           # compile warmup
t0 = time.perf_counter()
bout = session.bfs(roots)
jax.block_until_ready(bout.level)
sweep_s = time.perf_counter() - t0
# harmonic-mean TEPS of the sweep: same per-root numerators as above, over
# the amortised per-root time (the batch has ONE wall time)
batched_hm = harmonic_mean([m / (sweep_s / len(roots)) for m in comp_m])

lvl_sum = int(np.asarray(out.level)[:n].astype(np.int64).sum())
pred_sum = int(np.asarray(out.pred)[:n].astype(np.int64).sum())
# direction-optimised levels that run bottom-up exchange raw int32 parents,
# not the fold codec, and the split is not visible host-side -- leave the
# bytes column blank rather than report a codec-scaled fiction
bpe = ("" if VARIANT == "dir"
       else f"{fold_bytes / max(comp_edges, 1):.3f}")
print(f"{VARIANT},{R},{C},{SCALE},{EF},{N_ROOTS},"
      f"{harmonic_mean(teps):.3e},{np.mean(times):.4f},{max(levels)},"
      f"{FOLD},{bpe},{sweep_s:.4f},{comp_edges / sweep_s:.3e},"
      f"{batched_hm:.3e},{lvl_sum},{pred_sum}")
