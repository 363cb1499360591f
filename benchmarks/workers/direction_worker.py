"""Worker: direction-optimised traversal sweep on a 1x1 grid (DESIGN.md
sec. 11).

Times whole searches through the session API in all three modes --
direction=False (pure top-down), "adaptive" (alpha/beta switch) and
"bottomup" (every level pulls) -- over the same RMAT graph and root set,
plus a per-level replay of the bottom-up pull so the alpha/beta crossover
is visible level by level (which levels the adaptive heuristic flips, and
what the bottom-up phase costs at each frontier size).

Output lines (parsed by benchmarks/bfs_expansion_variants.direction_sweep):
  M,mode,roots,mean_s,levels,lvl_sum,pred_sum,dirs
     one per mode; `dirs` is the adaptive/bottomup per-level decision trace
     "0|1|1|0..." ("" for top-down); lvl_sum/pred_sum are the bit-equality
     checksums the suite gates on
  L,level,frontier,dir,bottomup_s
     one per BFS level: frontier size entering the level, the adaptive
     decision for it, and the measured wall time of the jitted bottom-up
     pull for that level

Usage: direction_worker.py SCALE EF
"""
import os
import sys
import time

SCALE, EF = int(sys.argv[1]), int(sys.argv[2])
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import BFSConfig, DistGraph
from repro.core import frontier as F
from repro.core.partition import partition_2d_csr
from repro.core.types import Grid2D
from repro.graphgen import rmat_edges
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

n = 1 << SCALE
edges = np.asarray(rmat_edges(jax.random.key(42), SCALE, EF))
deg_out = np.bincount(edges[0], minlength=n)
roots = np.random.default_rng(5).choice(np.flatnonzero(deg_out > 0), 4,
                                        replace=False)
EDGE_CHUNK = 16384
N_ITERS = 2


def checksums(out):
    lvl = np.asarray(out.level).astype(np.int64)
    pred = np.asarray(out.pred).astype(np.int64)
    return int(lvl.sum()), int(pred.sum())


# --- whole-search sweep per mode -------------------------------------------
adaptive_dirs = None
for mode in (False, "adaptive", "bottomup"):
    cfg = BFSConfig(grid=(1, 1), edge_chunk=EDGE_CHUNK, direction=mode)
    sess = DistGraph.from_edges(edges, cfg, n=n).session()
    out = sess.bfs(roots)                       # warm the AOT cache
    t0 = time.perf_counter()
    for _ in range(N_ITERS):
        jax.block_until_ready(sess.bfs(roots).level)
    mean_s = (time.perf_counter() - t0) / (N_ITERS * len(roots))
    lvl_sum, pred_sum = checksums(out)
    dirs = ""
    if out.directions is not None:
        d = np.asarray(out.directions[0])
        dirs = "|".join(str(int(x)) for x in d[d >= 0])
        if mode == "adaptive":
            adaptive_dirs = d[d >= 0]
    print(f"M,{mode},{len(roots)},{mean_s:.6f},"
          f"{int(out.n_levels[0])},{lvl_sum},{pred_sum},{dirs}")

# --- per-level bottom-up replay (root 0 of the sweep) ----------------------
grid = Grid2D.for_vertices(n, 1, 1)
csr = partition_2d_csr(edges, grid)
row_off = jnp.asarray(csr["row_off"][0, 0])
col_idx = jnp.asarray(csr["col_idx"][0, 0])
row_deg = jnp.diff(row_off)
S = grid.S


@jax.jit
def bu_level(visited, front_mask, lvl):
    """One full bottom-up level on the 1x1 grid: every unvisited row scans
    its in-edges against the frontier bitmap (the engine's pull phase,
    un-distributed)."""
    words = F.pack_bitmap(front_mask)
    deg = jnp.where(visited, 0, row_deg)
    cumul = F.exclusive_cumsum(deg)
    total = cumul[-1]
    gids = jnp.arange(col_idx.shape[0], dtype=jnp.int32)
    r, c, hit = F.reference_bottomup_chunk(gids, cumul, total, row_off,
                                           col_idx, words, block=S)
    cand = jnp.full((S + 1,), F.I32_MAX, jnp.int32).at[
        jnp.where(hit, r, S)].min(jnp.where(hit, c, F.I32_MAX),
                                  mode="drop")[:S]
    found = ~visited & (cand < F.I32_MAX)
    return visited | found, found, found.sum()


root = int(roots[0])
visited = jnp.zeros((S,), bool).at[root].set(True)
front = jnp.zeros((S,), bool).at[root].set(True)
fcnt, lvl = 1, 1
while fcnt:
    jax.block_until_ready(bu_level(visited, front, lvl))   # per-level warmup
    t0 = time.perf_counter()
    visited2, found, cnt = jax.block_until_ready(bu_level(visited, front,
                                                          lvl))
    bu_s = time.perf_counter() - t0
    d = (int(adaptive_dirs[lvl - 1])
         if adaptive_dirs is not None and lvl - 1 < len(adaptive_dirs)
         else -1)
    print(f"L,{lvl},{fcnt},{d},{bu_s:.6f}")
    visited, front = visited2, found
    fcnt, lvl = int(cnt), lvl + 1
