"""Worker: per-level expand-phase timing for ONE expand path (DESIGN.md
sec. 9).

Drives a real BFS level sequence on a 1x1 grid -- the device-local frontier
expansion `repro.core.frontier.expand_frontier` with the path's expand_fn, no
exchanges -- and wall-clocks the jitted expand per level, so the
reference-vs-pallas(-interpret) split is visible level by level (the paper's
per-level column-scan cost).  The final level-array checksum lets the suite
assert the paths are bit-identical across worker processes.

CSV rows: path,level,frontier,edges,expand_s,lvl_sum
  (lvl_sum repeated on every row; one row per BFS level that expanded)

Usage: expand_worker.py SCALE EF PATH
  PATH in {reference, pallas, pallas-interpret}
"""
import os
import sys
import time

SCALE, EF, PATH = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Grid2D, partition_2d
from repro.core import frontier as F
from repro.graphgen import rmat_edges
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

n = 1 << SCALE
edges = np.asarray(rmat_edges(jax.random.key(42), SCALE, EF))
grid = Grid2D.for_vertices(n, 1, 1)
lg = partition_2d(edges, grid)
co = jnp.asarray(lg.col_off[0, 0])
ri = jnp.asarray(lg.row_idx[0, 0])
ncl, nrl = grid.n_cols_local, grid.n_rows_local

if PATH == "reference":
    expand_fn = None
else:
    from repro.kernels import make_expand_fn
    expand_fn = make_expand_fn(path=PATH)

EDGE_CHUNK = 16384


@jax.jit
def scan(co, ri, vis, lvl_arr, pr, front, ftot, lvl):
    return F.expand_frontier(co, ri, vis, lvl_arr, pr, front, ftot, lvl,
                             grid=grid, i=jnp.int32(0), j=jnp.int32(0),
                             edge_chunk=EDGE_CHUNK, expand_fn=expand_fn)


root = int(np.flatnonzero(np.bincount(edges[0], minlength=n) > 0)[0])
vis = jnp.zeros((nrl,), bool).at[root].set(True)
lvl_arr = jnp.full((nrl,), -1, jnp.int32).at[root].set(0)
pr = jnp.full((nrl,), -1, jnp.int32).at[root].set(root)
front = jnp.full((ncl,), -1, jnp.int32).at[0].set(root)
ftot = jnp.int32(1)

rows, lvl = [], 1
while int(ftot) > 0 and lvl <= 64:
    args = (co, ri, vis, lvl_arr, pr, front, ftot, jnp.int32(lvl))
    ex = scan(*args)                        # compile (level 1) / warm
    jax.block_until_ready(ex.visited)
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(scan(*args).visited)
    dt = (time.perf_counter() - t0) / 3
    rows.append((lvl, int(ftot), int(ex.edges_scanned), dt))
    # next frontier: on a 1x1 grid every discovery is own-column (row == col
    # local id); keep the canonical ascending order the engines use
    cnt = int(ex.dst_cnt[0])
    nxt = np.sort(np.asarray(ex.dst[0])[:cnt]).astype(np.int32)
    front = jnp.full((ncl,), -1, jnp.int32).at[:cnt].set(jnp.asarray(nxt))
    ftot = jnp.int32(cnt)
    vis, lvl_arr, pr = ex.visited, ex.level, ex.pred
    lvl += 1

lvl_sum = int(np.asarray(lvl_arr).astype(np.int64).sum())
for (level, frontier, edges_scanned, dt) in rows:
    print(f"{PATH},{level},{frontier},{edges_scanned},{dt:.6f},{lvl_sum}")
