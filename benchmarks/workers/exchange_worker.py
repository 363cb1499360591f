"""Worker: flat vs butterfly fold-exchange head-to-head on a 1 x C column
grid (DESIGN.md sec. 14) -- the BENCH crossover evidence.

Runs the SAME telemetry-enabled BFS once per exchange strategy x fold
codec on C simulated devices and prints, from the in-program LevelTrace,
the per-level message and wire-byte totals plus bit-identity checksums.
The flat strategy ships one fused all_to_all (C-1 messages per device per
level); the butterfly ships log2(C) staged ppermutes (each C/2 of the C
buckets), so at C = 4 the message count drops 3 -> 2 per device while the
set-fold wire volume is EQUAL -- the crossover bfs_exchange.py asserts.

Output lines (parsed by benchmarks/bfs_exchange.py):
  X,strategy,codec,level,frontier,folded,wire_bytes,msgs   per level
  G,codec,lvl_sum,pred_sum,scanned   one row per strategy x codec; equal
                                     checksums across strategies = the
                                     bit-identity gate
  S,strategy,codec,levels,total_msgs,total_wire            totals

Usage: exchange_worker.py C SCALE EF
"""
import os
import sys

C, SCALE, EF = (int(a) for a in sys.argv[1:4])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={C}"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import numpy as np

from repro.api import BFSConfig, DistGraph
from repro.dist.compat import make_mesh
from repro.graphgen import rmat_edges
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

STRATEGIES = ("flat", "butterfly")
CODECS = ("list", "bitmap", "delta")

n = 1 << SCALE
edges_np = np.asarray(rmat_edges(jax.random.key(42), SCALE, EF))
mesh = make_mesh((1, C), ("r", "c"))
graph = DistGraph.from_edges(
    edges_np, BFSConfig(grid=(1, C), edge_chunk=16384), mesh=mesh, n=n)

deg = np.bincount(edges_np[0], minlength=n)
root = int(np.flatnonzero(deg > 0)[0])

for strategy in STRATEGIES:
    for codec in CODECS:
        sess = graph.session(BFSConfig(
            grid=(1, C), fold_codec=codec, edge_chunk=16384,
            telemetry=True, exchange=strategy))
        assert sess.engine.exchange.name == strategy
        out = sess.bfs(root)
        tr = sess.last_trace()
        for row in tr.levels():
            print(f"X,{strategy},{codec},{row['level']},{row['frontier']},"
                  f"{row['folded']},{row['wire_bytes']},{row['msgs']}")
        lvl_sum = int(np.asarray(out.level, np.int64).sum())
        pred_sum = int(np.asarray(out.pred, np.int64).sum())
        print(f"G,{strategy},{codec},{lvl_sum},{pred_sum},"
              f"{out.edges_scanned}")
        print(f"S,{strategy},{codec},{tr.n_levels},{tr.total_msgs},"
              f"{tr.total_wire_bytes}")
