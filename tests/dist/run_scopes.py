"""Write the compiled search program of a session on an R x C grid of
forced host devices, for the scope-coverage test in tests/test_scopes.py.

Usage: run_scopes.py R C OUT.hlo [direction]
"""
import os
import sys

R, C = int(sys.argv[1]), int(sys.argv[2])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={R * C}"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import numpy as np

from repro.api import BFSConfig, DistGraph
from repro.dist.compat import make_mesh
from repro.graphgen import rmat_edges

SCALE, EF = 8, 8
edges = np.asarray(rmat_edges(jax.random.key(0), SCALE, EF))
cfg = BFSConfig(grid=(R, C), edge_chunk=512,
                direction=len(sys.argv) > 4 and sys.argv[4] == "direction")
mesh = make_mesh((R, C), ("r", "c"))
sess = DistGraph.from_edges(edges, cfg, n=1 << SCALE, mesh=mesh).session()
with open(sys.argv[3], "w") as f:
    f.write(sess.compiled_for(1).as_text())
print("OK")
