"""Subprocess check of single-process placement on a forced 4-device CPU
mesh (DESIGN.md sec. 14.2):

  * `multihost.put_dev` gives every device only its own (1, 1, ...) block
    of an (R, C, ...) array, and `put_replicated` a whole copy on each;
  * `DistGraph.from_edges` places the planned graph that way, and a batched
    query over the sharded graph matches per-root queries.

Usage: run_placement.py
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.api import BFSConfig, DistGraph
from repro.dist import multihost
from repro.dist.compat import make_mesh
from repro.graphgen import rmat_edges

assert jax.process_count() == 1 and jax.device_count() == 4

mesh = make_mesh((2, 2), ("r", "c"))
x = np.arange(2 * 2 * 6, dtype=np.int32).reshape(2, 2, 6)
a = multihost.put_dev(x, mesh, P("r", "c"))
assert len({s.device for s in a.addressable_shards}) == 4
for s in a.addressable_shards:
    assert s.data.shape == (1, 1, 6), s.data.shape
    np.testing.assert_array_equal(np.asarray(s.data), x[s.index])
rep = multihost.put_replicated(np.arange(3, dtype=np.int32), mesh)
assert rep.sharding.is_fully_replicated
assert [s.data.shape for s in rep.addressable_shards] == [(3,)] * 4

SCALE = 8
n = 1 << SCALE
edges = np.asarray(rmat_edges(jax.random.key(0), SCALE, 8))
graph = DistGraph.from_edges(edges, BFSConfig(grid=(2, 2)), n=n)
for arr in (graph.csc.col_off, graph.csc.row_idx, graph.csc.nnz):
    shards = arr.addressable_shards
    assert len({s.device for s in shards}) == 4, arr.sharding
    assert all(s.data.size * 4 == arr.size for s in shards), arr.shape
roots = np.flatnonzero(np.bincount(edges[0], minlength=n) > 0)[:4]
session = graph.session()
batch = session.bfs(roots.astype(np.int32))
for b, r in enumerate(roots):
    one = session.bfs(int(r))
    np.testing.assert_array_equal(np.asarray(batch.level[b]),
                                  np.asarray(one.level))
    np.testing.assert_array_equal(np.asarray(batch.pred[b]),
                                  np.asarray(one.pred))
print("OK")
