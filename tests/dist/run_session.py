"""Subprocess integration check for the session API on a real device grid:

  * `DistGraph.from_edges` plans once on an R x C forced-host-device mesh
    (CSR twin only when direction is on);
  * batched `GraphSession.bfs` is bit-exact vs per-root queries AND the
    python reference, for the list codec and for direction optimisation;
  * every sweep runs with `validate=` on, so multi-device CI checks the
    Graph500 rules (tree/level/edge consistency), not just bit-equality;
  * a multi-root sweep traces the level loop exactly once (AOT cache);
  * the degenerate 1 x P topology works through the same session API.

Usage: run_session.py R C
"""
import os
import sys

R, C = int(sys.argv[1]), int(sys.argv[2])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={R * C}"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import numpy as np

from repro.api import BFSConfig, DistGraph
from repro.core import bfs_reference_py
from repro.dist.compat import make_mesh
from repro.graphgen import rmat_edges, build_csc
from repro.obs.trace import RECORDED_LEVELS

SCALE, EF = 9, 8
n = 1 << SCALE
edges_np = np.asarray(rmat_edges(jax.random.key(0), SCALE, EF))
co, ri = build_csc(edges_np, n)
deg = np.bincount(edges_np[0], minlength=n)
roots = np.random.default_rng(3).choice(np.flatnonzero(deg > 0), 8,
                                        replace=False)


def check_batch(sess, what):
    # validate= runs the Graph500 rules on every root inside the session
    # (explicit edge array: the direction session has released the host
    # copy to plan its CSR twin)
    bout = sess.bfs(roots, validate=edges_np)
    assert sess.engine.trace_count == 1, f"{what}: sweep traced more than once"
    for b, root in enumerate(roots):
        ref, _ = bfs_reference_py(co, ri, int(root), n)
        lvl = np.asarray(bout.level[b])[:n]
        assert (lvl == ref).all(), f"{what}: levels mismatch at root {root}"
    # batched == sequential, bit-exact (scalar goes through the B=1 program)
    sout = sess.bfs(int(roots[0]))
    assert (np.asarray(bout.level[0]) == np.asarray(sout.level)).all(), what
    assert (np.asarray(bout.pred[0]) == np.asarray(sout.pred)).all(), what
    assert bout.edges_scanned[0] == sout.edges_scanned, what
    return bout


# --- 2D grid, top-down (CSR must NOT be planned) ---------------------------
graph = DistGraph.from_edges(
    edges_np, BFSConfig(grid=(R, C), edge_chunk=2048), n=n)
assert graph.csr is None, "CSR twin built without direction"
check_batch(graph.session(), "2d")
# validate=True resolves to the retained host edges while CSR is unplanned
graph.session().bfs(int(roots[0]), validate=True)

# --- direction optimisation over the SAME resident graph (lazy CSR) --------
dsess = graph.session(BFSConfig(grid=(R, C), edge_chunk=2048,
                                direction=True))
assert graph.csr is not None
dout = check_batch(dsess, "direction")
dirs = np.asarray(dout.directions)
assert dirs.shape == (len(roots), RECORDED_LEVELS), "directions shape"
live = dirs[0][dirs[0] >= 0]
assert live.size == int(dout.n_levels[0]) - 1, "one decision per level"
assert (live == 0).any() and (live == 1).any(), \
    f"adaptive must exercise both directions on RMAT, got {live}"

# --- forced bottom-up: every level pulls, still bit-identical ---------------
bsess = graph.session(BFSConfig(grid=(R, C), edge_chunk=2048,
                                direction="bottomup"))
bout = bsess.bfs(roots)
assert (np.asarray(bout.level) == np.asarray(dout.level)).all(), "bottomup"
assert (np.asarray(bout.pred) == np.asarray(dout.pred)).all(), "bottomup"
bdirs = np.asarray(bout.directions)
assert (bdirs[bdirs >= 0] == 1).all(), "bottomup mode must never push"

# --- fold codecs agree through the session, bit-exact ----------------------
base = graph.session().bfs(roots)
for codec in ("bitmap", "delta"):
    out = graph.session(BFSConfig(grid=(R, C), edge_chunk=2048,
                                  fold_codec=codec)).bfs(roots)
    assert (np.asarray(out.level) == np.asarray(base.level)).all(), codec
    assert (np.asarray(out.pred) == np.asarray(base.pred)).all(), codec
    assert out.edges_scanned == base.edges_scanned, codec

# --- degenerate 1 x P topology through the same API ------------------------
mesh1 = make_mesh((R * C,), ("p",))
g1 = DistGraph.from_edges(
    edges_np,
    BFSConfig(grid=(1, R * C), row_axes=(), col_axes=("p",),
              edge_chunk=2048),
    mesh=mesh1, n=n)
check_batch(g1.session(), "1d")

print("OK")
