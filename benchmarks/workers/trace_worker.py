"""Worker: per-LEVEL traversal counters from the in-program telemetry
channel (DESIGN.md sec. 13) -- the consolidated replacement for the
phase-replay worker: instead of re-driving each level's phases host-side,
ONE telemetry-enabled search returns every per-level counter (frontier,
scanned edges, folded entries, fold wire bytes, direction) from inside the
compiled while_loop, and each counter is cross-checked against an
independent recomputation (np.bincount of the output levels, the codec's
static wire formula, the 64-bit edges_scanned total).

Output lines (parsed by benchmarks/bfs_breakdown.py / obs_bench.py):
  T,codec,level,frontier,scanned,folded,wire_bytes,msgs,dir  per codec/level
  W,codec,wire_bytes,wire_bytes_values                   static, per device
  A,codec,frontier_ok,wire_ok,scanned_ok,msgs_ok         trace agreement
  D,dir_ok                                               trace.direction vs
                                                         out.directions
  M,edges,<component edges>,n_levels,<levels>

MODE=obs additionally emits (telemetry-overhead + serve-span evidence):
  E,codec,on|off,lvl_sum,pred_sum        bit-identity checksums
  C,codec,traces_first,traces_second     AOT no-retrace proof
  O,rep,on_s,off_s                       alternating batched-sweep repeats
  S,spans_ok,n_events,prom_ok            serve request-trace smoke

Usage: trace_worker.py R C SCALE EF [MODE] [EVENTS_PATH]
"""
import os
import sys

R, C, SCALE, EF = (int(a) for a in sys.argv[1:5])
MODE = sys.argv[5] if len(sys.argv) > 5 else "trace"
EVENTS_PATH = sys.argv[6] if len(sys.argv) > 6 else None
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={R * C}"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import time

import jax
import numpy as np

from repro.api import BFSConfig, DistGraph
from repro.core.validate import count_component_edges
from repro.dist.compat import make_mesh
from repro.graphgen import rmat_edges
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

CODECS = ("list", "bitmap", "delta")

n = 1 << SCALE
edges_np = np.asarray(rmat_edges(jax.random.key(42), SCALE, EF))
mesh = make_mesh((R, C), ("r", "c"))
graph = DistGraph.from_edges(
    edges_np, BFSConfig(grid=(R, C), edge_chunk=16384), mesh=mesh, n=n)
grid = graph.grid

deg = np.bincount(edges_np[0], minlength=n)
root = int(np.flatnonzero(deg > 0)[0])


def cfg(codec, telemetry, direction=False):
    return BFSConfig(grid=(R, C), fold_codec=codec, edge_chunk=16384,
                     telemetry=telemetry, direction=direction)


# ---------------------------------------------------------------------------
# per-codec traced search + agreement checks
# ---------------------------------------------------------------------------
comp_edges = None
n_levels = None
for codec in CODECS:
    sess = graph.session(cfg(codec, telemetry=True))
    out = sess.bfs(root)
    tr = sess.last_trace()
    level = np.asarray(out.level)[:n]
    if comp_edges is None:
        comp_edges = count_component_edges(edges_np, level)
        n_levels = tr.n_levels
    bc = np.bincount(level[level >= 0])
    wb = sess.engine.codec.wire_bytes(grid)          # static, per device
    wbv = sess.engine.codec.wire_bytes_values(grid)
    frontier_ok = tr.n_levels == len(bc) and all(
        int(tr.frontier[k]) == int(bc[k]) for k in range(tr.n_levels))
    # BFS folds are SET folds: every level ships the codec's static frame
    # on each of the P devices (trace wire sums over devices)
    wire_ok = all(int(tr.wire_bytes[k]) == wb * grid.P
                  for k in range(tr.n_levels))
    scanned_ok = tr.total_scanned == out.edges_scanned
    # every device sends the strategy's per-exchange message count per level
    mpx = sess.engine.exchange.msgs_per_exchange(grid.C)
    msgs_ok = all(int(tr.msgs[k]) == mpx * grid.P
                  for k in range(tr.n_levels))
    for row in tr.levels():
        print(f"T,{codec},{row['level']},{row['frontier']},{row['scanned']},"
              f"{row['folded']},{row['wire_bytes']},{row['msgs']},"
              f"{row['dir']}")
    print(f"W,{codec},{wb},{wbv}")
    print(f"A,{codec},{frontier_ok},{wire_ok},{scanned_ok},{msgs_ok}")

# trace.direction must match the engine's own directions output
dsess = graph.session(cfg("list", telemetry=True, direction=True))
dout = dsess.bfs(root)
dtr = dsess.last_trace()
dirs = np.asarray(dout.directions)
dir_ok = all(int(dtr.direction[k]) == int(dirs[k])
             for k in range(dtr.n_levels))
print(f"D,{dir_ok}")
print(f"M,edges,{comp_edges},n_levels,{n_levels}")

if MODE != "obs":
    sys.exit(0)

# ---------------------------------------------------------------------------
# obs mode: bit-identity, no-retrace proof, overhead, serve spans
# ---------------------------------------------------------------------------
rng = np.random.default_rng(7)
alive = np.flatnonzero(deg > 0)
roots = np.asarray(rng.choice(alive, size=8), np.int32)

for codec in CODECS:
    on = graph.session(cfg(codec, telemetry=True))
    off = graph.session(cfg(codec, telemetry=False))
    out_on = on.bfs(roots)
    out_off = off.bfs(roots)
    for tag, o in (("on", out_on), ("off", out_off)):
        lvl_sum = int(np.asarray(o.level, np.int64).sum())
        pred_sum = int(np.asarray(o.pred, np.int64).sum())
        print(f"E,{codec},{tag},{lvl_sum},{pred_sum}")
    # no off-path (or on-path) retrace across repeated sweeps: the level
    # loop compiled once per (engine, B); a second sweep is a cache hit
    first = on.engine.trace_count
    on.bfs(roots)
    off.bfs(roots)
    print(f"C,{codec},{first},{on.engine.trace_count}")

# telemetry overhead: alternating timed batched sweeps, list codec
on = graph.session(cfg("list", telemetry=True))
off = graph.session(cfg("list", telemetry=False))
reps = 3 if os.environ.get("REPRO_BENCH_SMOKE") == "1" else 5


def sweep(sess):
    jax.block_until_ready(sess.bfs(roots).level)


sweep(on), sweep(off)                    # warm both executables
for rep in range(reps):
    t0 = time.perf_counter()
    sweep(on)
    t_on = time.perf_counter() - t0
    t0 = time.perf_counter()
    sweep(off)
    t_off = time.perf_counter() - t0
    print(f"O,{rep},{t_on:.5f},{t_off:.5f}")

# serve request-trace smoke: spans tile admit -> done in lifecycle order,
# the event log records the batches, and the Prometheus text renders
from repro.obs import PHASES
from repro.serve import GraphServer, ServeConfig

with GraphServer({"g": graph},
                 ServeConfig(max_batch=4, event_log_path=EVENTS_PATH)) as srv:
    tickets = [srv.bfs("g", int(r), tenant=("alice", "bob")[i % 2])
               for i, r in enumerate(roots[:6])]
    results = [t.result(timeout=300) for t in tickets]
    spans_ok = True
    for res in results:
        names = [s.name for s in res.trace.spans]
        ends = [s.t1 for s in res.trace.spans]
        spans_ok &= (res.ok and names == list(PHASES)
                     and all(s.t1 >= s.t0 for s in res.trace.spans)
                     and ends == sorted(ends)
                     and res.trace.spans[0].t0 <= res.trace.spans[-1].t1)
    prom = srv.prometheus()
    prom_ok = ("serve_admitted_total" in prom and "serve_pending" in prom
               and "serve_queue_wait_seconds_bucket" in prom)
    print(f"S,{spans_ok},{len(srv.events)},{prom_ok}")
