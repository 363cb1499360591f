"""Worker: drive a GraphServer with open-loop mixed traffic on forced host
devices and print tagged result lines (parsed by benchmarks/serve_load.py):

  LOAD,offered_qps,qps,p50_ms,p99_ms,n_ok,n_failed,mean_occupancy,bitexact
  FAULT,injected,failed,ok_after,retries
  CACHE,graph,size,maxsize,hits,misses,evictions
  TENANT,tenant,queries,ok,failed,rejected,edges_scanned

Two resident graphs (scale S and S-1, both weighted so SSSP serves), one
server on an R x C simulated-device mesh.  The offered-load points are
derived from the measured single-query time t1: [0.25, 1, 4] / t1 -- below,
at, and far beyond what sequential dispatch could sustain, so the highest
point MUST coalesce (mean batch occupancy > 1) to keep up.  Traffic mixes
BFS / CC / SSSP / multi-BFS across both graphs and two tenants; every
response is checked bit-identical against direct GraphSession references
computed before the server starts.  After the load sweep, a fault drill
injects one poisoned request (a FaultInjector covering every retry attempt)
into a batch of good ones and verifies the server keeps serving.

Latency is end-to-end: ticket submission -> QueryResult.t_done (admission
wait + batching window + execution), reported as p50/p99 per offered-load
point.  The gates downstream are on correctness counters and occupancy,
never wall-clock.

Usage: serve_worker.py SCALE EF R C N_REQ
"""
import os
import sys
import time

SCALE, EF = int(sys.argv[1]), int(sys.argv[2])
R, C = int(sys.argv[3]), int(sys.argv[4])
N_REQ = int(sys.argv[5])

os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={R * C}")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import numpy as np

from repro.api import BFSConfig, DistGraph
from repro.dist.compat import make_mesh
from repro.graphgen import rmat_edges
from repro.launch.compile_cache import use_compile_cache
from repro.runtime.fault import FaultInjector, RetryPolicy
from repro.serve import GraphServer, ServeConfig

use_compile_cache()

mesh = make_mesh((R, C), ("r", "c"))
config = BFSConfig(grid=(R, C), edge_chunk=16384, fold_codec="list")


def plan(scale, seed):
    n = 1 << scale
    edges = np.asarray(rmat_edges(jax.random.key(seed), scale, EF))
    w = ((np.abs(edges[0] * 31 + edges[1]) % 254) + 1).astype(np.uint8)
    g = DistGraph.from_edges(edges, config, mesh=mesh, n=n, weights=w)
    deg = np.bincount(edges[0], minlength=n)
    roots = np.flatnonzero(deg > 0)[:64:8].astype(np.int32)  # 8-root pool
    return g, roots


GRAPHS = {"web": plan(SCALE, 42), "road": plan(SCALE - 1, 7)}
K_SOURCES = {name: roots[:4] for name, (_, roots) in GRAPHS.items()}

server = GraphServer(
    {name: g for name, (g, _) in GRAPHS.items()},
    ServeConfig(max_batch=8, window_s=0.005,
                retry=RetryPolicy(max_retries=1, backoff_s=0.01)))
server.warm(("bfs", "sssp", "cc"))

# direct-session references for every (graph, program, root) the traffic can
# emit -- computed BEFORE the executors start, so the bit-exactness check
# compares against an untouched session-layer run
REF = {}
for name, (g, roots) in GRAPHS.items():
    sess = server._workers[name].session_for(config)
    for r in roots:
        ob = sess.bfs(int(r))
        REF[(name, "bfs", int(r))] = (np.asarray(ob.level),
                                      np.asarray(ob.pred))
        REF[(name, "sssp", int(r))] = np.asarray(sess.sssp(int(r)).dist)
    REF[(name, "cc")] = np.asarray(sess.connected_components().labels)
    om = sess.multi_bfs(K_SOURCES[name])
    REF[(name, "multi_bfs")] = (np.asarray(om.level), np.asarray(om.src))

# measured single-query time anchors the offered-load sweep
sess0 = server._workers["web"].session_for(config)
_times = []
for _ in range(3):
    _t0 = time.perf_counter()
    jax.block_until_ready(sess0.bfs(int(GRAPHS["web"][1][0])).level)
    _times.append(time.perf_counter() - _t0)
t1 = min(_times)

server.start()

# request mixture: bfs-heavy with cc/sssp/multi_bfs riders, two tenants,
# alternating graphs (i -> (program, graph, tenant))
MIX = ("bfs", "bfs", "sssp", "bfs", "cc", "bfs", "sssp", "multi_bfs")


def check(name, program, root, value) -> bool:
    if program == "bfs":
        lvl, pred = REF[(name, "bfs", root)]
        return (np.array_equal(np.asarray(value.level), lvl)
                and np.array_equal(np.asarray(value.pred), pred))
    if program == "sssp":
        return np.array_equal(np.asarray(value.dist),
                              REF[(name, "sssp", root)])
    if program == "cc":
        return np.array_equal(np.asarray(value.labels), REF[(name, "cc")])
    lvl, src = REF[(name, "multi_bfs")]
    return (np.array_equal(np.asarray(value.level), lvl)
            and np.array_equal(np.asarray(value.src), src))


tenant_totals = {}


def fold_tenants():
    for t, s in server.accounting.snapshot()["tenants"].items():
        agg = tenant_totals.setdefault(t, dict.fromkeys(s, 0))
        for k, v in s.items():
            agg[k] += v


def run_point(offered_qps: float):
    server.accounting.reset()
    gap = 1.0 / offered_qps
    inflight = []               # (ticket, t_submit, graph, program, root)
    t_first = time.perf_counter()
    for i in range(N_REQ):
        target = t_first + i * gap          # open loop: fixed schedule
        while time.perf_counter() < target:
            time.sleep(min(gap / 4, 1e-3))
        program = MIX[i % len(MIX)]
        name = ("web", "road")[i % 2]
        roots = GRAPHS[name][1]
        tenant = ("alice", "bob")[i % 3 == 0]
        root = int(roots[i % len(roots)])
        if program == "cc":
            ticket = server.connected_components(name, tenant=tenant)
        elif program == "multi_bfs":
            ticket = server.multi_bfs(name, K_SOURCES[name], tenant=tenant)
        else:
            ticket = server.submit(name, program, root, tenant=tenant)
        inflight.append((ticket, time.perf_counter(), name, program, root))
    server.drain()
    lat, n_ok, n_failed, bitexact = [], 0, 0, True
    t_last = t_first
    for ticket, t_submit, name, program, root in inflight:
        res = ticket.result(timeout=60)
        lat.append(res.t_done - t_submit)
        t_last = max(t_last, res.t_done)
        if res.ok:
            n_ok += 1
            bitexact &= check(name, program, root, res.value)
        else:
            n_failed += 1
    occ = server.accounting.occupancy()
    fold_tenants()
    print(f"LOAD,{offered_qps:.3f},{n_ok / (t_last - t_first):.3f},"
          f"{np.percentile(lat, 50) * 1e3:.3f},"
          f"{np.percentile(lat, 99) * 1e3:.3f},{n_ok},{n_failed},"
          f"{occ:.3f},{str(bool(bitexact)).lower()}")


for mult in (0.25, 1.0, 4.0):
    run_point(mult / t1)

# fault drill: one poisoned request (injector fires on EVERY attempt, so
# batch retries exhaust and the isolation replay fails it alone) coalesced
# with good batchmates; the server must keep serving afterwards
server.accounting.reset()
roots = GRAPHS["web"][1]
good = [server.bfs("web", int(roots[i]), tenant="alice") for i in range(2)]
poisoned = server.bfs(
    "web", int(roots[2]), tenant="bob",
    injector=FaultInjector({i: RuntimeError for i in range(64)}))
good.append(server.bfs("web", int(roots[3]), tenant="alice"))
server.drain()
after = [server.bfs("web", int(roots[i]), tenant="alice") for i in range(4)]
server.drain()
pres = poisoned.result(timeout=60)
assert not pres.ok and "injected" in pres.error, pres
n_failed = sum(0 if t.result(timeout=60).ok else 1 for t in good + after)
ok_after = sum(1 for i, t in enumerate(after)
               if t.result(timeout=60).ok
               and check("web", "bfs", int(roots[i]),
                         t.result(timeout=60).value))
stats = server.metrics_snapshot()
fold_tenants()
print(f"FAULT,1,{n_failed + 1},{ok_after},"
      f"{stats['runners']['web']['retries']}")
for name, cache in stats["aot_cache"].items():
    print(f"CACHE,{name},{cache.get('size', '')},{cache.get('maxsize', '')},"
          f"{cache.get('hits', '')},{cache.get('misses', '')},"
          f"{cache.get('evictions', '')}")
for tenant in sorted(tenant_totals):
    s = tenant_totals[tenant]
    print(f"TENANT,{tenant},{s['queries']},{s['ok']},{s['failed']},"
          f"{s['rejected']},{s['edges_scanned']}")
server.stop()
