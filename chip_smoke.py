"""Chip smoke: the BFS system's main path once, on the chip.

    python chip_smoke.py               # one chip: every phase, scale 20
    python chip_smoke.py --chips 4     # four chips: 1x1 vs 2x2 vs 1x4 only
    JAX_PLATFORMS=cpu python chip_smoke.py --scale 12   # CPU rehearsal

One process drives everything through the entry points a user calls:
`DistGraph.from_edges` plans a Graph500 R-MAT graph (edge factor 16, from a
fixed seed) onto the chip, `GraphSession` answers BFS and connected-
components queries, and a `GraphServer` answers the same queries from two
client threads.  Each phase checks its answers against the repository's own
means: Graph500 validation (`repro.core.validate.validate_bfs`), the numpy
CC reference (`repro.algos.reference.cc_reference`), and bit-identity
between the scalar, batched, direction-optimised, traced and served paths.

Query wall times (ended by `block_until_ready`) are printed as first-run
chip times; they are not a benchmark.

The last line of stdout is one JSON object,
`{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}`.
It is `"ok": true` only when every phase passed on a TPU.  Without a TPU
the script refuses the default run before planning anything; with an
explicit --scale it runs every phase as a rehearsal and still ends with
`"ok": false` and a non-zero exit.  It never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import resource
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
EF, N_ROOTS, SEED = 16, 8, 1
# R-MAT scale of both runs.  The one-chip run makes about 50 searches on
# the jnp reference path and took 530 s at scale 20 on a v5e (PERF.md,
# section 6); each scale doubles the edges, so scale 21 would come too
# near the 1200 s the run is allowed.
SCALE = 20
EDGE_CHUNK = 1 << 16
VALIDATE_THREADS = 4   # numpy releases the GIL; each holds int64 edge copies


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_rss_gib() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20


def block(out):
    import jax
    jax.block_until_ready(out)
    return out


def timed(fn):
    t0 = time.perf_counter()
    out = block(fn())
    return out, time.perf_counter() - t0


def memory_report(jax) -> None:
    for d in jax.devices():
        st = d.memory_stats() or {}
        keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        log(f"memory {d}: " + ", ".join(
            f"{k}={st[k]}" for k in keys if k in st) if st
            else f"memory {d}: not reported by this backend")
    log(f"host peak RSS {peak_rss_gib():.2f} GiB")


def versions(jax) -> None:
    from importlib import metadata

    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}")
    log(f"devices: {jax.devices()}")


class Phases:
    """Runs named phases, reports each, and remembers whether all passed."""

    def __init__(self):
        self.failed = []

    def run(self, name, fn, *args):
        log(f"--- {name}")
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        except Exception:
            self.failed.append(name)
            log(f"FAIL {name} after {time.perf_counter() - t0:.2f}s\n"
                f"{traceback.format_exc()}")
            return None
        log(f"PASS {name} ({time.perf_counter() - t0:.2f}s)")
        return out


def generate(jax, np, scale):
    """R-MAT edges on the default device, then to the host for planning."""
    from repro.graphgen import rmat_edges

    t0 = time.perf_counter()
    edges = np.asarray(rmat_edges(jax.random.key(SEED), scale, EF))
    n = 1 << scale
    deg = np.bincount(edges[0], minlength=n)
    roots = np.random.default_rng(SEED).choice(
        np.flatnonzero(deg > 0), N_ROOTS, replace=False).astype(np.int32)
    log(f"R-MAT scale {scale}, edge factor {EF}: n={n}, "
        f"{edges.shape[1]} directed edges, generated in "
        f"{time.perf_counter() - t0:.2f}s; roots {roots.tolist()} "
        f"(degrees {deg[roots].tolist()})")
    return edges, roots


def plan(jax, edges, n, grid, exchange="flat", devices=None):
    from repro.api import BFSConfig, DistGraph
    from repro.dist.compat import make_mesh

    config = BFSConfig(grid=grid, edge_chunk=EDGE_CHUNK, exchange=exchange)
    mesh = None if devices is None else make_mesh(grid, ("r", "c"),
                                                  devices=devices)
    t0 = time.perf_counter()
    graph = DistGraph.from_edges(edges, config, n=n, mesh=mesh)
    block(graph.csc.row_idx)
    log(f"planned {grid[0]}x{grid[1]} in {time.perf_counter() - t0:.2f}s: "
        f"row_idx {graph.csc.row_idx.shape}, host peak RSS "
        f"{peak_rss_gib():.2f} GiB")
    return graph


def validate_roots(edges, n, roots, level, pred) -> None:
    """Graph500-validate every root's (level, pred), the edge keys sorted
    once; raises on the first invalid root."""
    import numpy as np

    from repro.core.validate import edge_keys, validate_bfs

    keys = edge_keys(edges, n)
    with concurrent.futures.ThreadPoolExecutor(VALIDATE_THREADS) as pool:
        list(pool.map(
            lambda b: validate_bfs(edges, np.asarray(level[b])[:n],
                                   np.asarray(pred[b])[:n], int(roots[b]),
                                   keys),
            range(len(roots))))


def check(ok, what) -> None:
    """A failed check fails its phase (unlike assert, also under -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def same(a, b) -> bool:
    import numpy as np
    return np.array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------------
# One chip: every phase
# ----------------------------------------------------------------------------

def one_chip(jax, np, scale, phases: Phases) -> None:
    from repro.algos.reference import cc_reference
    from repro.obs import LevelTrace

    n = 1 << scale
    edges, roots = phases.run("generate", generate, jax, np, scale)
    graph = phases.run("plan 1x1", plan, jax, edges, n, (1, 1))
    memory_report(jax)
    session = graph.session()
    direct = {}

    def scalar_queries():
        for i, r in enumerate(roots):
            out, dt = timed(lambda: session.bfs(int(r)))
            direct[int(r)] = out
            log(f"root {r}: {int(out.n_levels)} levels, "
                f"{out.edges_scanned} edges scanned, {dt:.4f}s"
                + (" (includes compile)" if i == 0 else ""))

    phases.run("scalar BFS x8", scalar_queries)

    def validate():
        validate_roots(edges, n, roots,
                       [direct[int(r)].level for r in roots],
                       [direct[int(r)].pred for r in roots])
        log(f"Graph500 validation: {len(roots)}/{len(roots)} roots valid")

    phases.run("validate", validate)

    def batched():
        out, dt = timed(lambda: session.bfs(roots))
        log(f"batched B={len(roots)}: {dt:.4f}s (includes compile)")
        for b, r in enumerate(roots):
            s = direct[int(r)]
            check(same(out.level[b], s.level), f"level differs, root {r}")
            check(same(out.pred[b], s.pred), f"pred differs, root {r}")
            check(int(out.n_levels[b]) == int(s.n_levels), r)
            check(out.edges_scanned[b] == s.edges_scanned, r)
        log("batched results bit-identical to scalar: 8/8")

    phases.run("batched BFS", batched)

    def direction():
        t0 = time.perf_counter()
        dsess = graph.session(dataclasses.replace(config, direction=True))
        block(graph.csr["col_idx"])
        log(f"CSR twin planned in {time.perf_counter() - t0:.2f}s, host "
            f"peak RSS {peak_rss_gib():.2f} GiB")
        out, dt = timed(lambda: dsess.bfs(roots))
        log(f"direction batched B={len(roots)}: {dt:.4f}s (includes "
            f"compile)")
        preds_equal = 0
        for b, r in enumerate(roots):
            s = direct[int(r)]
            check(same(out.level[b], s.level), f"level differs, root {r}")
            preds_equal += same(out.pred[b], s.pred)
        log(f"direction levels identical to top-down: 8/8 "
            f"(preds identical: {preds_equal}/8)")

    phases.run("direction BFS", direction)

    def telemetry():
        tsess = graph.session(dataclasses.replace(config, telemetry=True))
        r = int(roots[0])
        out, dt = timed(lambda: tsess.bfs(r))
        s = direct[r]
        check(same(out.level, s.level) and same(out.pred, s.pred), r)
        check(isinstance(out.trace, LevelTrace), type(out.trace))
        log(f"telemetry: outputs identical, LevelTrace with "
            f"{out.trace.n_levels} levels attached ({dt:.4f}s incl. "
            f"compile)")

    phases.run("telemetry BFS", telemetry)

    cc_direct = {}

    def connected_components():
        out, dt = timed(lambda: session.connected_components())
        cc_direct["out"] = out
        t0 = time.perf_counter()
        ref = cc_reference(edges, n)
        labels = np.asarray(out.labels)[:n]
        check(np.array_equal(labels, ref), "CC labels differ from reference")
        log(f"CC: {len(np.unique(labels))} components, equal to "
            f"cc_reference ({dt:.4f}s on device incl. compile, "
            f"{time.perf_counter() - t0:.2f}s reference)")

    phases.run("connected components", connected_components)

    def serve():
        from repro.serve import GraphServer, ServeConfig

        with GraphServer({"g": graph},
                         ServeConfig(max_batch=8, window_s=0.005)) as server:
            t0 = time.perf_counter()
            server.warm(("bfs",))
            log(f"server warm: {time.perf_counter() - t0:.2f}s")
            tickets, lock = [], threading.Lock()

            def client(order):
                mine = [(int(r), server.bfs("g", int(r))) for r in order]
                with lock:
                    tickets.extend(mine)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(o,))
                       for o in (roots, roots[::-1])]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            cc_ticket = server.connected_components("g")
            server.drain(timeout=600)
            dt = time.perf_counter() - t0
            for r, ticket in tickets:
                res = ticket.result(timeout=60)
                check(res.ok, res.error)
                s = direct[r]
                check(same(res.value.level, s.level), f"level, root {r}")
                check(same(res.value.pred, s.pred), f"pred, root {r}")
                check(res.value.edges_scanned == s.edges_scanned, r)
            res = cc_ticket.result(timeout=60)
            check(res.ok, res.error)
            check(same(res.value.labels, cc_direct["out"].labels), "CC")
            snap = server.metrics_snapshot()
        log(f"server: {len(tickets)} BFS + 1 CC answers equal to the "
            f"direct session results, {dt:.4f}s, {snap['n_batches']} "
            f"batches, mean occupancy {snap['mean_occupancy']:.2f}")

    phases.run("GraphServer", serve)
    memory_report(jax)


# ----------------------------------------------------------------------------
# Four chips: the paper's 2D partition against one chip
# ----------------------------------------------------------------------------

def four_chips(jax, np, scale, phases: Phases) -> None:
    n = 1 << scale
    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs four devices, found {devices}")
    edges, roots = phases.run("generate", generate, jax, np, scale)
    base = {}

    def reference():
        g = plan(jax, edges, n, (1, 1), devices=devices[:1])
        out, dt = timed(lambda: g.session().bfs(roots))
        log(f"1x1 on {devices[0]}: batched B={len(roots)} {dt:.4f}s "
            f"(includes compile)")
        base["level"] = np.asarray(out.level)

    phases.run("1x1 on device 0", reference)

    def grid_phase(grid, exchange, want_exchange):
        g = plan(jax, edges, n, grid, exchange=exchange,
                 devices=devices[:4])
        session = g.session()
        check(session.config.exchange == want_exchange, session.config.exchange)
        row_idx = g.csc.row_idx
        shards = row_idx.addressable_shards
        check(len({s.device for s in shards}) == 4, shards)
        for s in shards:
            check(s.data.size * 4 == row_idx.size, (s.device, s.data.shape))
            log(f"  row_idx shard on {s.device}: {s.data.shape} = 1/4 of "
                f"{row_idx.shape} ({s.data.nbytes} bytes)")
        out, dt = timed(lambda: session.bfs(roots))
        log(f"{grid[0]}x{grid[1]} {want_exchange}: batched B={len(roots)} "
            f"{dt:.4f}s (includes compile)")
        level = np.asarray(out.level)
        check(np.array_equal(level, base["level"]),
              "levels differ from the 1x1 run")
        validate_roots(edges, n, roots, level, np.asarray(out.pred))
        log(f"{grid[0]}x{grid[1]} {want_exchange}: levels bit-identical to "
            f"1x1 for 8/8 roots, 8/8 preds Graph500-valid")
        memory_report(jax)

    phases.run("2x2 flat", grid_phase, (2, 2), "flat", "flat")
    phases.run("1x4 auto", grid_phase, (1, 4), "auto", "butterfly")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip partition phase")
    ap.add_argument("--scale", type=int, default=None,
                    help=f"R-MAT scale (default {SCALE}); required off "
                         f"TPU")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2

    import jax
    import numpy as np

    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    versions(jax)
    log(f"compile cache: {cache}")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    phases = Phases()
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.scale is None:
        log(f"no TPU: JAX found {dev.platform!r}; pass --scale to rehearse "
            f"every phase off the chip (it still fails)")
        phases.failed.append("no TPU")
    else:
        scale = SCALE if args.scale is None else args.scale
        run = four_chips if args.chips == 4 else one_chip
        t0 = time.perf_counter()
        try:
            run(jax, np, scale, phases)
        except Exception:           # a phase the next one depended on failed
            log(f"ABORT\n{traceback.format_exc()}")
            phases.failed.append("aborted")
        log(f"total {time.perf_counter() - t0:.2f}s, failed phases: "
            f"{phases.failed or 'none'}")
        if not on_tpu:
            phases.failed.append(f"not a TPU ({dev.platform})")
    ok = not phases.failed
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
