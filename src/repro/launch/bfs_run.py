"""Distributed-BFS driver CLI (the paper's workload as a service).

    PYTHONPATH=src python -m repro.launch.bfs_run --scale 14 --ef 16 \
        --roots 64 [--grid 2x2] [--fold bitmap] [--direction]
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.bfs_run \
        --devices 4 --scale 10 --ef 8 --roots 4

Built on the session API (DESIGN.md sec. 7): the graph is planned and made
resident ONCE (`DistGraph.from_edges`; the CSR twin is only partitioned when
--direction is on), then the root sweep runs through `GraphSession.bfs` --
per-root for harmonic TEPS, plus the whole batch as one compiled program for
the amortised Graph500-style number.

The grid defaults to the squarest R x C (R <= C) over the devices present:
1x1 on one chip, 2x2 on a four-chip host.  --devices simulates that many
host devices and applies to the CPU backend only."""
import argparse
import os


def squarest_grid(d: int) -> str:
    """The most nearly square "RxC" with R * C == d and R <= C."""
    r = max(x for x in range(1, int(d ** 0.5) + 1) if d % x == 0)
    return f"{r}x{d // r}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=None,
                    help="simulated host devices (CPU backend only)")
    ap.add_argument("--grid", default=None,
                    help="RxC processor grid (default: squarest over the "
                         "devices present)")
    ap.add_argument("--scale", type=int, default=14)
    ap.add_argument("--ef", type=int, default=16)
    ap.add_argument("--roots", type=int, default=64)
    ap.add_argument("--fold", default="list",
                    choices=["list", "bitmap", "delta"])
    ap.add_argument("--direction", action="store_true")
    ap.add_argument("--validate", type=int, default=4)
    args = ap.parse_args()

    if args.devices is not None:
        os.environ["XLA_FLAGS"] = " ".join(filter(None, (
            os.environ.get("XLA_FLAGS"),
            f"--xla_force_host_platform_device_count={args.devices}")))

    import time

    import jax
    import numpy as np

    from repro.api import BFSConfig, DistGraph
    from repro.core.validate import (count_component_edges, harmonic_mean,
                                     validate_bfs)
    from repro.graphgen import rmat_edges
    from repro.launch.compile_cache import use_compile_cache

    if args.devices is not None and jax.default_backend() != "cpu":
        ap.error("--devices simulates host devices; it applies to the CPU "
                 f"backend only, not {jax.default_backend()!r}")
    use_compile_cache()
    grid = args.grid or squarest_grid(jax.device_count())

    n = 1 << args.scale
    edges_np = np.asarray(rmat_edges(jax.random.key(1), args.scale, args.ef))

    config = BFSConfig(grid=grid, fold_codec=args.fold,
                       edge_chunk=16384, direction=args.direction)
    graph = DistGraph.from_edges(edges_np, config, n=n)
    session = graph.session()

    deg = np.bincount(edges_np[0], minlength=n)
    roots = np.random.default_rng(7).choice(np.flatnonzero(deg > 0),
                                            args.roots, replace=False)

    # per-root queries (harmonic-mean TEPS, the paper's headline metric);
    # the first --validate roots run the Graph500 rules AFTER the timing
    # window (the O(E) host-side check must not skew the reported TEPS)
    jax.block_until_ready(session.bfs(int(roots[0])).level)   # warm B=1
    teps, comp_m = [], []
    for i, root in enumerate(roots):
        t0 = time.perf_counter()
        out = session.bfs(int(root))
        jax.block_until_ready(out.level)
        dt = time.perf_counter() - t0
        lvl = np.asarray(out.level)[:n]
        m = count_component_edges(edges_np, lvl)
        comp_m.append(m)
        teps.append(m / dt)
        if i < args.validate:
            validate_bfs(edges_np, lvl, np.asarray(out.pred)[:n], int(root))

    # the whole sweep as ONE compiled program; harmonic-mean TEPS uses the
    # SAME count_component_edges numerators as the per-root path, over the
    # amortised per-root time sweep_s / n_roots (the batch has ONE wall
    # time), alongside the aggregate amortised number
    jax.block_until_ready(session.bfs(roots).level)           # warm B=roots
    t0 = time.perf_counter()
    bout = session.bfs(roots)
    jax.block_until_ready(bout.level)
    sweep_s = time.perf_counter() - t0
    swept = sum(comp_m)
    batched_hm = harmonic_mean([m / (sweep_s / len(roots)) for m in comp_m])

    R, C = graph.grid.R, graph.grid.C
    dev = jax.devices()[0]
    print(f"device={dev.platform}:{dev.device_kind}x{jax.device_count()} "
          f"grid={R}x{C} scale={args.scale} ef={args.ef} fold={args.fold} "
          f"dir={args.direction}: harmonic TEPS {harmonic_mean(teps):.3e} "
          f"({min(args.validate, len(roots))} validated) | "
          f"{len(roots)}-root sweep {sweep_s:.3f}s, "
          f"amortised {swept / sweep_s:.3e} TEPS, "
          f"harmonic {batched_hm:.3e} TEPS")


if __name__ == "__main__":
    main()
