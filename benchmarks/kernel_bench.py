"""Kernel microbenches (sec. 3.4.1 analog).

CPU container caveat: Pallas interpret mode executes the kernel body in
Python, so absolute times are NOT TPU times.  What we measure here:
  * correctness parity kernel-vs-oracle at bench shapes (gate),
  * the ORACLE path timings (XLA-compiled jnp) for the CPU baseline,
  * the work-model ratio for the TPU adaptation (broadcast-compare search:
    vector ops per edge vs log2(F) scalar gathers per edge).
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit


def main():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.kernels import binsearch_map, clip_cumul, local_expand, \
        visited_filter
    from repro.kernels import ref as R

    rng = np.random.default_rng(0)
    rows = [("name", "us_per_call", "derived")]

    for F_SZ, E in [(1024, 1 << 15), (8192, 1 << 18)]:
        deg = rng.integers(0, 64, size=F_SZ).astype(np.int32)
        cumul = jnp.asarray(np.concatenate([[0], np.cumsum(deg)]),
                            jnp.int32)
        gids = jnp.arange(E, dtype=jnp.int32)
        cc = clip_cumul(cumul, jnp.int32(F_SZ))
        k_kernel = binsearch_map(cc, gids, tile=512, window=256,
                                 interpret=True)
        k_ref = R.binsearch_map_ref(cumul, gids)
        ok = np.asarray(gids) < int(cumul[-1])
        assert (np.asarray(k_kernel)[ok] == np.asarray(k_ref)[ok]).all()
        f = jax.jit(lambda c, g: R.binsearch_map_ref(c, g))
        t = timeit(lambda: jax.block_until_ready(f(cumul, gids)))
        rows.append((f"binsearch_map_ref_F{F_SZ}_E{E}",
                     f"{t * 1e6:.0f}", "parity_ok"))

    v = jnp.asarray(rng.integers(0, 1 << 16, size=1 << 15), jnp.int32)
    valid = jnp.asarray(rng.random(1 << 15) < 0.8)
    words = jnp.asarray(
        rng.integers(0, 2**32, size=(1 << 16) // 32, dtype=np.uint64)
        .astype(np.uint32))
    won = visited_filter(v, valid, words, tile=256, interpret=True)
    wref = [R.visited_filter_ref(v[i:i + 256], valid[i:i + 256], words)
            for i in range(0, 1 << 15, 256)]
    assert (np.asarray(won) == np.concatenate([np.asarray(w) for w in wref])).all()
    f2 = jax.jit(lambda v, val, w: R.visited_filter_ref(v[:256], val[:256], w))
    t2 = timeit(lambda: jax.block_until_ready(f2(v, valid, words)))
    rows.append(("visited_filter_ref_tile256", f"{t2 * 1e6:.0f}", "parity_ok"))

    # the FUSED op (DESIGN.md sec. 9): reference-path timing + cross-path
    # parity gate at a bench shape
    n = 1 << 12
    fdeg = rng.integers(0, 16, size=n).astype(np.int32)
    col_off = jnp.asarray(np.concatenate([[0], np.cumsum(fdeg)]), jnp.int32)
    row_idx = jnp.asarray(rng.integers(0, n, size=int(fdeg.sum())), jnp.int32)
    front = jnp.arange(n, dtype=jnp.int32)
    vis = jnp.zeros((n,), bool)
    ref = local_expand((front, n), (col_off, row_idx), vis, path="reference",
                       edge_chunk=4096)
    pal = local_expand((front, n), (col_off, row_idx), vis,
                       path="pallas-interpret", edge_chunk=4096)
    assert (np.asarray(ref.verts) == np.asarray(pal.verts)).all()
    assert (np.asarray(ref.parents) == np.asarray(pal.parents)).all()
    t3 = timeit(lambda: jax.block_until_ready(local_expand(
        (front, n), (col_off, row_idx), vis, path="reference",
        edge_chunk=4096).verts))
    rows.append((f"local_expand_ref_n{n}", f"{t3 * 1e6:.0f}", "parity_ok"))
    emit(rows, "kernel_bench")


if __name__ == "__main__":
    main()
