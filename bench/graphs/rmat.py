"""Graph500 Kronecker (R-MAT) edge list and search keys, from a seed.

The benchmark's own copy of the generator, so that no change to the program
can change the graphs it is measured on.  It follows the Graph500
specification 2.1, section 3 ("Kronecker generator"): each of
`edge_factor * 2**scale` edges picks one quadrant per bit of the vertex id,
the row bit with probability C + D and the column bit with probability
B / (A + B) in the top half and D / (C + D) in the bottom half; vertex labels
are then permuted at random.  The list is symmetrised (each edge and its
reverse), which is the input the BFS programs take.  The spec's shuffle of
the edge order is left out: neither the planner nor the reference depends on
the order.

Search keys follow Kernel 2: distinct vertices, drawn at random from those
that have an edge other than a self-loop.

The configuration's `structure_seed` fixes the Kronecker draw and the keys;
the run's seed draws the permutation of the vertex labels.  So every seed
gives the same graph and the same searches, each of the same size, under
other labels: the CSC layout, the chunks and the partition of the grid
differ from seed to seed, the work does not.  Drawing the structure from
the run's seed too made the time of a window swing with the depths of the
keys it happened to draw: `teps` of the direction-optimised cell spread
by 18% between seeds on one TPU v5e chip.
"""
from __future__ import annotations

import functools

import numpy as np


def prng_key(seed: int):
    """A JAX key from a seed of any size: `jax.random.key` keeps only the
    low 32 bits, so the high bits are folded in."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.lru_cache(maxsize=None)
def _kronecker(scale: int, edge_factor: int, a: float, b: float, c: float):
    """The jitted generator for one shape: (structure key, label key) ->
    ((2, 2M) int32 edges, (n,) int32 labels) on device."""
    import jax
    import jax.numpy as jnp

    n, m = 1 << scale, edge_factor << scale
    ab = a + b
    a_norm = a / ab
    c_norm = c / (1.0 - ab)

    @jax.jit
    def generate(k_bits, k_perm):
        def bit(ib, ij):
            u = jax.random.uniform(jax.random.fold_in(k_bits, ib), (2, m))
            ii = u[0] > ab
            jj = u[1] > jnp.where(ii, c_norm, a_norm)
            w = jnp.int32(1) << ib
            return (ij[0] + ii.astype(jnp.int32) * w,
                    ij[1] + jj.astype(jnp.int32) * w)

        zero = jnp.zeros((m,), jnp.int32)
        src, dst = jax.lax.fori_loop(0, scale, bit, (zero, zero))
        perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
        src, dst = perm[src], perm[dst]
        return jnp.stack([jnp.concatenate([src, dst]),
                          jnp.concatenate([dst, src])]), perm

    return generate


def generate(config: dict, seed: int, n_keys: int
             ) -> tuple[np.ndarray, int, np.ndarray]:
    """(edges (2, 2 * edge_factor * 2**scale) int32 on the host, n, the
    first `n_keys` search keys in the order drawn)."""
    scale = int(config["scale"])
    structure = int(config["structure_seed"])
    gen = _kronecker(scale, int(config["edge_factor"]), float(config["a"]),
                     float(config["b"]), float(config["c"]))
    edges, perm = gen(prng_key(structure), prng_key(seed))
    edges, perm = np.asarray(edges), np.asarray(perm)
    n = 1 << scale
    return edges, n, perm[search_keys(edges, perm, n_keys, structure)]


def search_keys(edges: np.ndarray, perm: np.ndarray, count: int,
                structure_seed: int) -> np.ndarray:
    """`count` distinct vertices with an edge other than a self-loop, as
    unlabelled (structure) ids, in the order drawn from `structure_seed`:
    the same searches whatever the labels."""
    src, dst = edges
    deg = np.bincount(src[src != dst], minlength=perm.size)
    unlabel = np.empty_like(perm)
    unlabel[perm] = np.arange(perm.size, dtype=perm.dtype)
    cand = np.sort(unlabel[np.flatnonzero(deg > 0)])
    if cand.size < count:
        raise ValueError(f"only {cand.size} vertices have an edge; "
                         f"{count} search keys asked for")
    rng = np.random.default_rng(int(structure_seed))
    return rng.choice(cand, count, replace=False)
