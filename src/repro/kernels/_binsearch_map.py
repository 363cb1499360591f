"""Thread->edge mapping kernel (paper sec. 3.4, Alg. 3 line 2).

GPU original: every thread runs an independent binary search of its global id
in the cumulative-degree array (log F divergent scalar gathers per lane).

TPU adaptation (DESIGN.md sec. 3): edge ids handled by one tile are
CONSECUTIVE, so their frontier indices k form a non-decreasing run
[k0, k_last] (the same monotonicity the paper's sec. 3.4.1 optimisation
exploits to amortise searches across a thread's edge group).  We therefore:
  1. find k0 for the tile's first id with ONE scalar binary search;
  2. count, per lane, the cumul entries in (k0, ...] that are <= gid, with
     W-wide windowed broadcast-compares -- dense (TILE x W) VPU ops;
  3. k = k0 + count.
The loop runs ceil((k_last - k0 + 1) / W) times: total work O(TILE * span/W)
vector ops instead of O(TILE log F) divergent scalar ops.

cumul must be CLIPPED by the caller: entries at index > front_total set to
I32_MAX (`clip_cumul` below) so the window loop terminates after the live
frontier prefix.

`map_workload_tile` (repro.core.frontier, where the jnp reference path runs
it vmapped over tiles) is the kernel body on VALUES: it is the
workload-mapping STAGE of the fused local-expand pipeline
(repro.kernels.expand) and the whole kernel of the standalone
`binsearch_map` op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.frontier import map_workload_tile

I32_MAX = jnp.int32(jnp.iinfo(jnp.int32).max)


def clip_cumul(cumul, front_total):
    """Entries past the live frontier -> I32_MAX (terminates the kernel's
    window loop right after the prefix; see module docstring)."""
    idx = jnp.arange(cumul.shape[0], dtype=jnp.int32)
    return jnp.where(idx <= front_total, cumul, I32_MAX)


def _kernel(gids_ref, cumul_ref, k_ref, *, window: int, n_cumul: int):
    # the cumul block sits whole in VMEM; read it ONCE into a value so the
    # while loops stay ref-free
    k_ref[...] = map_workload_tile(gids_ref[...], cumul_ref[...],
                                   window=window, n_cumul=n_cumul)


@functools.partial(jax.jit,
                   static_argnames=("tile", "window", "interpret"))
def binsearch_map(cumul, gids, *, tile: int = 512, window: int = 256,
                  interpret: bool):
    """k[t] = max { l : cumul[l] <= gids[t] }; gids must be sorted ascending
    (they are consecutive edge ids in the BFS).  cumul int32 non-decreasing.
    """
    n_cumul = cumul.shape[0]
    e = gids.shape[0]
    assert e % tile == 0, "pad gids to a multiple of tile"
    if n_cumul < window:  # tiny frontier: pad so the window load is legal
        cumul = jnp.concatenate(
            [cumul, jnp.full((window - n_cumul,), I32_MAX, jnp.int32)])
        n_cumul = window
    grid = (e // tile,)
    return pl.pallas_call(
        functools.partial(_kernel, window=window, n_cumul=n_cumul),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile,), lambda t: (t,)),       # gid tile -> VMEM
            pl.BlockSpec((n_cumul,), lambda t: (0,)),    # cumul stays whole
        ],
        out_specs=pl.BlockSpec((tile,), lambda t: (t,)),
        out_shape=jax.ShapeDtypeStruct((e,), jnp.int32),
        interpret=interpret,
    )(gids, cumul)
