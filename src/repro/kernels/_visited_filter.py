"""Visited-bitmap filter kernel (paper Alg. 3 lines 5-8, the atomicOr dedup).

Per edge tile: test each candidate vertex's bit in the visited bitmap and
keep only the FIRST slot carrying each vertex -- exactly the winner that the
Kepler atomicOr race would elect, but deterministic.

TPU adaptation: the race is replaced by a dense triangular self-compare of
the tile (TILE x TILE bool ops on the VPU), and the word lookup is a dynamic
gather over the bitmap held in VMEM (Mosaic lowers 1D int32 dynamic gathers
to the VPU; the bitmap for 2^20 local rows is 128 KiB).  Bit SETTING stays
outside (an XLA scatter): grid steps are sequential per core so a fused
in-kernel RMW is legal on TPU, but the scatter keeps the kernel read-only and
lets XLA fuse the set with the level/pred updates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def filter_tile(v, valid, words):
    """The kernel body on VALUES: bitmap test + first-occurrence dedup for
    ONE tile.  Also the visited-filter STAGE of the fused local-expand
    pipeline (repro.kernels.expand)."""
    n_words = words.shape[0]
    w = jnp.clip(v >> 5, 0, n_words - 1)
    old = jnp.take(words, w, axis=0)
    bit = (old >> (v & 31).astype(jnp.uint32)) & jnp.uint32(1)
    unvis = valid & (bit == 0)
    tile = v.shape[0]
    eq = (v[:, None] == v[None, :]) & valid[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    dup = jnp.any(eq & (jj < ii), axis=1)
    return unvis & ~dup


def _kernel(v_ref, valid_ref, words_ref, won_ref):
    won_ref[...] = filter_tile(v_ref[...], valid_ref[...], words_ref[...])


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def visited_filter(v, valid, bitmap_words, *, tile: int = 256,
                   interpret: bool):
    """won (bool, same shape as v): first unvisited occurrence per vertex.

    NOTE: dedup is per-TILE (as the paper's dedup is per-race-window); the
    caller's scatter-min winner selection handles cross-tile duplicates.
    """
    e = v.shape[0]
    assert e % tile == 0
    nw = bitmap_words.shape[0]
    return pl.pallas_call(
        _kernel,
        grid=(e // tile,),
        in_specs=[
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec((nw,), lambda t: (0,)),
        ],
        out_specs=pl.BlockSpec((tile,), lambda t: (t,)),
        out_shape=jax.ShapeDtypeStruct((e,), bool),
        interpret=interpret,
    )(v, valid, bitmap_words)
