"""Fused bottom-up parent-search pipeline (DESIGN.md sec. 11).

Direction-optimised BFS (Beamer et al.; Buluc & Madduri) flips dense levels:
instead of scanning the frontier's out-edges, every UNVISITED vertex scans
its own in-edges (the CSR twin) for any parent already in the frontier.  The
fused op covers the per-chunk hot path:

  stage 1  workload map    r[t] = max { l : cumul[l] <= gid[t] } over the
                           MASKED-degree cumsum (visited rows contribute 0
                           edges, so the scan walks only live rows' edges)
  stage 2  neighbor gather c = col_idx[row_off[r] + gid - cumul[r]] (CSR
                           row-scan addressing, the transpose of the
                           top-down CSC column scan)
  stage 3  frontier test   blocked-bitmap membership of c in the gathered
                           frontier words (repro.core.frontier
                           .test_bit_blocks addressing, in-kernel)

There is NO dedup stage: the combine outside the kernel is a scatter-min of
the parent col per row, which is order-independent -- duplicates are free.

Three selectable implementations, bit-identical by construction ("pallas",
"pallas-interpret", "reference" -- the pure-jnp
`repro.core.frontier.reference_bottomup_chunk`); `resolve_bottomup_path`
implements the `BFSConfig(bottomup=...)` rules with the REPRO_BOTTOMUP
environment override, mirroring the expand/fold knobs.  The TPU compiler
refuses this kernel for the same `dynamic_slice` in `map_workload_tile` as
the expand kernel (DESIGN.md sec. 11), so "auto" is "reference" there.

The kernel's cumul is clipped BY VALUE (`repro.core.frontier.clip_by_value`:
entries >= total -> I32_MAX), not by index as the top-down kernel's
`clip_cumul`: the masked cumsum has no live "prefix" -- visited rows pepper
zero-width runs through the whole array -- but every entry that reaches
`total` can never satisfy cumul[l] <= gid for a valid gid < total, so the
I32_MAX tail terminates `map_workload_tile`'s window loop without
disturbing the row mapping on live lanes.

This module needs jax.experimental.pallas; path SELECTION lives in
`repro.kernels.select` so reference-path engines import clean without it.
Import this module only at top level (never lazily inside a traced
function).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.frontier import (I32_MAX, clip_by_value, map_workload_tile,
                                 pick_tile)
from repro.kernels.select import (BOTTOMUP_ENV, BOTTOMUP_PATHS,  # noqa: F401
                                  resolve_bottomup_path)


def _test_words(words, c, *, block: int):
    """In-kernel blocked-bitmap test (mirrors frontier.test_bit_blocks)."""
    W = (block + 31) // 32
    blk, off = c // block, c % block
    w = jnp.take(words, blk * W + (off >> 5), axis=0)
    return ((w >> (off & 31).astype(jnp.uint32)) & jnp.uint32(1)) != 0


# ----------------------------------------------------------------------------
# The fused kernels (stage 1 + 2 + 3 in one pallas_call)
# ----------------------------------------------------------------------------

def _bottomup_kernel(gids_ref, cumul_ref, total_ref, row_off_ref,
                     col_idx_ref, words_ref, r_ref, c_ref, hit_ref, *,
                     window: int, n_cumul: int, nrl: int, nnz_cap: int,
                     block: int):
    gid = gids_ref[...]
    cumul = cumul_ref[...]          # value-clipped: entries >= total = I32_MAX
    # stage 1: thread->edge workload mapping over the masked cumsum
    r = map_workload_tile(gid, cumul, window=window, n_cumul=n_cumul)
    r = jnp.clip(r, 0, nrl - 1)
    # stage 2: in-neighbor gather via CSR addressing (live lanes read the
    # same cumul[r] as the unclipped scan: cumul[r] <= gid < total there)
    addr = jnp.take(row_off_ref[...], r, axis=0) + gid \
        - jnp.take(cumul, r, axis=0)
    addr = jnp.clip(addr, 0, nnz_cap - 1)
    valid = gid < total_ref[0]
    c = jnp.where(valid, jnp.take(col_idx_ref[...], addr, axis=0), 0)
    # stage 3: frontier-bitmap membership (blocked layout)
    hit = valid & _test_words(words_ref[...], c, block=block)
    r_ref[...] = r
    c_ref[...] = c
    hit_ref[...] = hit


@functools.partial(jax.jit,
                   static_argnames=("block", "tile", "window", "interpret"))
def bottomup_chunk(gids, cumul, total, row_off, col_idx, words, *,
                   block: int, tile: int = 512, window: int = 256,
                   interpret: bool):
    """The fused parent search over one chunk of consecutive edge ids.

    cumul: (nrl + 1,) exclusive cumsum of MASKED degrees (visited rows 0);
    total: () live edge count (= cumul[-1]); words: (R * W,) row-gathered
    frontier bitmap in blocked layout (block = S bits per device).

    Returns (r, c, hit) exactly as
    `repro.core.frontier.reference_bottomup_chunk` -- the caller scatter-mins
    c into a per-row best-parent array.
    """
    e = gids.shape[0]
    tile = pick_tile(e, tile)
    nrl = row_off.shape[0] - 1
    nnz_cap = col_idx.shape[0]
    cc = clip_by_value(cumul, total)
    n_cumul = cc.shape[0]
    if n_cumul < window:   # tiny partition: pad so the window load is legal
        cc = jnp.concatenate(
            [cc, jnp.full((window - n_cumul,), I32_MAX, jnp.int32)])
        n_cumul = window
    nw = words.shape[0]
    return pl.pallas_call(
        functools.partial(_bottomup_kernel, window=window, n_cumul=n_cumul,
                          nrl=nrl, nnz_cap=nnz_cap, block=block),
        grid=(e // tile,),
        in_specs=[
            pl.BlockSpec((tile,), lambda t: (t,)),        # gid tile
            pl.BlockSpec((n_cumul,), lambda t: (0,)),     # masked cumsum
            pl.BlockSpec((1,), lambda t: (0,)),           # live-edge total
            pl.BlockSpec((nrl + 1,), lambda t: (0,)),     # CSR row offsets
            pl.BlockSpec((nnz_cap,), lambda t: (0,)),     # CSR col indices
            pl.BlockSpec((nw,), lambda t: (0,)),          # frontier bitmap
        ],
        out_specs=[pl.BlockSpec((tile,), lambda t: (t,))] * 3,
        out_shape=[jax.ShapeDtypeStruct((e,), jnp.int32),
                   jax.ShapeDtypeStruct((e,), jnp.int32),
                   jax.ShapeDtypeStruct((e,), bool)],
        interpret=interpret,
    )(gids, cc, total[None], row_off, col_idx, words)


def _value_bottomup_kernel(gids_ref, cumul_ref, total_ref, row_off_ref,
                           col_idx_ref, words_ref, pay_ref, r_ref, pv_ref,
                           addr_ref, hit_ref, *, window: int, n_cumul: int,
                           nrl: int, nnz_cap: int, block: int):
    gid = gids_ref[...]
    cumul = cumul_ref[...]
    r = map_workload_tile(gid, cumul, window=window, n_cumul=n_cumul)
    r = jnp.clip(r, 0, nrl - 1)
    addr = jnp.take(row_off_ref[...], r, axis=0) + gid \
        - jnp.take(cumul, r, axis=0)
    addr = jnp.clip(addr, 0, nnz_cap - 1)
    valid = gid < total_ref[0]
    c = jnp.where(valid, jnp.take(col_idx_ref[...], addr, axis=0), 0)
    hit = valid & _test_words(words_ref[...], c, block=block)
    r_ref[...] = r
    pv_ref[...] = jnp.take(pay_ref[...], c, axis=0)   # the pulled value
    addr_ref[...] = addr                              # for edge_vals outside
    hit_ref[...] = hit


@functools.partial(jax.jit,
                   static_argnames=("block", "tile", "window", "interpret"))
def bottomup_chunk_values(gids, cumul, total, row_off, col_idx, words,
                          dense_pay, *, block: int, tile: int = 512,
                          window: int = 256, interpret: bool):
    """The fused VALUE-PULLING parent search over one chunk (CC / SSSP /
    multi-BFS in bottom-up levels).

    dense_pay: (n_cols_local,) the frontier payload as a DENSE per-col
    channel (value programs pull the neighbour's label/distance).  Returns
    (r, pay, addr, hit) exactly as
    `repro.core.frontier.reference_bottomup_values_chunk`; the caller
    applies its relax monoid and scatter-min combine.
    """
    e = gids.shape[0]
    tile = pick_tile(e, tile)
    nrl = row_off.shape[0] - 1
    nnz_cap = col_idx.shape[0]
    ncl = dense_pay.shape[0]
    cc = clip_by_value(cumul, total)
    n_cumul = cc.shape[0]
    if n_cumul < window:
        cc = jnp.concatenate(
            [cc, jnp.full((window - n_cumul,), I32_MAX, jnp.int32)])
        n_cumul = window
    nw = words.shape[0]
    return pl.pallas_call(
        functools.partial(_value_bottomup_kernel, window=window,
                          n_cumul=n_cumul, nrl=nrl, nnz_cap=nnz_cap,
                          block=block),
        grid=(e // tile,),
        in_specs=[
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec((n_cumul,), lambda t: (0,)),
            pl.BlockSpec((1,), lambda t: (0,)),
            pl.BlockSpec((nrl + 1,), lambda t: (0,)),
            pl.BlockSpec((nnz_cap,), lambda t: (0,)),
            pl.BlockSpec((nw,), lambda t: (0,)),
            pl.BlockSpec((ncl,), lambda t: (0,)),         # dense payload
        ],
        out_specs=[pl.BlockSpec((tile,), lambda t: (t,))] * 4,
        out_shape=[jax.ShapeDtypeStruct((e,), jnp.int32),
                   jax.ShapeDtypeStruct((e,), jnp.int32),
                   jax.ShapeDtypeStruct((e,), jnp.int32),
                   jax.ShapeDtypeStruct((e,), bool)],
        interpret=interpret,
    )(gids, cc, total[None], row_off, col_idx, words, dense_pay)


# ----------------------------------------------------------------------------
# Engine hooks: the chunk closures the bottom-up steps thread into their scans
# ----------------------------------------------------------------------------

def make_bottomup_fn(*, path: str, tile: int = 512,
                     window: int = 256):
    """The kernel-backed chunk parent search for the bottom-up BFS step:

        (gids, cumul, total, row_off, col_idx, words, block=S) -> (r, c, hit)
    """
    interpret = path != "pallas"

    def bottomup_fn(gids, cumul, total, row_off, col_idx, words, *,
                    block: int):
        return bottomup_chunk(gids, cumul, total, row_off, col_idx, words,
                              block=block, tile=tile, window=window,
                              interpret=interpret)

    return bottomup_fn


def make_value_bottomup_fn(*, path: str, tile: int = 512,
                           window: int = 256):
    """The kernel-backed value-pulling chunk parent search (value programs):

        (gids, cumul, total, row_off, col_idx, words, dense_pay, block=S)
            -> (r, pay, addr, hit)
    """
    interpret = path != "pallas"

    def value_bottomup_fn(gids, cumul, total, row_off, col_idx, words,
                          dense_pay, *, block: int):
        return bottomup_chunk_values(gids, cumul, total, row_off, col_idx,
                                     words, dense_pay, block=block,
                                     tile=tile, window=window,
                                     interpret=interpret)

    return value_bottomup_fn
