"""Fused Pallas fold kernels (paper sec. 3.3; DESIGN.md sec. 10).

The fold half of Buluc & Madduri's expand/fold decomposition spends its
per-level device time in three places: packing discovery buckets into the
codec wire format (bitmap bit-packing, delta gap-encoding), unpacking the
received message, and COMPACTION -- front-packing valid entries of a padded
row, which the reference path does with an `argsort` per level in
`pack_blocks`, `owned_to_front`, `expand_exchange_values` and
`compact_blocks`.  This module implements those stages as Pallas kernels:

  compact_rows    the prefix-sum compaction primitive: an exclusive count
                  prefix-sum over the validity mask (host jnp, O(S) -- the
                  same role `cumul` plays for the expand scan) turns
                  front-packing into a per-lane rank-select, which the
                  kernel answers with an unrolled vectorised binary search
                  over the monotone prefix array (log2 S dense gathers per
                  row instead of an O(S log S) sort);
  pack_bits /     the bitmap codec's 1-bit-per-vertex pack/unpack as dense
  unpack_bits     VPU shift/weight ops over 32-lane groups;
  delta_gaps /    the delta codec's first-order gap encode (on sorted rows;
  delta_positions the sort itself stays XLA) and the cumsum decode.

Every kernel is bit-identical to the reference jnp path by construction:
compaction output (ascending, front-packed, fill-padded) is fully determined
by the mask, so rank-select and stable argsort produce the same arrays; the
bit/gap codecs compute the same formulas lane for lane.

`make_fold_ops(path=...)` bundles the kernels into the ops object the
engines thread through `repro.dist.exchange` and `repro.algos.program`
(`BFSConfig(fold=...)`, resolved by `repro.kernels.select.resolve_fold_path`
with the REPRO_FOLD override -- the exact mirror of the expand-path
plumbing, DESIGN.md sec. 9.2).

The TPU compiler refuses all five kernels (DESIGN.md sec. 10): each one
blocks its (N, S) operands as (1, S) rows, and a block's second-to-last
dimension must be divisible by 8 or equal the array's.  So "auto" resolves
to "reference" on TPU.

This module needs jax.experimental.pallas; path SELECTION does not and
lives in `repro.kernels.select` so reference-path engines import clean
without it.  Import this module only at top level (never lazily inside a
traced function): it caches jnp constants at import time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.select import (FOLD_ENV, FOLD_PATHS,  # noqa: F401
                                  resolve_fold_path)

I32_MAX = jnp.int32(jnp.iinfo(jnp.int32).max)


def _ceil_log2(n: int) -> int:
    """Iterations for a binary search over n+1 candidate indices."""
    return max(1, (n).bit_length())


# ----------------------------------------------------------------------------
# compact_rows: the prefix-sum compaction primitive
# ----------------------------------------------------------------------------

def _rank_select(ec, S: int, iters: int):
    """idx[s] = max { l : ec[l] <= s } for all output slots s in [0, S).

    ec is the (S+1,) exclusive count prefix-sum of the row's validity mask
    (monotone, ec[0] = 0): for s < ec[S], idx[s] is the source index of the
    s-th valid element -- rank-select as an unrolled per-lane binary search
    (log2(S+1) dense VPU gathers; `jnp.take` of int32 lanes is the same
    VMEM gather `filter_tile` uses)."""
    s = jax.lax.iota(jnp.int32, S)
    lo = jnp.zeros((S,), jnp.int32)       # invariant: ec[lo] <= s (ec[0]=0)
    hi = jnp.full((S,), S, jnp.int32)
    for _ in range(iters):
        mid = (lo + hi + jnp.int32(1)) >> 1
        go = jnp.take(ec, mid, axis=0) <= s
        lo = jnp.where(go, mid, lo)
        hi = jnp.where(go, hi, mid - 1)
    return lo


def _compact_kernel(ec_ref, *refs, n_arrays: int, fills: tuple, S: int,
                    iters: int):
    ec = ec_ref[0]
    idx = _rank_select(ec, S, iters)
    valid = jax.lax.iota(jnp.int32, S) < ec[S]
    src = jnp.clip(idx, 0, S - 1)
    for a in range(n_arrays):
        refs[n_arrays + a][0, :] = jnp.where(
            valid, jnp.take(refs[a][0], src, axis=0),
            jnp.int32(fills[a]))


@functools.partial(jax.jit, static_argnames=("fills", "interpret"))
def _compact_rows(mask, arrays, fills, *, interpret: bool):
    N, S = mask.shape
    inc = jnp.cumsum(mask.astype(jnp.int32), axis=1)
    ec = jnp.concatenate([jnp.zeros((N, 1), jnp.int32), inc], axis=1)
    n_arrays = len(arrays)
    packed = pl.pallas_call(
        functools.partial(_compact_kernel, n_arrays=n_arrays, fills=fills,
                          S=S, iters=_ceil_log2(S)),
        grid=(N,),
        in_specs=[pl.BlockSpec((1, S + 1), lambda r: (r, 0))]
        + [pl.BlockSpec((1, S), lambda r: (r, 0))] * n_arrays,
        out_specs=[pl.BlockSpec((1, S), lambda r: (r, 0))] * n_arrays,
        out_shape=[jax.ShapeDtypeStruct((N, S), jnp.int32)] * n_arrays,
        interpret=interpret,
    )(ec, *arrays)
    return tuple(packed), inc[:, -1]


def compact_rows(mask, arrays, fills, *, interpret: bool):
    """Front-pack each row's valid entries, preserving order (the argsort
    replacement shared by `pack_blocks`, `owned_to_front`,
    `expand_exchange_values`, `compact_blocks` and the bitmap decode).

    mask: (N, S) bool validity; arrays: aligned (N, S) int32 channels;
    fills: per-array pad value.  Returns (tuple of packed (N, S) arrays,
    (N,) int32 counts) -- bit-identical to compacting with a stable argsort
    of the mask.
    """
    arrays = tuple(jnp.asarray(a, jnp.int32) for a in arrays)
    return _compact_rows(jnp.asarray(mask, bool), arrays,
                         tuple(int(f) for f in fills), interpret=interpret)


# ----------------------------------------------------------------------------
# Bitmap pack/unpack
# ----------------------------------------------------------------------------

def _bit_weights():
    """(32,) uint32 [1, 2, 4, ...] built in-kernel (Pallas kernels cannot
    capture module-level array constants)."""
    return jnp.uint32(1) << jax.lax.iota(jnp.uint32, 32)


def _pack_kernel(mask_ref, words_ref, *, W: int):
    m = mask_ref[0].reshape(W, 32).astype(jnp.uint32)
    words_ref[0, :] = jnp.sum(m * _bit_weights()[None, :], axis=-1,
                              dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_bits(mask, *, interpret: bool):
    """(N, S) bool -> (N, ceil(S/32)) uint32 little-endian bit packing
    (the kernel twin of `repro.core.frontier.pack_bitmap`)."""
    N, S = mask.shape
    W = (S + 31) // 32
    pad = W * 32 - S
    if pad:
        mask = jnp.concatenate([mask, jnp.zeros((N, pad), bool)], axis=1)
    return pl.pallas_call(
        functools.partial(_pack_kernel, W=W),
        grid=(N,),
        in_specs=[pl.BlockSpec((1, W * 32), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((1, W), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((N, W), jnp.uint32),
        interpret=interpret,
    )(mask)


def _unpack_kernel(words_ref, bits_ref, *, W: int):
    w = words_ref[0]
    bits = (w[:, None] >> jax.lax.iota(jnp.uint32, 32)[None, :]) \
        & jnp.uint32(1)
    bits_ref[0, :] = bits.reshape(W * 32).astype(jnp.bool_)


@functools.partial(jax.jit, static_argnames=("S", "interpret"))
def unpack_bits(words, S: int, *, interpret: bool):
    """(N, W) uint32 -> (N, S) bool (the kernel twin of `unpack_bitmap`)."""
    N, W = words.shape
    bits = pl.pallas_call(
        functools.partial(_unpack_kernel, W=W),
        grid=(N,),
        in_specs=[pl.BlockSpec((1, W), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((1, W * 32), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((N, W * 32), jnp.bool_),
        interpret=interpret,
    )(words)
    return bits[:, :S]


# ----------------------------------------------------------------------------
# Delta gap encode / cumsum decode
# ----------------------------------------------------------------------------

def _gaps_kernel(ts_ref, valid_ref, gaps_ref, *, S: int):
    ts = ts_ref[0]
    pos = jax.lax.iota(jnp.int32, S)
    prev = jnp.where(pos > 0, jnp.take(ts, jnp.maximum(pos - 1, 0), axis=0),
                     0)
    gaps_ref[0, :] = jnp.where(valid_ref[0], ts - prev, 0) \
        .astype(jnp.uint16)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_gaps(ts, valid, *, interpret: bool):
    """Sorted per-row offsets -> uint16 first-order gaps (slot 0 absolute),
    the encode half of the delta codec on PRE-SORTED rows (the sort stays
    XLA; canonical value-fold buckets arrive already sorted)."""
    N, S = ts.shape
    return pl.pallas_call(
        functools.partial(_gaps_kernel, S=S),
        grid=(N,),
        in_specs=[pl.BlockSpec((1, S), lambda r: (r, 0))] * 2,
        out_specs=pl.BlockSpec((1, S), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((N, S), jnp.uint16),
        interpret=interpret,
    )(ts, valid)


def _positions_kernel(gaps_ref, pos_ref):
    pos_ref[0, :] = jnp.cumsum(gaps_ref[0].astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_positions(gaps, *, interpret: bool):
    """(N, S) uint16 gaps -> (N, S) int32 absolute offsets (cumsum), the
    decode half of the delta codec."""
    N, S = gaps.shape
    return pl.pallas_call(
        _positions_kernel,
        grid=(N,),
        in_specs=[pl.BlockSpec((1, S), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((1, S), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((N, S), jnp.int32),
        interpret=interpret,
    )(gaps)


# ----------------------------------------------------------------------------
# The ops bundle the engines thread through exchange/program
# ----------------------------------------------------------------------------

class PallasFoldOps:
    """The fold-kernel surface (`engine.fold_ops`): one object bundling the
    compaction/pack/unpack/delta kernels with the interpret flag bound, so
    call sites stay ignorant of the path.  `None` in its place means the
    reference jnp formulas (exactly the pre-sec.-10 code)."""

    def __init__(self, path: str):
        if path not in ("pallas", "pallas-interpret"):
            raise ValueError(f"fold ops need a pallas path, got {path!r}")
        self.name = path
        self.interpret = path != "pallas"

    def __repr__(self):
        return f"PallasFoldOps({self.name!r})"

    def compact_rows(self, mask, arrays, fills):
        return compact_rows(mask, arrays, fills, interpret=self.interpret)

    def pack_bits(self, mask):
        return pack_bits(mask, interpret=self.interpret)

    def unpack_bits(self, words, S: int):
        return unpack_bits(words, S, interpret=self.interpret)

    def delta_gaps(self, ts, valid):
        return delta_gaps(ts, valid, interpret=self.interpret)

    def delta_positions(self, gaps):
        return delta_positions(gaps, interpret=self.interpret)


def make_fold_ops(*, path: str) -> PallasFoldOps:
    """The kernel bundle for a resolved non-reference fold path."""
    return PallasFoldOps(path)
