"""Expand/fold exchanges with pluggable fold wire formats (DESIGN.md
sec. 4 + 10).

The fold exchange routes every newly-discovered vertex to its owner column.
WHICH vertices travel is fixed by the algorithm; HOW they are encoded on the
wire is an independent, swappable concern (Buluc & Madduri 2011 separate the
exchange pattern from its payload; Romera & Froning 2017 compress it).

Every fold is ONE `col_all_to_all`: per-bucket counts ride a HEADER WORD at
the front of the payload message instead of a second collective, and
value-carrying folds append the value channel to the same message instead of
a third (the paper's "reduce the number of communications among the GPUs"
applied to our collectives).  Fused single-message costs, per fold partner
(S = owned block size, W = ceil(S/32)):

  codec   set-fold message            bytes        value-fold message
  list    [cnt | ids]   int32         4*S + 4      [cnt | ids | vals]
  bitmap  [bit words]   uint32        4*W          [words | vals]
  delta   [cnt | gaps]  uint16        2*S + 4      [cnt | gaps | vals]

(bitmap needs no header: counts are derivable from the received words.)
The value channel is FRONT-PACKED into the message in the same canonical
ascending order as the ids, so only the first `cnt` value words per bucket
carry information: `wire_bytes_values` prices the static message capacity
(+4*S per bucket), `wire_bytes_values_sent` the count-proportional bytes a
count-aware transport (all_to_allv) ships -- the honest figure BENCH_bfs
tracks, cutting the bitmap value-fold from ~4*S + S/8 per bucket toward
4*count + S/8.

Delivery order per sender differs by codec (`list` keeps discovery order,
`bitmap`/`delta` deliver ascending) -- outputs are nonetheless bit-identical
across codecs because (a) a vertex appears at most once per sender, and the
update winner is the MINIMUM sender regardless of position within a message,
and (b) the next frontier is the owned rows with level == lvl, ascending
(`frontier.compact_mask`), fixing the next level's scan order.  Do not rely
on per-sender ordering in a decoder.  `delta` requires S <= 65536 so every
gap fits in a uint16; larger blocks would need an escape word, which this
repro does not implement (`get_fold_codec` names the codecs that DO work).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import frontier as F
from repro.core.types import Grid2D


def expand_exchange(front, front_cnt, *, topo):
    """Gather the frontiers of the processor-column (paper line 13).

    Returns (all_front (n_cols_local,), front_total) -- valid entries first,
    grid-row order preserved.
    """
    R, S = topo.grid.R, topo.grid.S
    af = topo.row_gather(front).reshape(R, S)
    ac = topo.row_gather(front_cnt).reshape(R)
    return F.compact_blocks(af, ac)


def expand_exchange_values(front, front_cnt, payload, *, topo, fill=0):
    """`expand_exchange` with an aligned per-vertex payload channel
    (frontier programs: the vertex's label / distance / source id).

    Returns (all_front (n_cols_local,), all_payload aligned, front_total) --
    the same compaction order as `expand_exchange` (valid entries first,
    grid-row order preserved), applied to ids and payload in lockstep.
    """
    R, S = topo.grid.R, topo.grid.S
    af = topo.row_gather(front).reshape(R, S)
    ac = topo.row_gather(front_cnt).reshape(R)
    ap = topo.row_gather(payload).reshape(R, S)
    mask = jnp.arange(S, dtype=jnp.int32)[None, :] < ac[:, None]
    total = jnp.sum(ac, dtype=jnp.int32)
    flat_m = mask.reshape(-1)
    order = jnp.argsort(~flat_m, stable=True)
    valid = flat_m[order]
    fr = jnp.where(valid, af.reshape(-1)[order], -1)
    pl = jnp.where(valid, ap.reshape(-1)[order], fill)
    return fr, pl, total


def resolve_preds(pred, *, topo, j):
    """Final deferred-predecessor exchange (paper sec. 3.5 / contribution [2]).

    One all_to_all of the pred array (viewed as C blocks of S) within each
    grid row delivers, for every owned vertex, the parent recorded by the
    processor-column that folded it."""
    C, S = topo.grid.C, topo.grid.S
    pb = pred.reshape(C, S)
    recv = topo.col_all_to_all(pb).reshape(C, S)
    own = jnp.take(pb, j, axis=0)                     # (S,) my owned block
    deferred = own < -1
    sender = jnp.clip(-own - 2, 0, C - 1)
    from_sender = jnp.take_along_axis(recv, sender[None, :], axis=0)[0]
    return jnp.where(deferred, from_sender, own)


# ----------------------------------------------------------------------------
# int32 <-> uint16 value-channel splitting (the delta value-fold rides a
# uint16 message; shifts/ors reconstruct the exact bit pattern)
# ----------------------------------------------------------------------------

def _i32_to_u16(v):
    """(C, S) int32 -> (C, 2*S) uint16 [lo, hi] pairs."""
    C, S = v.shape
    lo = (v & 0xFFFF).astype(jnp.uint16)
    hi = ((v >> 16) & 0xFFFF).astype(jnp.uint16)
    return jnp.stack([lo, hi], axis=-1).reshape(C, 2 * S)


def _u16_to_i32(u):
    """(C, 2*S) uint16 [lo, hi] pairs -> (C, S) int32, bit-exact."""
    C = u.shape[0]
    p = u.reshape(C, -1, 2).astype(jnp.int32)
    return (p[..., 1] << 16) | p[..., 0]


# ----------------------------------------------------------------------------
# Fold codecs
# ----------------------------------------------------------------------------

class FoldCodec:
    """Strategy for the fold exchange's wire format.

    fold() maps per-owner-column discovery buckets to received owned rows:
      dst:     (C, S) int32 local-row ids (bucket m holds rows of block m,
               i.e. ids m*S + t), padded -1, packed at the front;
      dst_cnt: (C,) int32;
    returns (int_verts (C, S) int32 -- MY owned rows j*S + t, one row per
    sender, padded -1 -- and int_cnt (C,)).  Order WITHIN a sender's row is
    codec-specific (see module docstring); consumers must not rely on it.

    Every fold (set or value) is ONE `col_all_to_all` of one fused message
    (counts in a header word, values appended) -- see the byte table in the
    module docstring.
    """
    name = "?"

    def __init__(self, grid: Grid2D = None):
        """A codec that cannot run at `grid`'s block size raises a
        ValueError here (`get_fold_codec` names the ones that can)."""

    def wire_bytes(self, grid: Grid2D) -> int:
        """Bytes this device SENDS on one fused set-fold message."""
        raise NotImplementedError

    def fold(self, dst, dst_cnt, *, topo, j):
        raise NotImplementedError

    # -- value-carrying fold (frontier programs, DESIGN.md sec. 8) -----------
    #
    # Same exchange pattern, but every travelling vertex carries an int32
    # value (its label / distance / source id).  The id-set goes on the wire
    # in THIS codec's format; the values are FRONT-PACKED into the tail of
    # the same message in the CANONICAL (ascending, front-packed) bucket
    # order, which callers must provide (repro.algos.program.pack_blocks
    # does).  Because the input is canonical and values are min-combined by
    # consumers, every codec delivers bit-identical results by construction.

    def wire_bytes_values(self, grid: Grid2D) -> int:
        """STATIC capacity of one fused value-fold message (ids + header +
        the S-slot value channel)."""
        return self.wire_bytes(grid) + grid.C * 4 * grid.S

    def wire_bytes_values_sent(self, grid: Grid2D, total_count) -> int:
        """Count-proportional bytes of one value-fold: the value channel is
        front-packed, so a count-aware transport (all_to_allv) ships only
        `total_count` value words beyond the set-fold message.  This is the
        figure BENCH_bfs tracks against the dense-channel baseline."""
        return self.wire_bytes(grid) + 4 * total_count

    def fold_values(self, ids, cnt, vals, *, topo, j):
        """ids: (C, S) local-row ids per owner bucket (bucket m holds ids
        m*S + t), ascending, front-packed, padded -1; vals: (C, S) int32
        aligned with ids.  Returns (recv_ids (C, S) owned rows j*S + t,
        ascending front-packed per sender, recv_cnt (C,), recv_vals (C, S)
        aligned)."""
        raise NotImplementedError


class ListFold(FoldCodec):
    """32-bit local indices, the paper's own wire format (sec. 3.3), with
    the count in the leading header word of each bucket."""
    name = "list"

    def wire_bytes(self, grid: Grid2D) -> int:
        return grid.C * (4 * grid.S + 4)

    def fold(self, dst, dst_cnt, *, topo, j):
        C, S = topo.grid.C, topo.grid.S
        msg = jnp.concatenate([dst_cnt[:, None], dst], axis=1)
        recv = topo.col_all_to_all(msg).reshape(C, 1 + S)
        return recv[:, 1:], recv[:, 0]

    def fold_values(self, ids, cnt, vals, *, topo, j):
        C, S = topo.grid.C, topo.grid.S
        msg = jnp.concatenate([cnt[:, None], ids, vals], axis=1)
        recv = topo.col_all_to_all(msg).reshape(C, 1 + 2 * S)
        return recv[:, 1:1 + S], recv[:, 0], recv[:, 1 + S:]


class BitmapFold(FoldCodec):
    """1-bit-per-vertex block bitmap: 32x below `list` at identical
    semantics (beyond-paper; see EXPERIMENTS.md "fold compression").  No
    header word: counts are derivable from the received bit words."""
    name = "bitmap"

    def wire_bytes(self, grid: Grid2D) -> int:
        return grid.C * 4 * ((grid.S + 31) // 32)

    @staticmethod
    def encode(dst, dst_cnt, S: int):
        """(C, S) id buckets -> (C, ceil(S/32)) uint32 bit words."""
        C = dst.shape[0]
        valid = dst >= 0
        rowsel = jnp.where(valid, jnp.arange(C, dtype=jnp.int32)[:, None], C)
        onehot = jnp.zeros((C, S), bool).at[
            rowsel.reshape(-1), jnp.where(valid, dst % S, 0).reshape(-1)
        ].set(True, mode="drop")
        return F.pack_bitmap(onehot)

    @staticmethod
    def decode(words, j, S: int):
        """(C, W) received words -> ascending owned rows j*S + t per sender."""
        recv_mask = F.unpack_bitmap(words, S)          # [m, t]: from sender m
        C = recv_mask.shape[0]
        rows = jnp.broadcast_to(
            j * S + jnp.arange(S, dtype=jnp.int32)[None, :], (C, S))
        int_verts = jax.vmap(lambda r, m: F.append_padded(
            jnp.full((S,), -1, jnp.int32), jnp.int32(0), r, m)[0])(
                rows, recv_mask)
        return int_verts, recv_mask.sum(axis=1, dtype=jnp.int32)

    def fold(self, dst, dst_cnt, *, topo, j):
        C, S = topo.grid.C, topo.grid.S
        words = topo.col_all_to_all(self.encode(dst, dst_cnt, S))
        return self.decode(words.reshape(C, -1), j, S)

    def fold_values(self, ids, cnt, vals, *, topo, j):
        # decode delivers ascending front-packed rows -- exactly the
        # canonical order the ids (and hence the values channel) arrived in
        C, S = topo.grid.C, topo.grid.S
        words = self.encode(ids, cnt, S)
        W = words.shape[1]
        msg = jnp.concatenate(
            [words, jax.lax.bitcast_convert_type(vals, jnp.uint32)], axis=1)
        recv = topo.col_all_to_all(msg).reshape(C, W + S)
        ri, rc = self.decode(recv[:, :W], j, S)
        rv = jax.lax.bitcast_convert_type(recv[:, W:], jnp.int32)
        return ri, rc, rv


class DeltaFold(FoldCodec):
    """Sort + delta + 16-bit narrowing (Romera & Froning 2017, sec. III):
    within one fold message all ids share the destination block, so after
    sorting, consecutive gaps are < S and fit a uint16 -- half the bytes of
    `list` independent of frontier density (unlike `bitmap`, which wins only
    once more than 1/16 of a block is discovered in one level).  The count
    rides a two-uint16 header (one 32-bit word) ahead of the gaps."""
    name = "delta"

    def __init__(self, grid: Grid2D = None):
        if grid is not None and grid.S > (1 << 16):
            raise ValueError(
                f"delta fold needs S <= 65536 (16-bit gaps), got S={grid.S}")

    def wire_bytes(self, grid: Grid2D) -> int:
        return grid.C * (2 * grid.S + 4)

    @staticmethod
    def encode(dst, dst_cnt, S: int):
        """(C, S) id buckets -> (C, S) uint16 ascending first-order gaps
        (slot 0 is the absolute first offset)."""
        C = dst.shape[0]
        valid = jnp.arange(S, dtype=jnp.int32)[None, :] < dst_cnt[:, None]
        t = jnp.where(valid, dst % S, F.I32_MAX)
        ts = jnp.sort(t, axis=1)                  # valid entries sort first
        prev = jnp.concatenate(
            [jnp.zeros((C, 1), jnp.int32), ts[:, :-1]], axis=1)
        return jnp.where(valid, ts - prev, 0).astype(jnp.uint16)

    @staticmethod
    def decode(gaps, cnt, j, S: int):
        """(C, S) uint16 gaps + (C,) counts -> owned rows j*S + t."""
        vals = jnp.cumsum(gaps.astype(jnp.int32), axis=1)
        valid = jnp.arange(S, dtype=jnp.int32)[None, :] < cnt[:, None]
        return jnp.where(valid, j * S + vals, -1), cnt

    @staticmethod
    def _header(cnt):
        """(C,) int32 counts -> (C, 2) uint16 [lo, hi] header words (count
        may be S = 65536, one past uint16, hence the pair)."""
        return _i32_to_u16(cnt[:, None])

    @staticmethod
    def _read_header(hdr):
        return _u16_to_i32(hdr)[:, 0]

    def fold(self, dst, dst_cnt, *, topo, j):
        C, S = topo.grid.C, topo.grid.S
        msg = jnp.concatenate(
            [self._header(dst_cnt), self.encode(dst, dst_cnt, S)], axis=1)
        recv = topo.col_all_to_all(msg).reshape(C, S + 2)
        cnt = self._read_header(recv[:, :2])
        return self.decode(recv[:, 2:], cnt, j, S)

    def fold_values(self, ids, cnt, vals, *, topo, j):
        # encode sorts per bucket; canonical input is already sorted, so the
        # delivered order equals the sent order and the values align
        C, S = topo.grid.C, topo.grid.S
        msg = jnp.concatenate(
            [self._header(cnt), self.encode(ids, cnt, S),
             _i32_to_u16(vals)], axis=1)
        recv = topo.col_all_to_all(msg).reshape(C, 2 + 3 * S)
        rc = self._read_header(recv[:, :2])
        ri, _ = self.decode(recv[:, 2:2 + S], rc, j, S)
        rv = _u16_to_i32(recv[:, 2 + S:])
        return ri, rc, rv


FOLD_CODECS = {"list": ListFold, "bitmap": BitmapFold, "delta": DeltaFold}


def get_fold_codec(spec, grid: Grid2D) -> FoldCodec:
    """Resolve "list" | "bitmap" | "delta" | FoldCodec instance.

    A codec that cannot operate at this grid's block size (delta needs
    S <= 65536) raises a ValueError naming the codecs that DO work --
    surfaced unchanged through `GraphSession`/`BFSConfig`.
    """
    if isinstance(spec, FoldCodec):
        return spec
    try:
        cls = FOLD_CODECS[spec]
    except KeyError:
        raise ValueError(
            f"unknown fold codec {spec!r}; choose from {sorted(FOLD_CODECS)}")
    try:
        return cls(grid)
    except ValueError as e:
        working = []
        for name, other in FOLD_CODECS.items():
            if name == spec:
                continue
            try:
                other(grid)
            except ValueError:
                continue
            working.append(name)
        raise ValueError(
            f"fold_codec={spec!r} cannot run on this grid ({grid.R}x{grid.C},"
            f" block size S={grid.S}): {e}; codecs that do work at this "
            f"block size: {sorted(working)}") from e
