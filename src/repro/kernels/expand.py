"""Fused local-expand pipeline (paper sec. 3.4 end to end; DESIGN.md sec. 9).

The paper's per-node hot loop -- binary-search workload mapping, warp-level
neighbor gather and the atomicOr visited bitmap -- as ONE fused op over a
chunk of consecutive edge ids:

  stage 1  workload map    k[t] = max { l : cumul[l] <= gid[t] }
                           (repro.core.frontier.map_workload_tile)
  stage 2  neighbor gather u = front[k]; v = row_idx[col_off[u] + gid -
                           cumul[k]] (the CSC column-scan addressing that the
                           old standalone gather_segments kernel DMA'd)
  stage 3  visited filter  bitmap test + per-tile first-occurrence dedup
                           (repro.kernels._visited_filter.filter_tile); the
                           SET half stays an XLA scatter outside the kernel
                           so it fuses with the level/pred updates
  stage 4  compaction      cross-tile winner selection + canonical packing
                           (`local_expand` driver; inside the engine this is
                           `repro.core.frontier.winner_dedup`/bucket append)

Three selectable implementations, bit-identical by construction:

  "pallas"            the fused Pallas kernel, compiled;
  "pallas-interpret"  the same kernel body in Pallas interpret mode (CPU
                      only) -- this is what CI drives via
                      REPRO_EXPAND=pallas-interpret;
  "reference"         the pure-jnp formulas (exactly the inline path of
                      `repro.core.frontier.expand_frontier` / `scan_relax`).

`resolve_expand_path` implements the `BFSConfig(expand=...)` selection rules
(`repro.kernels.select`): "auto" picks "reference" on CPU and TPU and honors
the REPRO_EXPAND environment variable so CI can force the interpret-mode
kernel path without touching configs.

The TPU compiler refuses this kernel (DESIGN.md sec. 9): the scalar search
in `map_workload_tile` uses `dynamic_slice`, which has no Pallas TPU
lowering, and `row_idx` is held whole in VMEM and gathered with a vector
`jnp.take`.  A chip version would keep row_idx in HBM and DMA windows of
it, with identical semantics.

This module needs jax.experimental.pallas; path SELECTION does not and lives
in `repro.kernels.select` so reference-path engines import clean without it.
Import this module only at top level (never lazily inside a traced
function): the stage modules cache jnp constants at import time, and an
import under an active trace would leak tracers into those globals.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.frontier import (I32_MAX, frontier_workload,
                                 map_workload_tile, pack_bitmap, pick_tile,
                                 reference_expand_chunk, set_bits,
                                 winner_dedup)
from repro.kernels._binsearch_map import clip_cumul
from repro.kernels.select import (EXPAND_ENV, EXPAND_PATHS,  # noqa: F401
                                  resolve_expand_path)
from repro.kernels._visited_filter import filter_tile


# ----------------------------------------------------------------------------
# The fused kernels (stage 1 + 2 + 3 in one pallas_call)
# ----------------------------------------------------------------------------

def _expand_kernel(gids_ref, cumul_ref, total_ref, front_ref, col_off_ref,
                   row_idx_ref, words_ref, v_ref, u_ref, won_ref, *,
                   window: int, n_cumul: int, ncl: int, nnz_cap: int):
    gid = gids_ref[...]
    cumul = cumul_ref[...]          # clipped: entries > front_total = I32_MAX
    # stage 1: thread->edge workload mapping
    k = map_workload_tile(gid, cumul, window=window, n_cumul=n_cumul)
    k = jnp.clip(k, 0, ncl - 1)
    # stage 2: neighbor gather via CSC addressing (valid lanes read the same
    # cumul[k] as the unclipped scan: k <= front_total on the live prefix)
    u = jnp.clip(jnp.take(front_ref[...], k, axis=0), 0, ncl - 1)
    addr = jnp.take(col_off_ref[...], u, axis=0) + gid \
        - jnp.take(cumul, k, axis=0)
    valid = gid < total_ref[0]
    v = jnp.take(row_idx_ref[...], jnp.clip(addr, 0, nnz_cap - 1), axis=0)
    v = jnp.where(valid, v, 0)
    # stage 3: visited-bitmap test + per-tile first-occurrence dedup
    won = filter_tile(v, valid, words_ref[...])
    v_ref[...] = v
    u_ref[...] = u
    won_ref[...] = won


@functools.partial(jax.jit, static_argnames=("tile", "window", "interpret"))
def expand_chunk(gids, cumul, all_front, front_total, col_off, row_idx,
                 visited, words=None, *, tile: int = 512, window: int = 256,
                 interpret: bool):
    """The fused set-expand over one chunk of consecutive edge ids.

    Drop-in for `repro.core.frontier.expand_frontier(expand_fn=...)`:
    returns (v, eligible, u) where `eligible` are the unvisited candidates
    surviving the per-tile first-occurrence dedup -- a subset of the
    reference path's mask that provably elects the SAME cross-chunk winners
    under `winner_dedup` (the global first occurrence of any vertex is also
    the first in its tile).

    words: the packed visited bitmap, when the caller maintains it
    incrementally across chunks (`frontier.set_bits`); None packs from the
    bool mask here -- an O(n_rows) repack per chunk, fine for one-shot
    calls but not for the engines' level loops.
    """
    ncl = all_front.shape[0]
    e = gids.shape[0]
    tile = pick_tile(e, tile)
    nnz_cap = row_idx.shape[0]
    cc = clip_cumul(cumul, front_total)
    total = cumul[front_total][None]
    n_cumul = cc.shape[0]
    if n_cumul < window:   # tiny frontier: pad so the window load is legal
        cc = jnp.concatenate(
            [cc, jnp.full((window - n_cumul,), I32_MAX, jnp.int32)])
        n_cumul = window
    if words is None:
        words = pack_bitmap(visited)
    nw = words.shape[0]
    v, u, won = pl.pallas_call(
        functools.partial(_expand_kernel, window=window, n_cumul=n_cumul,
                          ncl=ncl, nnz_cap=nnz_cap),
        grid=(e // tile,),
        in_specs=[
            pl.BlockSpec((tile,), lambda t: (t,)),        # gid tile
            pl.BlockSpec((n_cumul,), lambda t: (0,)),     # cumul whole
            pl.BlockSpec((1,), lambda t: (0,)),           # live-edge total
            pl.BlockSpec((ncl,), lambda t: (0,)),         # gathered frontier
            pl.BlockSpec((ncl + 1,), lambda t: (0,)),     # CSC col offsets
            pl.BlockSpec((nnz_cap,), lambda t: (0,)),     # CSC row indices
            pl.BlockSpec((nw,), lambda t: (0,)),          # visited bitmap
        ],
        out_specs=[pl.BlockSpec((tile,), lambda t: (t,))] * 3,
        out_shape=[jax.ShapeDtypeStruct((e,), jnp.int32),
                   jax.ShapeDtypeStruct((e,), jnp.int32),
                   jax.ShapeDtypeStruct((e,), bool)],
        interpret=interpret,
    )(gids, cc, total, all_front, col_off, row_idx, words)
    return v, won, u


def _value_expand_kernel(gids_ref, cumul_ref, total_ref, front_ref, pay_ref,
                         col_off_ref, row_idx_ref, v_ref, pv_ref, addr_ref,
                         valid_ref, *, window: int, n_cumul: int, ncl: int,
                         nnz_cap: int):
    gid = gids_ref[...]
    cumul = cumul_ref[...]
    k = map_workload_tile(gid, cumul, window=window, n_cumul=n_cumul)
    k = jnp.clip(k, 0, ncl - 1)
    u = jnp.clip(jnp.take(front_ref[...], k, axis=0), 0, ncl - 1)
    addr = jnp.clip(jnp.take(col_off_ref[...], u, axis=0) + gid
                    - jnp.take(cumul, k, axis=0), 0, nnz_cap - 1)
    valid = gid < total_ref[0]
    v = jnp.where(valid, jnp.take(row_idx_ref[...], addr, axis=0), 0)
    v_ref[...] = v
    pv_ref[...] = jnp.take(pay_ref[...], k, axis=0)   # the carried value
    addr_ref[...] = addr                              # for edge_vals outside
    valid_ref[...] = valid


@functools.partial(jax.jit, static_argnames=("tile", "window", "interpret"))
def expand_chunk_values(gids, cumul, all_front, all_payload, front_total,
                        col_off, row_idx, *, tile: int = 512,
                        window: int = 256, interpret: bool):
    """The fused VALUE-CARRYING expand over one chunk (CC / SSSP / multi-BFS).

    Returns (v, payload, addr, valid): candidate local rows, the frontier
    payload carried along each edge, the clipped CSC edge address (so the
    caller can gather per-edge values like SSSP weights), and the live-lane
    mask.  The caller applies its relax monoid and scatter-min combine --
    keeping the kernel algorithm-agnostic, exactly like the jnp scan in
    `repro.algos.program.scan_relax`.
    """
    ncl = all_front.shape[0]
    e = gids.shape[0]
    tile = pick_tile(e, tile)
    nnz_cap = row_idx.shape[0]
    cc = clip_cumul(cumul, front_total)
    total = cumul[front_total][None]
    n_cumul = cc.shape[0]
    if n_cumul < window:
        cc = jnp.concatenate(
            [cc, jnp.full((window - n_cumul,), I32_MAX, jnp.int32)])
        n_cumul = window
    return pl.pallas_call(
        functools.partial(_value_expand_kernel, window=window,
                          n_cumul=n_cumul, ncl=ncl, nnz_cap=nnz_cap),
        grid=(e // tile,),
        in_specs=[
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec((n_cumul,), lambda t: (0,)),
            pl.BlockSpec((1,), lambda t: (0,)),
            pl.BlockSpec((ncl,), lambda t: (0,)),
            pl.BlockSpec((ncl,), lambda t: (0,)),
            pl.BlockSpec((ncl + 1,), lambda t: (0,)),
            pl.BlockSpec((nnz_cap,), lambda t: (0,)),
        ],
        out_specs=[pl.BlockSpec((tile,), lambda t: (t,))] * 4,
        out_shape=[jax.ShapeDtypeStruct((e,), jnp.int32),
                   jax.ShapeDtypeStruct((e,), jnp.int32),
                   jax.ShapeDtypeStruct((e,), jnp.int32),
                   jax.ShapeDtypeStruct((e,), bool)],
        interpret=interpret,
    )(gids, cc, total, all_front, all_payload, col_off, row_idx)


# ----------------------------------------------------------------------------
# Engine hooks: the chunk closures FrontierEngine threads into the scans
# ----------------------------------------------------------------------------

def make_expand_fn(*, path: str, tile: int = 512,
                   window: int = 256):
    """The kernel-backed chunk expansion for
    `repro.core.frontier.expand_frontier(expand_fn=...)`:

        (gids, cumul, all_front, front_total, col_off, row_idx, visited,
         words=None) -> (v, eligible_mask, u)

    The closure advertises `accepts_words`: `expand_frontier` then packs
    the visited bitmap ONCE per level and maintains it incrementally,
    instead of this chunk op repacking O(n_rows) bits every chunk.
    """
    interpret = path != "pallas"

    def expand_fn(gids, cumul, all_front, front_total, col_off, row_idx,
                  visited, words=None):
        return expand_chunk(gids, cumul, all_front, front_total, col_off,
                            row_idx, visited, words, tile=tile,
                            window=window, interpret=interpret)

    expand_fn.accepts_words = True
    return expand_fn


def make_value_expand_fn(*, path: str, tile: int = 512,
                         window: int = 256):
    """The kernel-backed value-carrying chunk expansion for
    `repro.algos.program.scan_relax(expand_fn=...)`:

        (gids, cumul, all_front, all_payload, front_total, col_off, row_idx)
            -> (v, payload, addr, valid)
    """
    interpret = path != "pallas"

    def value_expand_fn(gids, cumul, all_front, all_payload, front_total,
                        col_off, row_idx):
        return expand_chunk_values(gids, cumul, all_front, all_payload,
                                   front_total, col_off, row_idx, tile=tile,
                                   window=window, interpret=interpret)

    return value_expand_fn


# ----------------------------------------------------------------------------
# The standalone fused op (stage 4 compaction included)
# ----------------------------------------------------------------------------

class LocalExpandOut(NamedTuple):
    verts: jax.Array          # (n_rows,) discovered local rows, canonical
                              # ascending, pad -1
    parents: jax.Array        # (n_rows,) winning parent's local col, pad -1
    count: jax.Array          # () int32 number of discoveries
    visited: jax.Array        # (n_rows,) bool mask with discoveries set
    edges_scanned: jax.Array  # () uint32 live edges in the frontier


@functools.partial(
    jax.jit, static_argnames=("path", "edge_chunk", "tile", "window",
                              "dedup"))
def _local_expand(front, front_total, col_off, row_idx, visited, *,
                  path: str, edge_chunk: int, tile: int, window: int,
                  dedup: str) -> LocalExpandOut:
    n_rows = visited.shape[0]
    cumul, total, table = frontier_workload(front, front_total, col_off)
    words = pack_bitmap(visited) if path != "reference" \
        else jnp.zeros((1,), jnp.uint32)               # pytree placeholder

    def chunk_body(state):
        start, visited, words, parent, new = state
        gids = start + jnp.arange(edge_chunk, dtype=jnp.int32)
        if path == "reference":
            # exactly expand_frontier's inline jnp formulas (one source of
            # truth: repro.core.frontier.reference_expand_chunk)
            v, u, _, _, valid = reference_expand_chunk(
                gids, total, table, row_idx)
            elig = valid & ~visited[v]
        else:
            v, elig, u = expand_chunk(
                gids, cumul, front, front_total, col_off, row_idx, visited,
                words, tile=tile, window=window,
                interpret=path != "pallas")
        win = winner_dedup(v, elig, n_rows, method=dedup)
        tgt = jnp.where(win, v, n_rows)
        visited = visited.at[tgt].set(True, mode="drop")
        if path != "reference":
            words = set_bits(words, v, win)
        parent = parent.at[tgt].set(jnp.where(win, u, 0), mode="drop")
        new = new.at[tgt].set(True, mode="drop")
        return start + edge_chunk, visited, words, parent, new

    init = (jnp.int32(0), visited, words,
            jnp.full((n_rows,), -1, jnp.int32), jnp.zeros((n_rows,), bool))
    _, visited, _, parent, new = jax.lax.while_loop(
        lambda s: s[0] < total, chunk_body, init)

    # stage 4: compaction, canonical ascending (the repo-wide frontier order)
    rows = jnp.arange(n_rows, dtype=jnp.int32)
    key = jnp.where(new, rows, I32_MAX)
    srt = jnp.sort(key)
    ok = srt < I32_MAX
    verts = jnp.where(ok, srt, -1)
    parents = jnp.where(ok, parent[jnp.clip(srt, 0, n_rows - 1)], -1)
    return LocalExpandOut(verts=verts, parents=parents,
                          count=new.sum(dtype=jnp.int32), visited=visited,
                          edges_scanned=total.astype(jnp.uint32))


def local_expand(frontier, csc, visited, *, path: str = "auto",
                 edge_chunk: int = 2048, tile: int = 512, window: int = 256,
                 dedup: str = "scatter") -> LocalExpandOut:
    """One fused local frontier expansion (the paper's column scan).

    frontier: padded (L,) int32 local col ids (pad -1), or a (front, count)
              pair when the live count is already known.
    csc:      (col_off, row_idx) pair or any object with those attributes
              (e.g. `repro.core.types.LocalGraph2D` device blocks).
    visited:  (n_rows,) bool mask; returned updated (test-AND-set).

    Returns discoveries compacted in canonical ascending order with their
    winning parents -- bit-identical across all three expand paths.
    """
    if isinstance(frontier, (tuple, list)):
        front, count = frontier
    else:
        front, count = frontier, (jnp.asarray(frontier) >= 0).sum()
    front = jnp.asarray(front, jnp.int32)
    if hasattr(csc, "col_off"):
        col_off, row_idx = csc.col_off, csc.row_idx
    else:
        col_off, row_idx = csc
    col_off = jnp.asarray(col_off, jnp.int32)
    row_idx = jnp.asarray(row_idx, jnp.int32)
    ncl = col_off.shape[0] - 1
    if front.shape[0] > ncl:
        raise ValueError(f"frontier length {front.shape[0]} exceeds the "
                         f"{ncl} CSC columns")
    if front.shape[0] < ncl:   # pad to the column count the kernels index
        front = jnp.concatenate(
            [front, jnp.full((ncl - front.shape[0],), -1, jnp.int32)])
    return _local_expand(
        front, jnp.asarray(count, jnp.int32), col_off, row_idx,
        jnp.asarray(visited, bool), path=resolve_expand_path(path),
        edge_chunk=edge_chunk, tile=tile, window=window, dedup=dedup)
