"""Device-local frontier expansion / update (paper sec. 3.4, 3.5).

Everything here is pure jnp with static shapes: the one implementation of
the scan, the filter and the bottom-up parent search, on every backend
(DESIGN.md sec. 9-11).

Adaptation notes (DESIGN.md sec. 3):
  * `atomicOr` visited dedup      -> scatter-min "winner" selection (the first
    edge slot to reach v wins, deterministically);
  * `atomicInc` bucket append     -> stable sort by destination column +
    per-segment positions (the paper's own pre-Kepler compact variant);
  * thread-per-edge scan+search   -> tiled search over the exclusive-scanned
    degree array (`edge_slots`), processed in fixed-size chunks inside a
    `lax.while_loop` so per-level work stays O(frontier edges + chunk).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import Grid2D

I32_MAX = jnp.int32(jnp.iinfo(jnp.int32).max)
# `edge_slots`' tile of consecutive edge ids and cumul window
TILE, WINDOW = 512, 256
# values the top-down map picks per frontier slot: its column, its address
# base (`frontier_workload`)
SLOT_VALS = 2


def exclusive_cumsum(x):
    """Thrust exclusive_scan equivalent, returns len(x)+1 (with total)."""
    c = jnp.cumsum(x, dtype=jnp.int32)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), c])


def pick_tile(e: int, tile: int) -> int:
    """Largest DIVISOR of the chunk length <= tile.  Never rounds UP to e:
    a tile is a dense (tile, window) compare, so one e-wide tile on a big
    odd chunk would be as wide as the chunk.  Both arguments are static
    (e is the engine's edge_chunk), so this runs at trace time."""
    t = min(tile, e)
    while e % t:
        t -= 1
    return t


def map_workload_tile(gid, cumul, *, window: int, n_cumul: int,
                      n_vals: int = 0):
    """k[t] = max { l : cumul[l] <= gid[t] } for ONE tile of consecutive edge
    ids, as dense vector work (the paper's thread->edge mapping, sec. 3.4).

    The ids of a tile are consecutive, so their k form a non-decreasing run
    [k0, k_last]: one scalar binary search finds k0, then W-wide windowed
    broadcast-compares count, per lane, the entries in (k0, ...] that are
    <= gid.  The window loop runs ceil((k_last - k0) / W) times, so
    cumul must be CLIPPED by the caller (entries no live gid can reach set
    to I32_MAX) for the loop to stop after the live entries.

    n_vals > 0: cumul is a `slot_table`, each entry followed by its n_vals
    per-slot values.  A window pass then slices entries and values at once,
    and the compares that count k also pick each lane's values at k.  The
    result is (k, vals (n_vals, tile)).

    Vmapped over tiles, the body of `edge_slots`."""
    g0 = gid[0]
    gmax = gid[-1]
    stride = 1 + n_vals

    def key(i):                         # cumul[i], a scalar slice
        if n_vals:
            i = i * stride
        return jax.lax.dynamic_slice(cumul, (i,), (1,))[0]

    # --- 1. scalar binary search for k0 = max { l : cumul[l] <= g0 } ------
    def bcond(s):
        lo, hi = s
        return hi - lo > 1

    def bbody(s):
        lo, hi = s
        mid = (lo + hi) // 2
        cm = key(mid)
        lo2 = jnp.where(cm <= g0, mid, lo)
        hi2 = jnp.where(cm <= g0, hi, mid)
        return lo2, hi2

    k0, _ = jax.lax.while_loop(
        bcond, bbody, (jnp.int32(0), jnp.int32(n_cumul)))

    # --- 2. windowed broadcast-compare count over (k0, ...] ---------------
    if n_vals:
        vals = jnp.broadcast_to(jax.lax.dynamic_slice(
            cumul, (k0 * stride + 1,), (n_vals,))[:, None],
            (n_vals, gid.shape[0]))
    else:
        vals = ()

    def wcond(s):
        start = s[0]
        probe = key(jnp.minimum(start, n_cumul - 1))
        return (start < n_cumul) & (probe <= gmax)

    def wbody(s):
        start, count, vals = s
        base = jnp.minimum(start, n_cumul - window)
        if n_vals:
            wins = jax.lax.dynamic_slice(
                cumul, (base * stride,), (window * stride,)).reshape(
                    window, stride)
            win = wins[:, 0]
        else:
            win = jax.lax.dynamic_slice(cumul, (base,), (window,))
        pos = jax.lax.iota(jnp.int32, window)
        idx_ok = base + pos >= start
        hits = (win[None, :] <= gid[:, None]) & idx_ok[None, :]
        nxt = start + window
        n = jnp.sum(hits, axis=1, dtype=jnp.int32)
        if n_vals:
            # a lane's hits are one run from the window's first entry
            # >= start (cumul is non-decreasing), so its last hit, the
            # lane's slot so far, sits at window position first + n - 1
            last = jnp.maximum(start - base, 0) + n - 1
            pick = pos[None, :] == last[:, None]
            got = jnp.sum(jnp.where(pick[None], wins.T[1:, None, :], 0),
                          axis=2, dtype=jnp.int32)
            vals = jnp.where(n[None, :] > 0, got, vals)
        return nxt, count + n, vals

    _, count, vals = jax.lax.while_loop(
        wcond, wbody, (k0 + 1, jnp.zeros_like(gid), vals))
    if n_vals:
        return k0 + count, vals
    return k0 + count


def clip_by_value(cumul, total):
    """cumul with every entry >= total set to I32_MAX.  No live gid
    (< total) can reach such an entry, so `map_workload_tile` gives the same
    k on live lanes and its window loop stops after the live entries.
    Exact for any non-decreasing cumul: a live prefix then `total` repeated
    (top-down) or a masked cumsum with zero-width runs (bottom-up)."""
    return jnp.where(cumul < total, cumul, I32_MAX)


def slot_table(cumul, total, payload):
    """What `edge_slots` searches when it also picks per-slot values: each
    entry of `clip_by_value(cumul, total)` followed by its P values
    (payload (P, len(cumul))), flat, so a window pass slices entries and
    values with one contiguous read.  Built once per level, outside the
    chunk loop: a 2-D table costs the chip a relayout of all of it per
    chunk, to lay each slot's values side by side for the window slices."""
    rows = jnp.concatenate([clip_by_value(cumul, total)[None], payload])
    return rows.T.reshape(-1)


def edge_slots(cumul, gids, total, *, n_vals: int = 0, tile: int = TILE,
               window: int = WINDOW):
    """k[t] = max { l : cumul[l] <= gids[t] } on every live lane
    (gids[t] < total) of a chunk of consecutive edge ids -- what
    `searchsorted(cumul, gids, side="right") - 1` gives there.  Dead lanes
    get some in-range slot; callers mask them.

    `map_workload_tile` per tile of consecutive ids: one binary search per
    tile instead of one per lane.  A per-lane search is log2(len(cumul))
    dependent gathers for every edge, and on a TPU it took most of a
    level's time (DESIGN.md sec. 9.2).  cumul is clipped with
    `clip_by_value`.

    n_vals > 0: cumul is a `slot_table` (clipped already) of n_vals values
    per slot, and the result is (k, vals) with vals[:, t] the values of
    slot k[t], picked by the window loop's own compares.  On a TPU a
    per-lane gather costs about the same per lane whatever its source's
    size, so a caller that needs a slot's values takes them here
    (DESIGN.md sec. 9.2).

    The window loop of a tile runs once per `window` cumul entries its ids
    span, and under vmap every tile pays the widest tile's count
    (`slot_passes`).  A run of zero-width entries inside a tile
    (zero-degree or visited rows in the bottom-up masked cumsum) widens
    that span beyond the tile's length."""
    e = gids.shape[0]
    n_cumul = cumul.shape[0] // (1 + n_vals)
    tile = pick_tile(e, tile)
    window = min(window, n_cumul)
    if n_vals:
        k, vals = jax.vmap(
            lambda g: map_workload_tile(g, cumul, window=window,
                                        n_cumul=n_cumul, n_vals=n_vals),
            out_axes=(0, 1))(gids.reshape(e // tile, tile))
        return k.reshape(e), vals.reshape(n_vals, e)
    cc = clip_by_value(cumul, total)
    k = jax.vmap(lambda g: map_workload_tile(g, cc, window=window,
                                             n_cumul=n_cumul))(
        gids.reshape(e // tile, tile))
    return k.reshape(e)


def slot_passes(k, n_cumul: int, *, tile: int = TILE,
                window: int = WINDOW):
    """The window passes `edge_slots` ran for one chunk whose slots are k:
    a tile's loop runs ceil((k_last - k_first) / window) times and vmap
    runs the widest tile's count for every tile.  The cost of a chunk's
    slot search, and of each value it picks, scales with it."""
    e = k.shape[0]
    tile = pick_tile(e, tile)
    window = min(window, n_cumul)
    kt = k.reshape(e // tile, tile)
    return jnp.max((kt[:, -1] - kt[:, 0] + window - 1) // window)


def compact_blocks(vals, cnts, fill=-1):
    """Concatenate R padded blocks (R, S) with per-block counts into one
    padded (R*S,) array (valid entries first, order preserved)."""
    R, S = vals.shape
    mask = jnp.arange(S, dtype=jnp.int32)[None, :] < cnts[:, None]
    total = jnp.sum(cnts, dtype=jnp.int32)
    flat_v = vals.reshape(-1)
    flat_m = mask.reshape(-1)
    order = jnp.argsort(~flat_m, stable=True)
    out = jnp.where(flat_m[order], flat_v[order], fill)
    return out, total


def winner_dedup(v, eligible, n_rows: int, method: str = "scatter"):
    """First-occurrence selection among eligible entries with equal v.

    Emulates the paper's `atomicOr` first-thread-wins semantics
    deterministically.  Two implementations:
      * "scatter" (default): scatter-min of slot ids into an (n_rows,) claim
        array -- the smallest slot claiming v wins.  O(chunk) scatters but
        touches an n_rows-sized temp every chunk.
      * "sort": sort by v, keep the first of each equal run -- O(chunk log
        chunk) with NO n_rows-sized temp (the memory-roofline win for large
        local partitions; winner = lowest v-then-slot, still deterministic
        and a valid first-claimant).
    Returns a bool mask of winners (subset of `eligible`).
    """
    slots = jnp.arange(v.shape[0], dtype=jnp.int32)
    if method == "sort":
        key = jnp.where(eligible, v, I32_MAX)
        order = jnp.argsort(key, stable=True)
        ks = key[order]
        first = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
        first = first & (ks < I32_MAX)
        win = jnp.zeros_like(eligible).at[order].set(first)
        return win & eligible
    claim = jnp.full((n_rows,), I32_MAX, jnp.int32)
    claim = claim.at[jnp.where(eligible, v, n_rows)].min(
        jnp.where(eligible, slots, I32_MAX), mode="drop")
    return eligible & (claim[jnp.clip(v, 0, n_rows - 1)] == slots)


def bucket_append(dst, dst_cnt, v, tgt, take, n_buckets: int):
    """Append v[take] into per-target buckets (paper Alg. 3 lines 9-14).

    dst: (n_buckets, cap) padded -1; dst_cnt: (n_buckets,).
    Sort-based: stable sort by target, per-segment positions, scatter at
    dst_cnt[tgt] + position.  Entries overflowing `cap` are dropped -- callers
    size cap = S so overflow is impossible (<= S distinct owned vertices per
    target per search).
    """
    cap = dst.shape[1]
    key = jnp.where(take, tgt, n_buckets).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    ks, vs = key[order], v[order]
    seg_start = jnp.searchsorted(ks, jnp.arange(n_buckets + 1, dtype=jnp.int32))
    pos = jnp.arange(ks.shape[0], dtype=jnp.int32) - seg_start[jnp.clip(ks, 0, n_buckets)]
    ok = ks < n_buckets
    row = jnp.where(ok, ks, 0)
    col = dst_cnt[row] + pos
    ok = ok & (col < cap)
    dst = dst.at[jnp.where(ok, row, n_buckets), jnp.clip(col, 0, cap - 1)].set(
        jnp.where(ok, vs, -1), mode="drop")
    add = jnp.diff(seg_start)[:n_buckets]
    return dst, dst_cnt + jnp.minimum(add, cap - dst_cnt)


def compact_mask(mask, offset=0):
    """Ascending positions of the set lanes of an (S,) bool mask, plus
    `offset`, padded with -1 to (S,), and their count.

    One prefix sum and one scatter (each set lane lands at its rank), no
    sort: the frontier update's compaction of a level's owned discoveries.
    """
    S = mask.shape[0]
    lanes = jnp.arange(S, dtype=jnp.int32)
    rank = jnp.cumsum(mask, dtype=jnp.int32)
    out = jnp.full((S,), -1, jnp.int32).at[
        jnp.where(mask, rank - 1, S)].set(lanes + offset, mode="drop")
    return out, rank[-1]


def append_padded(buf, cnt, vals, valid):
    """Append vals[valid] to a padded (cap,) buffer at position cnt."""
    b, c = bucket_append(buf[None, :], cnt[None], vals,
                         jnp.zeros_like(vals), valid, 1)
    return b[0], c[0]


def pack_bitmap(mask):
    """(..., S) bool -> (..., ceil(S/32)) uint32 little-endian bit packing."""
    S = mask.shape[-1]
    W = (S + 31) // 32
    pad = W * 32 - S
    if pad:
        mask = jnp.concatenate(
            [mask, jnp.zeros(mask.shape[:-1] + (pad,), bool)], axis=-1)
    m = mask.reshape(mask.shape[:-1] + (W, 32)).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(m * weights, axis=-1, dtype=jnp.uint32)


def unpack_bitmap(words, S: int):
    """(..., W) uint32 -> (..., S) bool."""
    bits = (words[..., :, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    return bits.reshape(words.shape[:-1] + (-1,))[..., :S].astype(bool)


def frontier_workload(all_front, front_total, col_off):
    """Per level, dense over the ncl gathered frontier slots: the edge
    workload the chunk scan walks and what each slot gives its edges.

    Returns (cumul, total, table): cumul the exclusive cumsum of the
    frontier columns' degrees (ncl + 1), total = cumul[front_total] the
    level's edge count, and table the `slot_table` that
    `reference_expand_chunk` searches: beside each clipped cumul entry k,
    the SLOT_VALS values of slot k, its column u and its address base
    col_off[u] - cumul[k] (at k = ncl the last slot's again: slots are
    clipped to ncl - 1).

    It runs under the sub-scope `workload` of the caller's layer scope
    (`repro/expand/workload`): work dense over ncl that every level pays
    before its first chunk, whatever its frontier."""
    ncl = all_front.shape[0]
    with jax.named_scope("workload"):
        u_safe = jnp.clip(all_front, 0, ncl - 1)
        off = col_off[u_safe]
        deg = col_off[u_safe + 1] - off
        deg = jnp.where(jnp.arange(ncl) < front_total, deg, 0)
        cumul = exclusive_cumsum(deg)                  # (ncl + 1,)
        total = cumul[front_total]
        vals = jnp.stack([u_safe, off - cumul[:-1]])
        vals = jnp.concatenate([vals, vals[:, -1:]], axis=1)
        return cumul, total, slot_table(cumul, total, vals)


def reference_expand_chunk(gids, total, table, row_idx):
    """One chunk of the paper's column scan: the map/gather formulas that
    `expand_frontier` and `repro.algos.program.scan_relax` share.

    total, table: the level's `frontier_workload`.  The slot search picks
    each lane's frontier column u and address base along with its slot k,
    so the CSC row id v = row_idx[base + gid] is the one per-lane gather:
    u = front[k] and addr = col_off[u] + gid - cumul[k].

    Returns (v, u, k, addr, valid): candidate local rows (masked lanes
    -> 0), parent frontier cols, frontier slot index, clipped CSC edge
    address, live-lane mask.
    """
    ncl = table.shape[0] // (1 + SLOT_VALS) - 1
    nnz_cap = row_idx.shape[0]
    k, (u, base) = edge_slots(table, gids, total, n_vals=SLOT_VALS)
    k = jnp.clip(k, 0, ncl - 1)
    addr = jnp.clip(base + gids, 0, nnz_cap - 1)
    valid = gids < total
    v = jnp.where(valid, row_idx[addr], 0)
    return v, u, k, addr, valid


def test_bit_blocks(words, c, block: int):
    """Test bit `c` of a row-gathered blocked bitmap.

    words: (R * W,) uint32, R per-device blocks of W = ceil(block/32) words
    each, every block packing `block` bits (`pack_bitmap` of one owned
    frontier mask).  Blocked addressing -- NOT a flat n-bit bitmap -- so the
    layout stays exact when block % 32 != 0 (each device's pad bits are
    zero, never aliased by a neighbour's first word).
    """
    W = (block + 31) // 32
    blk, off = c // block, c % block
    w = words[blk * W + (off >> 5)]
    return ((w >> (off & 31).astype(jnp.uint32)) & jnp.uint32(1)) != 0


def reference_bottomup_chunk(gids, cumul, total, row_off, col_idx, words, *,
                             block: int):
    """One chunk of the bottom-up parent search (the CSR mirror of
    `reference_expand_chunk`), shared by the BFS bottom-up step and the
    value programs' pull scan (`reference_bottomup_values_chunk`).

    gids index the masked-degree workload: `cumul` is the exclusive cumsum
    of per-row degrees with VISITED rows zeroed, so the scan walks only
    unvisited rows' edges; `total = cumul[-1]` is the level's edge count.
    words: row-gathered frontier bitmap, blocked layout (`test_bit_blocks`).

    Returns (r, c, hit): candidate local row, its neighbour's local col
    (masked lanes -> 0), and whether that neighbour is in the frontier.
    """
    nrl = cumul.shape[0] - 1
    nnz_cap = col_idx.shape[0]
    r = jnp.clip(edge_slots(cumul, gids, total), 0, nrl - 1)
    addr = jnp.clip(row_off[r] + gids - cumul[r], 0, nnz_cap - 1)
    valid = gids < total
    c = jnp.where(valid, col_idx[addr], 0)
    hit = valid & test_bit_blocks(words, c, block)
    return r, c, hit


def reference_bottomup_values_chunk(gids, cumul, total, row_off, col_idx,
                                    words, dense_pay, *, block: int):
    """`reference_bottomup_chunk` with an aligned payload gather (value
    programs pull the sender's label/distance from a dense per-col channel).

    Returns (r, pay, addr, hit) -- addr is the clipped CSR edge address so
    callers can gather per-edge weights (SSSP)."""
    r, c, hit = reference_bottomup_chunk(
        gids, cumul, total, row_off, col_idx, words, block=block)
    nnz_cap = col_idx.shape[0]
    addr = jnp.clip(row_off[r] + gids - cumul[r], 0, nnz_cap - 1)
    pay = dense_pay[c]
    return r, pay, addr, hit


class ExpandResult(NamedTuple):
    visited: jax.Array
    level: jax.Array
    pred: jax.Array
    dst: jax.Array        # (C, S) local-row ids grouped by owner column
    dst_cnt: jax.Array    # (C,)
    edges_scanned: jax.Array  # uint32 -- callers accumulate across levels
                              # with engine.wide_add (int32 wraps at scale 26)
    map_passes: jax.Array     # int32 slot-search window passes of the level
                              # (`slot_passes` summed over chunks), 0 unless
                              # counted


def expand_frontier(col_off, row_idx, visited, level, pred, all_front,
                    front_total, lvl, *, grid: Grid2D, i, j,
                    edge_chunk: int = 8192, dedup: str = "scatter",
                    count_passes: bool = False) -> ExpandResult:
    """Scan the CSC columns of the gathered frontier (paper Alg. 3).

    all_front: (n_cols_local,) local col indices (valid first `front_total`).
    i, j: this device's grid coordinates (traced or static).
    count_passes: static; count the map's window passes into `map_passes`
        (the telemetry channel).  Off, the program holds no counter.
    """
    n_rows = visited.shape[0]
    S, C = grid.S, grid.C
    ncl = grid.n_cols_local

    cumul, total, table = frontier_workload(all_front, front_total,
                                            col_off)

    dst = jnp.full((C, S), -1, jnp.int32)
    dst_cnt = jnp.zeros((C,), jnp.int32)

    # The chunk body's three parts run under their own scopes, nested in
    # the caller's layer scope (DESIGN.md sec. 13.4): `map` (edge slot ->
    # frontier column -> row id), `filter` (visited test and first-claimant
    # selection), `mark` (visited / pred / level scatters, bucket append).
    def chunk_body(state):
        start, visited, level, pred, dst, dst_cnt = state[:6]
        gids = start + jnp.arange(edge_chunk, dtype=jnp.int32)
        with jax.named_scope("map"):
            v, u, k, _, valid = reference_expand_chunk(
                gids, total, table, row_idx)
            if count_passes:
                passes = state[6] + slot_passes(k, cumul.shape[0])
        with jax.named_scope("filter"):
            unvis = valid & ~visited[v]
            win = winner_dedup(v, unvis, n_rows, method=dedup)
        with jax.named_scope("mark"):
            # mark visited (paper: atomicOr on the full-local-row bitmap --
            # this is what makes every remote vertex fold at most once per
            # search)
            visited = visited.at[jnp.where(win, v, n_rows)].set(
                True, mode="drop")
            # predecessor: global parent id, stored also for remote rows
            # (deferred resolution, paper sec. 3.5 / [2])
            pg = (j * ncl + u).astype(jnp.int32)
            pred = pred.at[jnp.where(win, v, n_rows)].set(
                jnp.where(win, pg, 0), mode="drop")
            # local rows get their level here (Alg. 3 line 15)
            m = v // S
            is_local = win & (m == j)
            level = level.at[jnp.where(is_local, v, n_rows)].set(
                jnp.where(is_local, lvl, 0), mode="drop")
            dst, dst_cnt = bucket_append(dst, dst_cnt, v, m, win, C)
        out = (start + edge_chunk, visited, level, pred, dst, dst_cnt)
        return out + (passes,) if count_passes else out

    def chunk_cond(state):
        return state[0] < total

    init = (jnp.int32(0), visited, level, pred, dst, dst_cnt)
    if count_passes:
        init += (jnp.int32(0),)
    out = jax.lax.while_loop(chunk_cond, chunk_body, init)
    _, visited, level, pred, dst, dst_cnt = out[:6]
    # per-level count reported unsigned: one level's local scan is bounded by
    # the int32-indexable local nnz, but the SUM across levels/devices is not
    return ExpandResult(visited, level, pred, dst, dst_cnt,
                        total.astype(jnp.uint32),
                        out[6] if count_passes else jnp.int32(0))


class UpdateResult(NamedTuple):
    visited: jax.Array
    level: jax.Array
    pred: jax.Array


def update_frontier(int_verts, int_cnt, visited, level,
                    pred, lvl) -> UpdateResult:
    """Process fold-received vertices (paper sec. 3.5).

    int_verts: (C, S) local-row ids received from each processor-column
    (sender m in slot m).  Received vertices are OWNED here; unvisited ones
    get level/visited set and pred <- -(sender_col + 2) (deferred).  They
    join the next frontier through `level == lvl` (the caller's
    `compact_mask`).
    """
    n_rows = visited.shape[0]
    C, S = int_verts.shape
    sender = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[:, None], (C, S))
    mask = jnp.arange(S, dtype=jnp.int32)[None, :] < int_cnt[:, None]
    v = jnp.where(mask, int_verts, 0).reshape(-1)
    snd = sender.reshape(-1)
    eligible = mask.reshape(-1) & ~visited[v]
    win = winner_dedup(v, eligible, n_rows)
    visited = visited.at[jnp.where(win, v, n_rows)].set(True, mode="drop")
    level = level.at[jnp.where(win, v, n_rows)].set(
        jnp.where(win, lvl, 0), mode="drop")
    pred = pred.at[jnp.where(win, v, n_rows)].set(
        jnp.where(win, -(snd + 2), 0), mode="drop")
    return UpdateResult(visited, level, pred)
