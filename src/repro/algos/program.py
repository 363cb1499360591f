"""The `FrontierProgram` contract + shared value-propagation blocks
(DESIGN.md sec. 8).

A frontier program is a distributed graph algorithm expressed against the
2D-partitioned engine: per-vertex state that evolves under a commutative,
idempotent combine (a monoid -- min over labels for connected components,
min over distances for SSSP, first-wave-wins source ids for multi-source
BFS), a per-level `step` that expands the current frontier and folds an
outgoing payload to the owners, and a convergence predicate.  The engine
(`repro.algos.engine.FrontierEngine`) supplies the loop, the collectives and
the accounting; the fold wire format is the codec layer of
`repro.dist.exchange` (`codec_hint` picks a default, callers may override).

The helpers below implement the common "value propagation" level shape used
by CC / SSSP / multi-source BFS:

  gather frontier + payload  ->  chunked CSC scan min-combining relaxed
  payloads into a dense per-local-row candidate array  ->  pack improved
  rows into canonical per-owner buckets  ->  value-carrying fold
  (`FoldCodec.fold_values`)  ->  scatter-min merge into owned state  ->
  rebuild the frontier from changed owned rows.

Everything is min-combined, so results are independent of delivery order --
the reason every fold codec produces bit-identical outputs by construction.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import frontier as F
from repro.core.types import Grid2D, _dc

I32_MAX = F.I32_MAX


# ----------------------------------------------------------------------------
# The contract
# ----------------------------------------------------------------------------

class FrontierProgram:
    """What a distributed frontier algorithm implements.

    Attributes
    ----------
    name:        short program id; part of every engine/AOT cache key.
    codec_hint:  fold wire format used when the caller does not pin one.
    n_extra:     number of extra per-device (R, C, ...) graph arrays the
                 program consumes (e.g. per-edge weights).
    n_csr_extra: how many MORE extras the bottom-up twin of the step needs
                 appended after the regular ones -- (row_off, col_idx) of
                 the CSR twin for everyone, plus the CSR-ordered weights
                 for SSSP.  Only consumed via `DirectionProgram`.
    uses_bottomup: True when `make_step` may call into the bottom-up kernel
                 hooks (`engine.bottomup_fn` / `engine.value_bottomup_fn`);
                 the engine only constructs those hooks when set.

    The engine calls, in order: `init` (per search), `make_step` (once per
    trace), the loop (`keep_going` / the step), then `finalize`; host-side
    `assemble` turns gathered device outputs into the program's output
    object.  All methods receive the engine for access to the topology,
    grid, codec and knobs.
    """
    name = "?"
    codec_hint = "list"
    n_extra = 0
    n_csr_extra = 2
    uses_bottomup = False

    @property
    def key(self) -> tuple:
        """Hashable identity: programs with equal keys may share an engine
        (together with codec/chunking, see BFSConfig.algo_engine_key)."""
        return (self.name,)

    def init(self, engine, graph, extra, arg, i, j):
        """Per-device initial state pytree for one search argument."""
        raise NotImplementedError

    def make_step(self, engine, graph, extra, i, j):
        """Return step(state, prev_total) -> (state', total, scanned[, aux]).

        The optional 4th element is the per-level telemetry channel
        (DESIGN.md sec. 13): a dict with scalar entries `folded` (entries
        this device folded to owners), `wire` (fold wire bytes sent) and
        `dir` (0 top-down / 1 bottom-up).  Untraced engines drop it before
        the loop carry, so returning it costs nothing when telemetry is
        off; legacy 3-tuple steps remain valid (the trace records zeros).
        """
        raise NotImplementedError

    def make_bottomup_step(self, engine, graph, extra, i, j):
        """Bottom-up twin of `make_step` (same signature/return), consuming
        the `n_csr_extra` CSR arrays at the END of `extra`.  Must be
        bit-identical to the top-down step in its state trajectory, so the
        direction driver may mix directions level by level."""
        raise NotImplementedError(
            f"{self.name} has no bottom-up step; it cannot run under "
            f"direction optimisation")

    def front_count(self, st):
        """This device's own frontier count entering a level (the telemetry
        carry's `front_dev` channel).  Every state pytree in the repo
        carries `front_cnt`; wrappers delegate to their inner program."""
        return st.front_cnt

    def keep_going(self, engine, st, total):
        """Convergence predicate (True = run another level)."""
        raise NotImplementedError

    def init_total(self, engine, st):
        """Global size of the initial frontier (the loop's entry total)."""
        raise NotImplementedError

    def finalize(self, engine, st, i, j) -> tuple:
        """Per-device output arrays (engine appends the (hi, lo) counters)."""
        raise NotImplementedError

    def out_specs(self, engine) -> tuple:
        """PartitionSpecs matching `finalize`'s outputs."""
        raise NotImplementedError

    def assemble(self, engine, outs, B):
        """Host-side: gathered device outputs -> output object (B=None for a
        scalar search, else the leading batch size)."""
        raise NotImplementedError

    # -- mid-traversal checkpointing (DESIGN.md sec. 15) ---------------------

    def level_count(self, st):
        """The state's 1-based level/iteration counter (device array; the
        segmented driver's progress readout).  Works on host-fetched
        (R, C[, B]) state pytrees too -- it is plain attribute access."""
        raise NotImplementedError

    def export_state(self, engine, st, n: int) -> dict:
        """Host-fetched scalar-search state (leaves (R, C, ...) numpy) ->
        flat dict of numpy arrays in GLOBAL vertex-id order, sliced to the
        raw `n` -- the grid-independent half of the checkpoint schema.
        Must include a 0-d `levels_done` entry."""
        raise NotImplementedError(
            f"{self.name} does not support mid-traversal checkpointing")

    def import_state(self, engine, snap: dict):
        """Inverse of `export_state` onto ENGINE's grid (which need not be
        the grid that exported `snap`): a state pytree with (R, C, ...)
        numpy leaves, re-padded to the new grid and with per-device caches
        rebuilt from the authoritative global state."""
        raise NotImplementedError(
            f"{self.name} does not support mid-traversal checkpointing")


# ----------------------------------------------------------------------------
# Shared state pytree for min-monoid value programs (CC, SSSP)
# ----------------------------------------------------------------------------

@_dc
@dataclasses.dataclass
class ValueState:
    """Per-device state of a min-monoid value-propagation program.

    `val` spans ALL local rows (n/R), generalizing the BFS visited bitmap:
    the owned block is the authoritative value, remote rows are this
    device's send-suppression cache (the smallest value it has ever
    proposed/seen for that vertex -- proposing anything >= it is provably
    redundant, the exact role `visited` plays for BFS).
    """
    val: jax.Array        # (n_rows_local,) int32, I32_MAX = top
    front: jax.Array      # (S,) local col ids, canonical ascending, pad -1
    payload: jax.Array    # (S,) int32 values aligned with front
    front_cnt: jax.Array  # () int32
    it: jax.Array         # () int32, 1-based iteration counter


# ----------------------------------------------------------------------------
# Level building blocks
# ----------------------------------------------------------------------------

def scan_relax(col_off, row_idx, edge_vals, all_front, all_payload,
               front_total, relax, *, n_rows: int,
               edge_chunk: int = 8192, expand_fn=None):
    """Chunked CSC scan of the gathered frontier, min-combining relaxed
    payloads into a dense per-local-row candidate array.

    For each edge u -> v of a frontier column u, proposes
    `relax(payload[u], edge_vals[edge])` for v; proposals for the same v
    combine by MIN (the monoid), so the result is independent of scan order.
    Same chunked edge walk as `frontier.expand_frontier` (paper Alg. 3,
    slots by `frontier.edge_slots`), same O(frontier edges + chunk) cost
    per level.

    expand_fn: optional value-carrying kernel override for one chunk (the
    fused Pallas path, `repro.kernels.expand.make_value_expand_fn`):
        (gids, cumul, all_front, all_payload, front_total, col_off, row_idx)
            -> (v, payload, addr, valid)
    Bit-identical to the inline scan: the kernel maps/gathers, the relax
    monoid and the scatter-min combine stay here.

    Returns (cand (n_rows,) int32, edges_scanned uint32).
    """
    cumul, total, table = F.frontier_workload(all_front, front_total,
                                              col_off)

    def chunk_body(state):
        start, cand = state
        gids = start + jnp.arange(edge_chunk, dtype=jnp.int32)
        if expand_fn is None:
            v, _, k, addr, valid = F.reference_expand_chunk(
                gids, total, table, row_idx)
            pay = all_payload[k]
        else:
            v, pay, addr, valid = expand_fn(gids, cumul, all_front,
                                            all_payload, front_total,
                                            col_off, row_idx)
        w = None if edge_vals is None else edge_vals[addr]
        val = jnp.where(valid, relax(pay, w), I32_MAX)
        cand = cand.at[jnp.where(valid, v, n_rows)].min(val, mode="drop")
        return start + edge_chunk, cand

    init = (jnp.int32(0), jnp.full((n_rows,), I32_MAX, jnp.int32))
    _, cand = jax.lax.while_loop(lambda s: s[0] < total, chunk_body, init)
    return cand, total.astype(jnp.uint32)


def pack_blocks(improved, vals, grid: Grid2D, fill_val=I32_MAX, ops=None):
    """Dense (n_rows_local,) improvements -> canonical fold buckets.

    Local row m*S + t of block m maps to bucket row m, so the dense array IS
    the bucket structure after a reshape; per bucket, improved entries are
    front-packed ascending (the canonical form `FoldCodec.fold_values`
    requires).  Returns (ids (C, S) local-row ids pad -1, cnt (C,),
    vals (C, S) aligned, pad `fill_val`).

    ops: optional fold-kernel bundle (`repro.kernels.fold`) whose prefix-sum
    compaction replaces the per-level argsort; bit-identical either way.
    """
    C, S = grid.C, grid.S
    imp = improved.reshape(C, S)
    vv = vals.reshape(C, S)
    t = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (C, S))
    m = jnp.arange(C, dtype=jnp.int32)[:, None]
    if ops is not None:
        # pads are -1 (not I32_MAX as on the reference path), so m*S + ts
        # cannot overflow and a single mask suffices
        (ts, vs), cnt = ops.compact_rows(imp, (t, vv), (-1, fill_val))
        ids = jnp.where(ts >= 0, m * S + ts, -1)
        return ids, cnt, vs
    key = jnp.where(imp, t, I32_MAX)
    order = jnp.argsort(key, axis=1)
    ts = jnp.take_along_axis(key, order, axis=1)
    vs = jnp.take_along_axis(vv, order, axis=1)
    ok = ts < I32_MAX
    ids = jnp.where(ok, m * S + jnp.where(ok, ts, 0), -1)
    vs = jnp.where(ok, vs, fill_val)
    return ids, imp.sum(axis=1, dtype=jnp.int32), vs


def scatter_min_received(recv_ids, recv_vals, j, S: int):
    """Fold-received (C, S) owned rows j*S + t + aligned values -> (S,)
    per-owned-row MIN over all senders (I32_MAX where nothing arrived)."""
    t = jnp.where(recv_ids >= 0, recv_ids - j * S, S)
    inc = jnp.full((S,), I32_MAX, jnp.int32)
    return inc.at[t.reshape(-1)].min(
        jnp.where(recv_ids >= 0, recv_vals, I32_MAX).reshape(-1), mode="drop")


def make_value_step(engine, graph, i, j, *, relax, edge_vals=None,
                    expand_fill=I32_MAX, scan=None):
    """The complete min-monoid level step shared by CC and SSSP.

    gather frontier+payload -> scan_relax -> suppress (strict improvements
    over the local cache only) -> pack_blocks -> codec fold_values ->
    scatter-min merge into the owned block -> rebuild the frontier from
    changed owned rows.  `relax(payload_u, w)` is the per-edge proposal
    (identity for label propagation, min-plus for SSSP); `edge_vals` is the
    per-device per-edge array `relax` consumes (or None); `expand_fill`
    pads the gathered payload channel (never read under the valid mask).

    scan: optional replacement for the gather + scan_relax prefix,
    `state -> (cand (n_rows_local,), edges_scanned uint32)` -- the bottom-up
    pull scan (`repro.algos.direction.make_pull_scan`) injects here; it must
    produce bit-identical candidates, so everything downstream is shared.
    """
    from repro.dist import exchange as X

    grid, topo = engine.grid, engine.topo
    S, nrl = grid.S, grid.n_rows_local
    fold_ops = engine.fold_ops

    # telemetry channel constants: pull scans are the bottom-up direction,
    # and a value fold's wire bytes are count-proportional (on the flat
    # route, PR 5's wire_bytes_values_sent = static header + 4 bytes per
    # folded entry; the exchange strategy scales header and hop count)
    step_dir = jnp.int32(1 if scan is not None else 0)
    ex_strat = engine.exchange
    wire_base = jnp.uint32(ex_strat.wire_bytes(
        engine.codec.wire_bytes(grid), grid.C))
    step_msgs = jnp.int32(ex_strat.msgs_per_exchange(grid.C))

    def step(st: ValueState, prev_total):
        with jax.named_scope("repro/expand"):
            if scan is not None:
                with jax.named_scope("bottomup"):
                    cand, scanned = scan(st)
            else:
                with jax.named_scope("exchange"):
                    all_front, all_pay, ftot = X.expand_exchange_values(
                        st.front, st.front_cnt, st.payload, topo=topo,
                        fill=expand_fill, ops=fold_ops)
                cand, scanned = scan_relax(
                    graph.col_off, graph.row_idx, edge_vals, all_front,
                    all_pay, ftot, relax, n_rows=nrl,
                    edge_chunk=engine.edge_chunk,
                    expand_fn=engine.value_expand_fn)
        with jax.named_scope("repro/update"):
            # propose only strict improvements over what we already know
            improved = cand < st.val
            val1 = jnp.minimum(st.val, cand)
        with jax.named_scope("repro/fold"):
            ids, cnt, vals = pack_blocks(improved, cand, grid, ops=fold_ops)
            ri, rc, rv = engine.codec.fold_values(ids, cnt, vals, topo=topo,
                                                  j=j)
        with jax.named_scope("repro/update"):
            inc = scatter_min_received(ri, rv, j, S)
            # merge against the PRE-scan owned block: this device's own
            # proposals travel through the self all_to_all block, so
            # comparing with val1 would mask them out of `changed`
            owned_prev = jax.lax.dynamic_slice_in_dim(st.val, j * S, S)
            new_owned = jnp.minimum(owned_prev, inc)
            changed = new_owned < owned_prev
            val2 = jax.lax.dynamic_update_slice(val1, new_owned, (j * S,))
            front, payload, nc = owned_to_front(changed, new_owned, i, S,
                                                ops=fold_ops)
            st2 = ValueState(val=val2, front=front, payload=payload,
                             front_cnt=nc, it=st.it + 1)
        with jax.named_scope("repro/loop"):
            folded = cnt.sum(dtype=jnp.int32)
            aux = {"folded": folded,
                   "wire": wire_base + ex_strat.value_extra_bytes(
                       cnt, j, grid.C),
                   "msgs": step_msgs,
                   "dir": step_dir}
            return st2, topo.psum_all(nc), scanned, aux

    return step


def owned_to_front(changed, vals, i, S: int, fill_val=I32_MAX, ops=None):
    """Changed owned rows -> next frontier, canonical ascending.

    Owned local row j*S + t converts to local col i*S + t (paper ROW2COL).
    Returns (front (S,) col ids pad -1, payload (S,) aligned, cnt).

    ops: optional fold-kernel bundle replacing the argsort (bit-identical).
    """
    t = jnp.arange(S, dtype=jnp.int32)
    if ops is not None:
        (ts, vs), cnt = ops.compact_rows(changed[None, :],
                                         (t[None, :], vals[None, :]),
                                         (-1, fill_val))
        ts, vs = ts[0], vs[0]
        front = jnp.where(ts >= 0, i * S + ts, -1)      # pads are -1
        return front, vs, cnt[0]
    key = jnp.where(changed, t, I32_MAX)
    order = jnp.argsort(key)
    ts = key[order]
    vs = vals[order]
    ok = ts < I32_MAX
    front = jnp.where(ok, i * S + jnp.where(ok, ts, 0), -1)
    payload = jnp.where(ok, vs, fill_val)
    return front, payload, changed.sum(dtype=jnp.int32)


# ----------------------------------------------------------------------------
# Checkpoint-schema helpers (DESIGN.md sec. 15)
#
# Export walks the (R, C, ...) host leaves into GLOBAL vertex-id order;
# import rebuilds a new grid's per-device layout from the global arrays.
# Both live on the partition identities of DESIGN.md sec. 2: device (i, j)'s
# owned block b = j*R + i covers global ids [(j*R + i)*S, (j*R + i + 1)*S),
# its local rows run over blocks m*R + i for m in 0..C-1, and owned local
# row j*S + t converts to local col i*S + t (ROW2COL).
# ----------------------------------------------------------------------------

def rows_to_global(grid: Grid2D, i: int) -> np.ndarray:
    """Global vertex ids of device-row i's local rows, in local-row order
    (identical for every device in grid row i -- the j-independence that
    lets import fill ALL local rows from one gather)."""
    R, C, S = grid.R, grid.C, grid.S
    return ((np.arange(C)[:, None] * R + i) * S
            + np.arange(S)[None, :]).reshape(-1)


def export_value_state(grid: Grid2D, st: ValueState, n: int) -> dict:
    """Host (R, C, ...) ValueState -> global snapshot.

    `val` exports the RAW owned blocks (I32_MAX = top; programs whose
    finalize remaps sentinels do so only at output time), `in_front` is the
    explicit frontier mask (value frontiers are not derivable from `val`
    alone), and the frontier payloads are NOT stored -- they equal the owned
    value at the frontier rows, which import re-reads.
    """
    R, C, S = grid.R, grid.C, grid.S
    val = np.full((grid.n,), I32_MAX, np.int32)
    in_front = np.zeros((grid.n,), bool)
    for i in range(R):
        for j in range(C):
            g0 = (j * R + i) * S
            val[g0:g0 + S] = st.val[i, j, j * S:(j + 1) * S]
            cnt = int(st.front_cnt[i, j])
            t = np.asarray(st.front[i, j, :cnt], np.int64) - i * S
            in_front[g0 + t] = True
    it = int(st.it[0, 0])
    return {"val": val[:n], "in_front": in_front[:n],
            "it": np.asarray(it, np.int64),
            "levels_done": np.asarray(it - 1, np.int64)}


def import_value_state(grid: Grid2D, snap: dict, pad: str = "max"
                       ) -> ValueState:
    """Global snapshot -> (R, C, ...) ValueState on `grid`.

    Every local row takes the authoritative global value: the owned block
    exactly, and remote rows get a send-suppression cache that is a SUPERSET
    of any organically-grown one -- suppressed proposals would have carried
    cand >= the owner's current value, invisible to the min-merge and the
    strict `changed` mask, so resumed trajectories stay bit-identical.

    pad: value for the new grid's padding vertices (>= the raw n): "max"
    (I32_MAX -- never-visited sentinel, SSSP/multi-BFS) or "gid" (own global
    id -- CC's converged self-label, what an uninterrupted run holds there
    after level 1).
    """
    R, C, S, nrl = grid.R, grid.C, grid.S, grid.n_rows_local
    n_raw = int(snap["val"].shape[0])
    gv = np.empty((grid.n,), np.int32)
    gv[:n_raw] = snap["val"]
    if pad == "gid":
        gv[n_raw:] = np.arange(n_raw, grid.n, dtype=np.int32)
    else:
        gv[n_raw:] = I32_MAX
    inf = np.zeros((grid.n,), bool)
    inf[:n_raw] = snap["in_front"]
    val = np.empty((R, C, nrl), np.int32)
    front = np.full((R, C, S), -1, np.int32)
    payload = np.full((R, C, S), I32_MAX, np.int32)
    cnt = np.zeros((R, C), np.int32)
    for i in range(R):
        vi = gv[rows_to_global(grid, i)]
        for j in range(C):
            val[i, j] = vi
            g0 = (j * R + i) * S
            t = np.flatnonzero(inf[g0:g0 + S]).astype(np.int32)
            front[i, j, :t.size] = i * S + t
            payload[i, j, :t.size] = gv[g0 + t]
            cnt[i, j] = t.size
    it = np.full((R, C), int(snap["it"]), np.int32)
    return ValueState(val=val, front=front, payload=payload, front_cnt=cnt,
                      it=it)
