"""Direction-optimised traversal as a first-class frontier-program mode
(DESIGN.md sec. 11; Beamer et al., Buluc & Madduri 1104.4518).

`DirectionProgram` wraps ANY `FrontierProgram` whose per-level step has a
bottom-up twin (`make_bottomup_step`): instead of scanning the frontier's
out-edges (CSC), every unvisited/active vertex scans its own in-edges (the
CSR twin) for a parent in the frontier -- the win on dense levels, where the
frontier touches most edges but almost every candidate is already settled.
The per-level choice runs INSIDE the compiled `lax.while_loop` as a
`lax.cond` on the global frontier total the engine already threads through
every step, so an adaptive search traces exactly once.

Heuristic (the alpha/beta hysteresis of Beamer's hybrid): go bottom-up when
the global frontier exceeds n/alpha, return top-down once it falls below
n/beta (beta > alpha, so the exit threshold sits under the entry threshold
and a frontier hovering at the boundary does not thrash).  `mode="bottomup"`
pins every level bottom-up instead (the benchmark sweep's fixed arm).

Bit-identity (the repo-wide contract): for BFS the bottom-up merge gives the
owner's own column block priority and otherwise takes the minimum sender
column, each contributing its minimum frontier-neighbour column -- exactly
the winner the top-down visited-suppression + ascending scan order elects,
so levels, preds and n_levels match top-down bit for bit at ANY per-level
direction mix.  Both steps hand on the same frontier: the owned rows with
level == lvl, as local cols, ascending.  For the value programs the pull
scan proposes the same relaxed-value multiset per row (CSR and CSC hold the
same local edges), and the min-monoid combine is order-independent.
`edges_scanned` is the honest per-direction work (bottom-up scans unvisited
rows' in-edges), so it legitimately differs from top-down -- Graph500 TEPS
stays input-edge-based.

The frontier travels to the bottom-up scan as the BITMAP the fold codecs
already know how to pack (`frontier.pack_bitmap`), row-gathered in a blocked
layout (`frontier.test_bit_blocks`); discoveries return to their owners
through the regular `FoldCodec.fold_values` exchange, so every codec works
both directions.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.algos import program as PR
from repro.algos.program import FrontierProgram, I32_MAX
from repro.core import frontier as F
from repro.core.types import _dc
from repro.obs.trace import RECORDED_LEVELS


# ----------------------------------------------------------------------------
# State: the wrapped program's state plus the direction trace
# ----------------------------------------------------------------------------

@_dc
@dataclasses.dataclass
class DirState:
    """Wrapped program state + per-level direction bookkeeping."""
    inner: Any            # the wrapped program's state pytree
    dir: jax.Array        # () int32: 1 while running bottom-up (hysteresis)
    dirs: jax.Array       # (RECORDED_LEVELS,) int32: -1 unused / 0 TD /
                          # 1 BU; levels past it fold into the last slot
    k: jax.Array          # () int32 0-based level counter


# ----------------------------------------------------------------------------
# Frontier bitmap + pull-scan building blocks
# ----------------------------------------------------------------------------

def frontier_words(topo, front, i):
    """Own (S,) frontier col ids -> row-gathered blocked bitmap (R*W,).

    Device (i, j)'s frontier entries always lie in [i*S, (i+1)*S) (ROW2COL
    of owned rows), so the own block packs to exactly S bits; the gather
    stacks grid-row r's words at block r -- matching `test_bit_blocks`'s
    blocked addressing of local col c (block c // S, bit c % S)."""
    S = topo.grid.S
    fvalid = front >= 0
    t = jnp.where(fvalid, front - i * S, S)
    own_mask = jnp.zeros((S,), bool).at[t].set(True, mode="drop")
    return topo.row_gather(F.pack_bitmap(own_mask)).reshape(-1)


def make_pull_scan(engine, row_off, col_idx, i, j, *, relax,
                   csr_edge_vals=None, row_mask_fn=None):
    """Bottom-up twin of the `scan_relax` prefix of a value-program step.

    Pulls: every (row-mask selected) local row scans its CSR in-edges; an
    edge from frontier col c proposes `relax(dense_payload[c], w)`, min-
    combined per row.  CSR and CSC hold the same local edge multiset and the
    combine is order-independent, so the candidate array is bit-identical to
    the top-down push scan on every row the mask keeps.

    row_mask_fn: optional state -> (n_rows_local,) bool; rows masked out
    contribute no edges to the workload (multi-source BFS skips visited
    rows -- their candidates are discarded downstream anyway).
    Returns scan(state) -> (cand (n_rows_local,), edges_scanned uint32).
    """
    topo, grid = engine.topo, engine.grid
    S = grid.S
    nrl, ncl = grid.n_rows_local, grid.n_cols_local
    chunk = engine.edge_chunk

    def scan(st):
        fvalid = st.front >= 0
        t = jnp.where(fvalid, st.front - i * S, S)
        own_pay = jnp.zeros((S,), jnp.int32).at[t].set(
            jnp.where(fvalid, st.payload, 0), mode="drop")
        all_words = frontier_words(topo, st.front, i)
        dense_pay = topo.row_gather(own_pay).reshape(ncl)
        deg = jnp.diff(row_off)
        if row_mask_fn is not None:
            deg = jnp.where(row_mask_fn(st), deg, 0)
        cumul = F.exclusive_cumsum(deg)
        total = cumul[nrl]

        def chunk_body(state):
            start, cand = state
            gids = start + jnp.arange(chunk, dtype=jnp.int32)
            r, pay, addr, hit = F.reference_bottomup_values_chunk(
                gids, cumul, total, row_off, col_idx, all_words, dense_pay,
                block=S)
            w = None if csr_edge_vals is None else csr_edge_vals[addr]
            val = jnp.where(hit, relax(pay, w), I32_MAX)
            cand = cand.at[jnp.where(hit, r, nrl)].min(val, mode="drop")
            return start + chunk, cand

        _, cand = jax.lax.while_loop(
            lambda s: s[0] < total, chunk_body,
            (jnp.int32(0), jnp.full((nrl,), I32_MAX, jnp.int32)))
        return cand, total.astype(jnp.uint32)

    return scan


# ----------------------------------------------------------------------------
# The BFS bottom-up step
# ----------------------------------------------------------------------------

def make_bfs_bottomup_step(engine, graph, extra, i, j):
    """One bottom-up BFS level, bit-identical to `bfs.topdown_step`.

    Every unvisited local row (the masked-degree workload) scans its CSR
    in-edges for a frontier parent; the per-row minimum frontier col is this
    device's proposal, value-folded to the owner; the owner merges with
    own-column priority then minimum sender -- exactly the parent top-down's
    visited suppression + min-slot dedup elects (see module docstring).
    """
    from repro.core.types import BFSState

    row_off, col_idx = extra[-2], extra[-1]
    topo, grid = engine.topo, engine.grid
    S, C = grid.S, grid.C
    nrl, ncl = grid.n_rows_local, grid.n_cols_local
    chunk = engine.edge_chunk
    snd = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[:, None], (C, S))

    def step(st: BFSState, prev_total):
        with jax.named_scope("repro/expand"), jax.named_scope("bottomup"):
            all_words = frontier_words(topo, st.front, i)
            # masked-degree workload: only unvisited rows' in-edges are
            # scanned (the visited cache is consistent across the
            # processor-row, so these are exactly the globally-undiscovered
            # rows of this block)
            deg = jnp.where(~st.visited, jnp.diff(row_off), 0)
            cumul = F.exclusive_cumsum(deg)
            total = cumul[nrl]

            def chunk_body(state):
                start, best = state
                gids = start + jnp.arange(chunk, dtype=jnp.int32)
                r, c, hit = F.reference_bottomup_chunk(
                    gids, cumul, total, row_off, col_idx, all_words, block=S)
                best = best.at[jnp.where(hit, r, nrl)].min(
                    jnp.where(hit, c, I32_MAX), mode="drop")
                return start + chunk, best

            _, best = jax.lax.while_loop(
                lambda s: s[0] < total, chunk_body,
                (jnp.int32(0), jnp.full((nrl,), I32_MAX, jnp.int32)))

        with jax.named_scope("repro/update"):
            found = best < I32_MAX             # rows with a frontier parent
            visited1 = st.visited | found      # the send-suppression cache
            parent_g = jnp.where(found, j * ncl + best, I32_MAX)

        # value-fold (vertex, encoded parent) to the owners -- the same
        # exchange the value programs use, so every codec works here
        with jax.named_scope("repro/fold"):
            ids, cnt, vals = PR.pack_blocks(found, parent_g, grid)
            ri, rc, rv = engine.codec.fold_values(ids, cnt, vals, topo=topo,
                                                  j=j)

        with jax.named_scope("repro/update"):
            # dense (C, S) per-sender parent table of my owned block (dump
            # col S swallows the pads; senders propose each row at most
            # once)
            tt = jnp.where(ri >= 0, ri - j * S, S)
            dense = jnp.full((C, S + 1), I32_MAX, jnp.int32).at[
                snd.reshape(-1), tt.reshape(-1)].min(
                jnp.where(ri >= 0, rv, I32_MAX).reshape(-1))[:, :S]
            has = dense < I32_MAX
            own_row = jnp.take(dense, j, axis=0)
            own_has = own_row < I32_MAX
            first_m = jnp.min(jnp.where(has, snd, C), axis=0)   # min sender
            sel = jnp.where(own_has, j, jnp.clip(first_m, 0, C - 1))
            parent = jnp.take_along_axis(dense, sel[None, :], axis=0)[0]
            newly = own_has | (first_m < C)

            rows_owned = j * S + jnp.arange(S, dtype=jnp.int32)
            vis_owned_prev = jax.lax.dynamic_slice_in_dim(st.visited, j * S,
                                                          S)
            new = newly & ~vis_owned_prev
            tgt = jnp.where(new, rows_owned, nrl)
            visited2 = visited1.at[tgt].set(True, mode="drop")
            level2 = st.level.at[tgt].set(jnp.where(new, st.lvl, 0),
                                          mode="drop")
            pred2 = st.pred.at[tgt].set(jnp.where(new, parent, 0),
                                        mode="drop")

            # the next frontier: this level's owned discoveries, ascending
            nf, nc = F.compact_mask(new, i * S)
            st2 = BFSState(level=level2, pred=pred2, visited=visited2,
                           front=nf, front_cnt=nc, lvl=st.lvl + 1)
        with jax.named_scope("repro/loop"):
            # value fold: count-proportional
            folded = cnt.sum(dtype=jnp.int32)
            ex_strat = engine.exchange
            aux = {"folded": folded,
                   "wire": jnp.uint32(ex_strat.wire_bytes(
                       engine.codec.wire_bytes(grid), grid.C))
                   + ex_strat.value_extra_bytes(cnt, j, grid.C),
                   "msgs": jnp.int32(ex_strat.msgs_per_exchange(grid.C)),
                   "dir": jnp.int32(1),
                   "map_passes": jnp.int32(0)}
            return st2, topo.psum_all(nc), total.astype(jnp.uint32), aux

    return step


# ----------------------------------------------------------------------------
# The wrapper program
# ----------------------------------------------------------------------------

class DirectionProgram(FrontierProgram):
    """Direction-optimised wrapper around any bottom-up-capable program.

    mode:  "adaptive" (alpha/beta hysteresis per level) or "bottomup"
           (every level bottom-up -- the benchmark sweep's fixed arm).
    alpha: enter bottom-up when the global frontier exceeds n / alpha.
    beta:  leave it once the frontier falls below n / beta (beta > alpha).

    Outputs are the wrapped program's, bit-identical to its pure top-down
    run, plus a `directions` trace ((RECORDED_LEVELS,) int32 per search:
    -1 unused level / 0 top-down / 1 bottom-up; whatever the loop's bound,
    levels past the record fold into its last slot).
    """

    def __init__(self, inner: FrontierProgram, *, mode: str = "adaptive",
                 alpha: int = 24, beta: int = 64):
        if mode not in ("adaptive", "bottomup"):
            raise ValueError(
                f"mode={mode!r}: expected 'adaptive' or 'bottomup'")
        self.inner = inner
        self.mode = mode
        self.alpha = int(alpha)
        self.beta = int(beta)
        self.name = "dir+" + inner.name
        self.codec_hint = inner.codec_hint
        # inner extras first, then the CSR twin (row_off, col_idx[, w_csr])
        self.n_extra = inner.n_extra + inner.n_csr_extra

    @property
    def key(self) -> tuple:
        return ("dir",) + tuple(self.inner.key) + (self.mode, self.alpha,
                                                   self.beta)

    def init(self, engine, graph, extra, arg, i, j):
        inner_st = self.inner.init(engine, graph,
                                   extra[:self.inner.n_extra], arg, i, j)
        dirs = jnp.full((RECORDED_LEVELS,), -1, jnp.int32)
        return DirState(inner=inner_st, dir=jnp.int32(0), dirs=dirs,
                        k=jnp.int32(0))

    def make_step(self, engine, graph, extra, i, j):
        td = self.inner.make_step(engine, graph,
                                  extra[:self.inner.n_extra], i, j)
        bu = self.inner.make_bottomup_step(engine, graph, extra, i, j)
        n = engine.grid.n
        L = RECORDED_LEVELS
        hi_thr = jnp.int32(n // self.alpha)   # enter bottom-up above this
        lo_thr = jnp.int32(n // self.beta)    # leave it below this

        def step(st: DirState, prev_total):
            # the switch's predicate and bookkeeping are loop control; the
            # `lax.cond` itself runs under no scope, so each branch's
            # instructions keep the step's own layer scopes
            if self.mode == "bottomup":
                use_bu = jnp.bool_(True)
                inner2, total, scanned, aux = bu(st.inner, prev_total)
            else:
                with jax.named_scope("repro/loop"):
                    use_bu = jnp.where(st.dir == 1, prev_total > lo_thr,
                                       prev_total > hi_thr)
                # both branches return (state, total, scanned, aux) with
                # identical aux structure, so telemetry rides the cond
                inner2, total, scanned, aux = jax.lax.cond(
                    use_bu, lambda s: bu(s, prev_total),
                    lambda s: td(s, prev_total), st.inner)
            with jax.named_scope("repro/loop"):
                dirs = st.dirs.at[jnp.minimum(st.k, L - 1)].set(
                    use_bu.astype(jnp.int32))
                st2 = DirState(inner=inner2, dir=use_bu.astype(jnp.int32),
                               dirs=dirs, k=st.k + 1)
            return st2, total, scanned, aux

        return step

    def front_count(self, st):
        return self.inner.front_count(st.inner)

    def keep_going(self, engine, st, total):
        return self.inner.keep_going(engine, st.inner, total)

    def init_total(self, engine, st):
        return self.inner.init_total(engine, st.inner)

    def finalize(self, engine, st, i, j):
        return tuple(self.inner.finalize(engine, st.inner, i, j)) + (st.dirs,)

    def out_specs(self, engine):
        return tuple(self.inner.out_specs(engine)) + (engine.topo.dev_spec,)

    def level_count(self, st):
        return self.inner.level_count(st.inner)

    def export_state(self, engine, st, n: int) -> dict:
        """Inner snapshot nested under "inner" + the direction bookkeeping
        (replicated across devices, so device (0, 0) is authoritative)."""
        import numpy as np

        snap = {"inner": self.inner.export_state(engine, st.inner, n),
                "dir": np.asarray(int(st.dir[0, 0]), np.int32),
                "dirs": np.asarray(st.dirs[0, 0], np.int32),
                "k": np.asarray(int(st.k[0, 0]), np.int32)}
        snap["levels_done"] = snap["inner"]["levels_done"]
        return snap

    def import_state(self, engine, snap: dict) -> DirState:
        import numpy as np

        grid = engine.grid
        R, C, L = grid.R, grid.C, RECORDED_LEVELS
        dirs = np.full((L,), -1, np.int32)
        src = np.asarray(snap["dirs"], np.int32)
        m = min(L, src.shape[0])
        dirs[:m] = src[:m]
        return DirState(
            inner=self.inner.import_state(engine, snap["inner"]),
            dir=np.full((R, C), int(snap["dir"]), np.int32),
            dirs=np.broadcast_to(dirs, (R, C, L)).copy(),
            k=np.full((R, C), int(snap["k"]), np.int32))

    def assemble(self, engine, outs, B):
        # engine appends (hi, lo) after finalize's outputs, so the direction
        # trace sits third from the end
        inner_outs = tuple(outs[:-3]) + tuple(outs[-2:])
        out = self.inner.assemble(engine, inner_outs, B)
        L = RECORDED_LEVELS
        dirs = outs[-3]
        # every device records the identical (psum-replicated) decision
        directions = dirs.reshape(-1, L)[0] if B is None \
            else dirs.reshape(-1, B, L)[0]
        return dataclasses.replace(out, directions=directions)
