"""BFS levels/preds as a `FrontierProgram` (DESIGN.md sec. 6 + 8).

This is the paper's algorithm -- expand exchange, CSC scan, fold, frontier
update, deferred-predecessor resolution -- expressed as ONE instance of the
generalized driver.  The monoid is first-visit-wins (the visited bitmap is
the suppression cache, the fold payload is the vertex set itself), which is
why plain set codecs suffice on the wire.  `repro.dist.engine.DistBFSEngine`
wraps this program to keep the historical constructor.

The frontier a level hands on is every owned row whose level equals that
level, as local cols, ascending (`frontier.compact_mask`).  Its order fixes
the next level's scan order, and so which parent wins each first-visit
race: levels and predecessors are bit-identical across fold codecs (whose
delivery orders differ) and direction mixes.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.algos.program import FrontierProgram, rows_to_global
from repro.core import frontier as F
from repro.core.types import Grid2D, LocalGraph2D, BFSState, BFSOutput
from repro.dist import exchange as X


# ----------------------------------------------------------------------------
# Level-loop building blocks (shared with the direction-optimised step)
# ----------------------------------------------------------------------------

def init_state(root, *, grid: Grid2D, i, j) -> BFSState:
    S = grid.S
    nrl = grid.n_rows_local
    b = root // S
    oi, oj = b % grid.R, b // grid.R
    mine = (oi == i) & (oj == j)
    lr = (root // S // grid.R) * S + root % S
    lc = root % grid.n_cols_local
    level = jnp.full((nrl,), -1, jnp.int32)
    pred = jnp.full((nrl,), -1, jnp.int32)
    visited = jnp.zeros((nrl,), bool)
    front = jnp.full((S,), -1, jnp.int32)
    level = jnp.where(mine, level.at[lr].set(0), level)
    pred = jnp.where(mine, pred.at[lr].set(root), pred)
    visited = jnp.where(mine, visited.at[lr].set(True), visited)
    front = jnp.where(mine, front.at[0].set(lc), front)
    cnt = jnp.where(mine, jnp.int32(1), jnp.int32(0))
    return BFSState(level=level, pred=pred, visited=visited, front=front,
                    front_cnt=cnt, lvl=jnp.int32(1))


def owned_level(level, *, grid: Grid2D, j):
    return jax.lax.dynamic_slice_in_dim(level, j * grid.S, grid.S)


def topdown_step(engine, graph: LocalGraph2D, st: BFSState, *, i, j):
    """One top-down level (paper Alg. 2 lines 12-18).

    Returns (state', total, scanned, aux); aux is the per-level telemetry
    channel (DESIGN.md sec. 13) -- a SET fold, so the wire stamp is the
    exchange strategy's scaling of the codec's static `wire_bytes(grid)`,
    `msgs` the strategy's per-exchange message count and `folded` the
    entries routed to remote owners (the own column never travels).
    """
    topo, grid = engine.topo, engine.grid
    S = grid.S

    with jax.named_scope("repro/expand"):
        # expand exchange: gather frontiers within the processor-column
        with jax.named_scope("exchange"):
            all_front, front_total = X.expand_exchange(
                st.front, st.front_cnt, topo=topo)

        # frontier expansion (local CSC column scan)
        ex = F.expand_frontier(
            graph.col_off, graph.row_idx, st.visited, st.level, st.pred,
            all_front, front_total, st.lvl, grid=grid, i=i, j=j,
            edge_chunk=engine.edge_chunk, dedup=engine.dedup,
            count_passes=engine.telemetry)

    with jax.named_scope("repro/fold"):
        # the own column never travels; the rest route to their owners
        dst = ex.dst.at[j].set(-1)
        dst_cnt = ex.dst_cnt.at[j].set(0)
        # fold exchange: route discoveries to their owners (same grid row)
        int_verts, int_cnt = engine.codec.fold(dst, dst_cnt, topo=topo, j=j)

    with jax.named_scope("repro/update"):
        # frontier update (paper sec. 3.5): the fold's winners join the
        # own column's, already marked in the scan; the next frontier is
        # every owned row at this level, ascending
        up = F.update_frontier(int_verts, int_cnt, ex.visited, ex.level,
                               ex.pred, st.lvl)
        nf, nc = F.compact_mask(
            owned_level(up.level, grid=grid, j=j) == st.lvl, i * S)
        st2 = BFSState(level=up.level, pred=up.pred, visited=up.visited,
                       front=nf, front_cnt=nc, lvl=st.lvl + 1)
    with jax.named_scope("repro/loop"):
        # the telemetry stamps (dead code unless telemetry is on) and the
        # global frontier total the loop's exit test reads
        ex_strat = engine.exchange
        aux = {"folded": dst_cnt.sum(dtype=jnp.int32),
               "wire": jnp.uint32(ex_strat.wire_bytes(
                   engine.codec.wire_bytes(grid), grid.C)),
               "msgs": jnp.int32(ex_strat.msgs_per_exchange(grid.C)),
               "dir": jnp.int32(0),
               "map_passes": ex.map_passes}
        total = topo.psum_all(nc)
    return st2, total, ex.edges_scanned, aux


# ----------------------------------------------------------------------------
# The program
# ----------------------------------------------------------------------------

class BFSLevelsProgram(FrontierProgram):
    """The paper's BFS (levels + deferred predecessors) on the driver.

    step_factory: optional `(engine, graph, extra, i, j, topdown) -> step`
                  hook replacing the default top-down per-level step (the
                  direction-optimising driver injects its `lax.cond` here).
    n_extra:      extra per-device graph arrays the step consumes (the CSR
                  twin for bottom-up).
    """
    name = "bfs"
    codec_hint = "list"

    def __init__(self, step_factory=None, n_extra: int = 0):
        self.step_factory = step_factory
        self.n_extra = n_extra

    @property
    def key(self) -> tuple:
        return (self.name, self.step_factory, self.n_extra)

    def init(self, engine, graph, extra, root, i, j):
        return init_state(root, grid=engine.grid, i=i, j=j)

    def make_step(self, engine, graph, extra, i, j):
        topdown = functools.partial(topdown_step, engine, graph, i=i, j=j)
        if self.step_factory is None:
            return lambda st, prev_total: topdown(st)
        return self.step_factory(engine, graph, extra, i, j, topdown)

    def make_bottomup_step(self, engine, graph, extra, i, j):
        from repro.algos.direction import make_bfs_bottomup_step
        return make_bfs_bottomup_step(engine, graph, extra, i, j)

    def keep_going(self, engine, st, total):
        return (total > 0) & (st.lvl <= engine.max_levels)

    def init_total(self, engine, st):
        return engine.topo.psum_all(st.front_cnt)

    def finalize(self, engine, st, i, j):
        pred = X.resolve_preds(st.pred, topo=engine.topo, j=j)
        level = owned_level(st.level, grid=engine.grid, j=j)
        return level, pred, st.lvl

    def out_specs(self, engine):
        out_g = engine.topo.out_block_spec
        return (out_g, out_g, engine.topo.dev_spec)

    def level_count(self, st):
        return st.lvl

    def export_state(self, engine, st, n: int) -> dict:
        """(R, C, ...) BFSState -> global canonical snapshot.

        `level` and `pred` export from the owned blocks; deferred
        predecessor markers -(c+2) resolve at export time by reading the
        sender column's pred row (the same fetch `resolve_preds` performs
        with an all_to_all at finalize), so the snapshot is marker-free and
        grid-independent.  The frontier is DERIVED state -- exactly the
        vertices with level == lvl-1 -- and the visited bitmap is derivable
        as level >= 0, so neither is stored.
        """
        grid = engine.grid
        R, C, S = grid.R, grid.C, grid.S
        gl = np.full((grid.n,), -1, np.int32)
        gp = np.full((grid.n,), -1, np.int32)
        for i in range(R):
            for j in range(C):
                g0 = (j * R + i) * S
                sl = slice(j * S, (j + 1) * S)
                gl[g0:g0 + S] = st.level[i, j, sl]
                pr = np.asarray(st.pred[i, j, sl]).copy()
                dm = pr < -1
                if dm.any():
                    snd = -pr[dm] - 2                 # the sender column
                    t = np.flatnonzero(dm)
                    pr[dm] = st.pred[i, snd, j * S + t]
                gp[g0:g0 + S] = pr
        lvl = int(st.lvl[0, 0])
        return {"level": gl[:n], "pred": gp[:n],
                "lvl": np.asarray(lvl, np.int64),
                "levels_done": np.asarray(lvl - 1, np.int64)}

    def import_state(self, engine, snap: dict) -> BFSState:
        """Global snapshot -> (R, C, ...) BFSState on engine's grid.

        Every local row rebuilds `level` and `visited = level >= 0` from the
        global truth: for still-unvisited vertices no device suppresses, and
        for claimed vertices extra suppression only drops proposals the
        owner's `eligible &= ~visited` would discard anyway -- so a resumed
        trajectory (same grid) is bit-identical, predecessors included.
        `pred` is authoritative at the owned block only (resolve_preds is
        idempotent on resolved entries); the frontier re-derives from
        level == lvl-1, ascending -- as every step builds it.
        """
        grid = engine.grid
        R, C, S, nrl = grid.R, grid.C, grid.S, grid.n_rows_local
        n_raw = int(snap["level"].shape[0])
        gl = np.full((grid.n,), -1, np.int32)
        gl[:n_raw] = snap["level"]
        gp = np.full((grid.n,), -1, np.int32)
        gp[:n_raw] = snap["pred"]
        lvl = int(snap["lvl"])
        level = np.empty((R, C, nrl), np.int32)
        visited = np.empty((R, C, nrl), bool)
        pred = np.full((R, C, nrl), -1, np.int32)
        front = np.full((R, C, S), -1, np.int32)
        cnt = np.zeros((R, C), np.int32)
        for i in range(R):
            li = gl[rows_to_global(grid, i)]
            for j in range(C):
                level[i, j] = li
                visited[i, j] = li >= 0
                g0 = (j * R + i) * S
                pred[i, j, j * S:(j + 1) * S] = gp[g0:g0 + S]
                t = np.flatnonzero(gl[g0:g0 + S] == lvl - 1).astype(np.int32)
                front[i, j, :t.size] = i * S + t
                cnt[i, j] = t.size
        return BFSState(level=level, pred=pred, visited=visited, front=front,
                        front_cnt=cnt, lvl=np.full((R, C), lvl, np.int32))

    def assemble(self, engine, outs, B) -> BFSOutput:
        """Gathered device outputs -> global BFSOutput.

        Scalar (B=None): (n,) level/pred in vertex-block order (b = j*R + i,
        i.e. plain global vertex ids) + the exact 64-bit scanned-edge count.
        Batched: (B, n) level/pred, (B,) n_levels, tuple of B counts.
        """
        from repro.algos.engine import wide_total

        level, pred, lvls, hi, lo = outs
        if B is None:
            return BFSOutput(level=level.reshape(-1), pred=pred.reshape(-1),
                             n_levels=lvls.max(),
                             edges_scanned=wide_total(hi, lo))
        Pn, S = engine.grid.P, engine.grid.S
        level = jnp.swapaxes(level.reshape(Pn, B, S), 0, 1).reshape(B, -1)
        pred = jnp.swapaxes(pred.reshape(Pn, B, S), 0, 1).reshape(B, -1)
        n_levels = lvls.reshape(-1, B).max(axis=0)
        hi_s = np.asarray(hi).astype(np.int64).reshape(-1, B).sum(axis=0)
        lo_s = np.asarray(lo).astype(np.int64).reshape(-1, B).sum(axis=0)
        scanned = tuple((int(h) << 32) + int(l) for h, l in zip(hi_s, lo_s))
        return BFSOutput(level=level, pred=pred, n_levels=n_levels,
                         edges_scanned=scanned)
