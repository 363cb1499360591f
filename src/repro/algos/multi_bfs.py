"""Batched multi-source BFS / reachability (DESIGN.md sec. 8).

ONE wave sweeps out from K sources simultaneously (Pan et al.'s frontier
loop with a source-id payload): every vertex records the level at which the
combined wave first reached it and the id (index into `sources`) of the
claiming source, with ties inside a wave broken by the minimum source id.
This is the k-hop-neighborhood primitive of the `models/gnn` stack -- run
with `max_levels=k` and `level >= 0` marks the union k-hop neighborhood of
the source set, `src` its nearest-source assignment.

Unlike `GraphSession.bfs(roots)` (K independent searches under `lax.map`),
the K sources here share a single frontier, so the whole sweep costs one
traversal of the reachable region.

The monoid is first-wave-wins with min-source-id inside a wave; like BFS,
a per-device visited bitmap over ALL local rows suppresses re-folds, and
the fold carries (vertex, source id) pairs via `FoldCodec.fold_values` --
bit-identical across wire codecs.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.algos import program as PR
from repro.algos.program import FrontierProgram, I32_MAX
from repro.core.types import _dc
from repro.dist import exchange as X


@_dc
@dataclasses.dataclass
class MultiBFSState:
    """Per-device multi-source BFS state.

    `visited` spans ALL local rows (the BFS suppression bitmap: each remote
    vertex is folded at most once per sweep); `level`/`src` are
    authoritative for the owned block only.
    """
    visited: jax.Array    # (n_rows_local,) bool
    level: jax.Array      # (n_rows_local,) int32, -1 = unreached
    src: jax.Array        # (n_rows_local,) int32 claiming source id
    front: jax.Array      # (S,) local col ids, canonical ascending
    payload: jax.Array    # (S,) source ids aligned with front
    front_cnt: jax.Array  # () int32
    lvl: jax.Array        # () int32 current wave


@_dc
@dataclasses.dataclass
class MultiBFSOutput:
    """Global multi-source BFS result."""
    level: jax.Array       # (n,) int32 hops to the nearest source, -1 = none
    src: jax.Array         # (n,) int32 claiming source id (index into
                           #   sources), -1 = unreached
    n_levels: jax.Array    # waves run
    edges_scanned: Any = None  # exact Python int (64-bit safe)
    directions: Any = None     # per-level direction trace when direction
                               # optimisation ran (see BFSOutput), else None
    trace: Any = None          # LevelTrace when telemetry ran, else None


class MultiSourceBFSProgram(FrontierProgram):
    """Simultaneous BFS from a (K,) sources vector (arg = sources)."""
    name = "multi_bfs"
    codec_hint = "list"

    def init(self, engine, graph, extra, sources, i, j):
        grid = engine.grid
        S, nrl, R = grid.S, grid.n_rows_local, grid.R
        K = sources.shape[0]
        b = sources // S
        mine = (b % R == i) & (b // R == j) & (sources >= 0)
        lr = (sources // S // R) * S + sources % S
        idx = jnp.arange(K, dtype=jnp.int32)
        # min source id per claimed row (duplicate sources: first index wins)
        src = jnp.full((nrl,), I32_MAX, jnp.int32).at[
            jnp.where(mine, lr, nrl)].min(jnp.where(mine, idx, I32_MAX),
                                          mode="drop")
        claimed = src < I32_MAX
        level = jnp.where(claimed, 0, -1).astype(jnp.int32)
        owned_src = jax.lax.dynamic_slice_in_dim(src, j * S, S)
        front, payload, cnt = PR.owned_to_front(owned_src < I32_MAX,
                                                owned_src, i, S,
                                                ops=engine.fold_ops)
        return MultiBFSState(visited=claimed, level=level, src=src,
                             front=front, payload=payload, front_cnt=cnt,
                             lvl=jnp.int32(1))

    def make_step(self, engine, graph, extra, i, j):
        return self._make_step(engine, graph, i, j)

    def make_bottomup_step(self, engine, graph, extra, i, j):
        # the pull twin additionally masks visited rows out of the workload:
        # their candidates are discarded by the visited discipline below
        # anyway, so skipping their in-edges changes nothing but the work
        from repro.algos.direction import make_pull_scan
        scan = make_pull_scan(engine, extra[-2], extra[-1], i, j,
                              relax=lambda p, w: p,
                              row_mask_fn=lambda st: ~st.visited)
        return self._make_step(engine, graph, i, j, scan=scan)

    def _make_step(self, engine, graph, i, j, scan=None):
        grid, topo = engine.grid, engine.topo
        S, nrl = grid.S, grid.n_rows_local
        fold_ops = engine.fold_ops
        step_dir = jnp.int32(1 if scan is not None else 0)
        wire_base = jnp.uint32(engine.codec.wire_bytes(grid))

        def step(st: MultiBFSState, prev_total):
            with jax.named_scope("repro/expand"):
                if scan is not None:
                    with jax.named_scope("bottomup"):
                        cand, scanned = scan(st)
                else:
                    with jax.named_scope("exchange"):
                        all_front, all_pay, ftot = X.expand_exchange_values(
                            st.front, st.front_cnt, st.payload, topo=topo,
                            fill=I32_MAX, ops=fold_ops)
                    cand, scanned = PR.scan_relax(
                        graph.col_off, graph.row_idx, None, all_front,
                        all_pay, ftot, lambda p, w: p, n_rows=nrl,
                        edge_chunk=engine.edge_chunk,
                        expand_fn=engine.value_expand_fn)
            with jax.named_scope("repro/update"):
                # first fold per vertex per device (the BFS visited
                # discipline)
                improved = (cand < I32_MAX) & ~st.visited
                vis1 = st.visited | improved
            with jax.named_scope("repro/fold"):
                ids, cnt, vals = PR.pack_blocks(improved, cand, grid,
                                                ops=fold_ops)
                ri, rc, rv = engine.codec.fold_values(ids, cnt, vals,
                                                      topo=topo, j=j)
            with jax.named_scope("repro/update"):
                inc = PR.scatter_min_received(ri, rv, j, S)
                # claims merge against the PRE-scan owned state: this
                # device's own discoveries travel through the self
                # all_to_all block, so judging them here would shadow a
                # smaller source id arriving from a peer in the same wave
                vis_owned_prev = jax.lax.dynamic_slice_in_dim(st.visited,
                                                              j * S, S)
                changed = (inc < I32_MAX) & ~vis_owned_prev
                src_prev = jax.lax.dynamic_slice_in_dim(st.src, j * S, S)
                lvl_prev = jax.lax.dynamic_slice_in_dim(st.level, j * S, S)
                new_src = jnp.where(changed, inc, src_prev)
                new_lvl = jnp.where(changed, st.lvl, lvl_prev)
                src2 = jax.lax.dynamic_update_slice(st.src, new_src,
                                                    (j * S,))
                lvl2 = jax.lax.dynamic_update_slice(st.level, new_lvl,
                                                    (j * S,))
                vis_owned = jax.lax.dynamic_slice_in_dim(vis1, j * S, S)
                vis2 = jax.lax.dynamic_update_slice(
                    vis1, vis_owned | changed, (j * S,))
                front, payload, nc = PR.owned_to_front(changed, new_src, i,
                                                       S, ops=fold_ops)
                st2 = MultiBFSState(visited=vis2, level=lvl2, src=src2,
                                    front=front, payload=payload,
                                    front_cnt=nc, lvl=st.lvl + 1)
            with jax.named_scope("repro/loop"):
                # per-level telemetry channel: value folds ship 4 extra
                # payload bytes per folded entry on top of the codec's
                # static frame
                folded = cnt.sum(dtype=jnp.int32)
                aux = {"folded": folded,
                       "wire": wire_base + 4 * folded.astype(jnp.uint32),
                       "dir": step_dir}
                return st2, topo.psum_all(nc), scanned, aux

        return step

    def keep_going(self, engine, st, total):
        return (total > 0) & (st.lvl <= engine.max_levels)

    def init_total(self, engine, st):
        return engine.topo.psum_all(st.front_cnt)

    def finalize(self, engine, st, i, j):
        S = engine.grid.S
        level = jax.lax.dynamic_slice_in_dim(st.level, j * S, S)
        src = jax.lax.dynamic_slice_in_dim(st.src, j * S, S)
        return level, jnp.where(src == I32_MAX, -1, src), st.lvl

    def out_specs(self, engine):
        out_g = engine.topo.out_block_spec
        return (out_g, out_g, engine.topo.dev_spec)

    def level_count(self, st):
        return st.lvl

    def export_state(self, engine, st, n: int) -> dict:
        """(R, C, ...) MultiBFSState -> global canonical snapshot.

        `level`/`src` export RAW from the owned blocks (src keeps I32_MAX for
        unclaimed vertices; finalize's -1 remap is output-only).  The
        frontier derives from level == lvl-1 with the claiming source id as
        payload, and per-device `visited` is rebuilt as level >= 0 -- a
        superset of any one device's organic bitmap, which only suppresses
        proposals for already-claimed vertices (invisible to the owner's
        `~vis_owned_prev` merge), so a same-grid resume is bit-identical.
        """
        grid = engine.grid
        R, C, S = grid.R, grid.C, grid.S
        gl = np.full((grid.n,), -1, np.int32)
        gs = np.full((grid.n,), I32_MAX, np.int32)
        for i in range(R):
            for j in range(C):
                g0 = (j * R + i) * S
                sl = slice(j * S, (j + 1) * S)
                gl[g0:g0 + S] = st.level[i, j, sl]
                gs[g0:g0 + S] = st.src[i, j, sl]
        lvl = int(st.lvl[0, 0])
        return {"level": gl[:n], "src": gs[:n],
                "lvl": np.asarray(lvl, np.int64),
                "levels_done": np.asarray(lvl - 1, np.int64)}

    def import_state(self, engine, snap: dict) -> MultiBFSState:
        """Global snapshot -> (R, C, ...) MultiBFSState on engine's grid.

        `level`/`src` are authoritative at the owned block only (steps never
        read the non-owned rows after init, so those import as -1/I32_MAX);
        padding vertices of the new grid are unreached.
        """
        grid = engine.grid
        R, C, S, nrl = grid.R, grid.C, grid.S, grid.n_rows_local
        n_raw = int(snap["level"].shape[0])
        gl = np.full((grid.n,), -1, np.int32)
        gl[:n_raw] = snap["level"]
        gs = np.full((grid.n,), I32_MAX, np.int32)
        gs[:n_raw] = snap["src"]
        lvl = int(snap["lvl"])
        visited = np.empty((R, C, nrl), bool)
        level = np.full((R, C, nrl), -1, np.int32)
        src = np.full((R, C, nrl), I32_MAX, np.int32)
        front = np.full((R, C, S), -1, np.int32)
        payload = np.full((R, C, S), I32_MAX, np.int32)
        cnt = np.zeros((R, C), np.int32)
        for i in range(R):
            li = gl[PR.rows_to_global(grid, i)]
            for j in range(C):
                visited[i, j] = li >= 0
                g0 = (j * R + i) * S
                sl = slice(j * S, (j + 1) * S)
                level[i, j, sl] = gl[g0:g0 + S]
                src[i, j, sl] = gs[g0:g0 + S]
                t = np.flatnonzero(gl[g0:g0 + S] == lvl - 1).astype(np.int32)
                front[i, j, :t.size] = i * S + t
                payload[i, j, :t.size] = gs[g0 + t]
                cnt[i, j] = t.size
        return MultiBFSState(visited=visited, level=level, src=src,
                             front=front, payload=payload, front_cnt=cnt,
                             lvl=np.full((R, C), lvl, np.int32))

    def assemble(self, engine, outs, B) -> MultiBFSOutput:
        from repro.algos.engine import wide_total

        level, src, lvls, hi, lo = outs
        return MultiBFSOutput(level=level.reshape(-1), src=src.reshape(-1),
                              n_levels=lvls.max(),
                              edges_scanned=wide_total(hi, lo))
