"""The generalized frontier-program driver (DESIGN.md sec. 8).

`FrontierEngine` is the `lax.while_loop` level loop extracted from the BFS
engine: init -> loop(step until converged) -> finalize, compiled ONCE per
(program, topology) as a single shard_map'd device program, with the same
64-bit (hi, lo)-uint32 edge accounting and the same scalar/batched (`lax.map`
over a leading arg axis) entry points the BFS engine always had.  What the
loop computes is a `FrontierProgram` (repro.algos.program): BFS levels/preds
is ONE instance (repro.algos.bfs); connected components, SSSP and
multi-source BFS are others.

Buluc & Madduri cast the BFS level loop as a semiring matrix-vector product
over the 2D partition; this module is that observation as code -- the
partition, the expand/fold collectives and the wire codecs are
algorithm-agnostic, only the per-vertex state monoid and the per-level step
change.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.types import LocalGraph2D

# NOTE: no module-level repro.dist imports here.  `repro.dist.engine` imports
# this module, and `repro.dist/__init__` imports `repro.dist.engine`, so a
# top-level `from repro.dist import ...` would re-enter a partially
# initialized package whenever repro.algos is imported first.  The one
# runtime dependency (the fold-codec registry) is imported inside __init__.


# ----------------------------------------------------------------------------
# Wide (64-bit) accumulation without jax_enable_x64
# ----------------------------------------------------------------------------

def wide_add(hi, lo, delta):
    """(hi, lo) uint32 pair += delta (any non-negative integer dtype)."""
    new_lo = lo + delta.astype(jnp.uint32)
    return hi + (new_lo < lo).astype(jnp.uint32), new_lo


def wide_total(hi, lo) -> int:
    """Sum per-device (hi, lo) pairs into one exact Python int."""
    hi = np.asarray(hi).astype(np.int64)
    lo = np.asarray(lo).astype(np.int64)
    return (int(hi.sum()) << 32) + int(lo.sum())


# ----------------------------------------------------------------------------
# The driver
# ----------------------------------------------------------------------------

class FrontierEngine:
    """Whole-search program for one `FrontierProgram` over a Topology.

    Parameters
    ----------
    topo:       Topology binding the processor grid to mesh axes.
    program:    the FrontierProgram to drive.
    fold_codec: "list" | "bitmap" | "delta" | FoldCodec instance | None
                (None defers to `program.codec_hint`).
    edge_chunk: CSC scan chunk size of the expand phase.
    max_levels: loop bound fed to `program.keep_going`; None bounds it by
                the grid's n + 1, which no search reaches, so the loop
                runs to completion.  The per-level records (telemetry,
                directions) hold `repro.obs.trace.RECORDED_LEVELS` levels
                whatever the bound.
    dedup:      winner-selection method for set-valued folds.
    exchange:   fold exchange strategy: "flat" (one all_to_all per fold) |
                "butterfly" (log2(C) pairwise ppermute stages over the XOR
                hypercube) | "auto" (butterfly when it strictly reduces
                message count: power-of-two C >= 4 on a single column
                axis) | an ExchangeStrategy instance (DESIGN.md sec. 14).
                The resolved strategy is bound into the engine's topology,
                so every codec and the predecessor resolution route through
                it; outputs are bit-identical across strategies.
    telemetry:  when True, thread the per-level `repro.obs.trace` carry
                through the while_loop and return a `LevelTrace` with every
                search (DESIGN.md sec. 13).  Off by default; the flag is
                part of every engine/AOT cache key, so the off path
                compiles to exactly the untraced program.  Outputs are
                bit-identical either way.
    fault_tolerance:  when True, ALSO build the segmented level loop
                (DESIGN.md sec. 15): three extra jitted programs
                (`ft_start` / `ft_segment` / `ft_finish`) that run at most
                `ckpt_every` levels per call and hand the loop carry back
                to the host between segments, so a traversal can be
                checkpointed, interrupted and resumed mid-flight.  Off by
                default; the flags key every engine/AOT cache, the regular
                single-while_loop programs are built IDENTICALLY either
                way, and segmented outputs are bit-identical to them.
    ckpt_every: levels per resumable segment (the K of "checkpoint every
                K levels"); only consulted when fault_tolerance=True.
    """

    def __init__(self, topo, program, *, fold_codec=None,
                 edge_chunk: int = 8192, max_levels: int | None = None,
                 dedup: str = "scatter", exchange="flat",
                 telemetry: bool = False, fault_tolerance: bool = False,
                 ckpt_every: int = 1):
        from repro.dist.exchange import get_fold_codec
        from repro.dist.strategy import get_exchange

        # resolve + validate the exchange strategy and bind it into the
        # topology: codecs and resolve_preds call topo.col_all_to_all and
        # pick the route up without knowing strategies exist
        self.exchange = get_exchange(exchange, topo.grid, topo.col_axes)
        if topo.exchange is not self.exchange:
            topo = topo.with_exchange(self.exchange)
        self.topo = topo
        self.grid = topo.grid
        self.program = program
        self.edge_chunk = edge_chunk
        self.max_levels = self.grid.n + 1 if max_levels is None \
            else int(max_levels)
        spec = fold_codec if fold_codec is not None else program.codec_hint
        self.codec = get_fold_codec(spec, topo.grid)
        self.dedup = dedup
        self.telemetry = bool(telemetry)
        self.fault_tolerance = bool(fault_tolerance)
        self.ckpt_every = max(1, int(ckpt_every))
        # segmented programs, built lazily and ONLY when fault_tolerance=True
        # -- an off-path engine never constructs (or traces) them, which is
        # the no-retrace guarantee tests assert
        self._ft_progs = {}
        # traces of the level loop (scalar or batched); jit/AOT cache hits do
        # not retrace, so tests can assert a 64-root sweep compiles once
        self.trace_count = 0
        self._run = jax.jit(self._build())
        self._run_batch = jax.jit(self._build(batched=True))

    # -- whole-search program (lax.while_loop over levels) -------------------
    def _build(self, batched: bool = False):
        """Device program for one search arg (scalar) or a leading arg axis.

        The batched program runs the whole level loop per arg under
        `lax.map` (a scan: per-search work stays proportional to that
        search's levels, unlike vmap which would pad every search to the
        slowest), so a multi-root sweep is ONE compiled executable.
        """
        topo, prog = self.topo, self.program
        telemetry = self.telemetry
        from repro.obs import trace as T

        def device_fn(col_off, row_idx, nnz, *rest):
            extra, arg = rest[:-1], rest[-1]
            with jax.named_scope("repro/loop"):
                graph = LocalGraph2D(col_off=col_off[0, 0],
                                     row_idx=row_idx[0, 0], nnz=nnz[0, 0])
                extra = tuple(e[0, 0] for e in extra)
                i, j = topo.device_coords()

            # One layer scope per instruction (DESIGN.md sec. 13.4): the level
            # loop's own control runs under `repro/loop`, the step under the
            # scopes the program opens, and the `while_loop` / `lax.map`
            # calls themselves under none, so no layer scope encloses
            # another.
            def search(a):
                with jax.named_scope("repro/loop"):
                    st = prog.init(self, graph, extra, a, i, j)
                    step = prog.make_step(self, graph, extra, i, j)
                    init_total = prog.init_total(self, st)

                def cond(carry):
                    st, total = carry[0], carry[1]
                    with jax.named_scope("repro/loop"):
                        return prog.keep_going(self, st, total)

                def run_step(st, total):
                    # steps return (st', total, scanned[, aux]); aux is the
                    # per-level telemetry channel (folded / wire / dir).
                    # Untraced engines drop it right here, so XLA dead-code
                    # eliminates the aux reductions and the off path
                    # compiles to exactly the pre-telemetry program.
                    res = step(st, total)
                    aux = res[3] if len(res) > 3 else None
                    return res[0], res[1], res[2], aux

                def body(carry):
                    st, total, hi, lo = carry[:4]
                    st2, total2, scanned, aux = run_step(st, total)
                    with jax.named_scope("repro/loop"):
                        hi, lo = wide_add(hi, lo, scanned)
                        if not telemetry:
                            return st2, total2, hi, lo
                        tr = T.record_level(
                            carry[4], frontier=total,
                            front_dev=prog.front_count(st), scanned=scanned,
                            aux=T.normalize_aux(aux))
                    return st2, total2, hi, lo, tr

                with jax.named_scope("repro/loop"):
                    carry = (st, init_total, jnp.uint32(0), jnp.uint32(0))
                    if telemetry:
                        carry += (T.init_trace(),)
                carry = jax.lax.while_loop(cond, body, carry)
                st, hi, lo = carry[0], carry[2], carry[3]
                with jax.named_scope("repro/finalize"):
                    outs = tuple(prog.finalize(self, st, i, j)) + (hi, lo)
                    if telemetry:
                        outs += T.trace_outputs(carry[4])
                return outs

            if batched:
                outs = jax.lax.map(search, arg)
            else:
                outs = search(arg)
            with jax.named_scope("repro/finalize"):
                return tuple(o[None, None] for o in outs)

        dev = topo.dev_spec
        out_specs = tuple(prog.out_specs(self)) + (dev, dev)
        if telemetry:
            out_specs += (dev,) * T.N_TRACE_OUTS
        mapped = topo.shard_map(
            device_fn,
            in_specs=(dev,) * (3 + prog.n_extra) + (P(),),
            out_specs=out_specs)

        def counted(*args):
            # runs at TRACE time only (jit / .lower()); cache hits skip it
            self.trace_count += 1
            return mapped(*args)

        return counted

    def assemble(self, outs, B):
        """Gathered device outputs -> output object, with telemetry split
        off, assembled into a host `LevelTrace`, attached to the output's
        `trace` field (`GraphSession.last_trace()` keeps it per session).

        This is the ONE funnel both invocation paths share: `run` /
        `run_batch` here, and the session layer's AOT executables (which
        call the compiled artifact directly and assemble through this).
        In a process group the device outputs are global arrays whose
        remote shards this process cannot read; fetch them first (identity
        for every fully-addressable, i.e. single-process, output).
        """
        from repro.dist import multihost
        outs = multihost.fetch_all(outs)
        trace = None
        if self.telemetry:
            from repro.obs import trace as T
            outs, traw = outs[:-T.N_TRACE_OUTS], outs[-T.N_TRACE_OUTS:]
            trace = T.assemble_traces(traw, B, grid=self.grid,
                                      program=self.program.name,
                                      codec=self.codec.name)
        out = self.program.assemble(self, tuple(outs), B)
        if trace is not None:
            import dataclasses
            out = dataclasses.replace(out, trace=trace)
        return out

    def run(self, graph: LocalGraph2D, arg, *extra):
        """One search; extra = the program's per-device graph arrays.

        `arg` is the program's search argument (a root, a sources vector, a
        dummy scalar for argument-free programs like CC)."""
        outs = self._run(graph.col_off, graph.row_idx, graph.nnz, *extra, arg)
        return self.assemble(outs, None)

    def run_batch(self, graph: LocalGraph2D, args, *extra):
        """A leading-axis batch of searches as ONE compiled program."""
        outs = self._run_batch(graph.col_off, graph.row_idx, graph.nnz,
                               *extra, args)
        return self.assemble(outs, int(args.shape[0]))

    # -- segmented level loop (DESIGN.md sec. 15) ----------------------------
    #
    # The same init / step / finalize as `_build`, split at checkpoint lines:
    # `ft_start` runs init, `ft_segment` runs AT MOST `ckpt_every` levels of
    # the while_loop, `ft_finish` runs finalize.  Between calls the loop
    # carry lives on the host side as a dict of (R, C[, B], ...) device
    # arrays -- the checkpoint schema IS the FrontierProgram carry -- so the
    # driver in repro.runtime.recovery can snapshot it, detect injected
    # device loss, and resume (same grid or shrunken via export/import).
    # Segment boundaries add no arithmetic: level k's inputs are exactly the
    # carry level k-1 produced, so segmented outputs are bit-identical to
    # the single-while_loop program for every K.

    def _ft(self, batched: bool):
        if not self.fault_tolerance:
            raise ValueError(
                "segmented traversal needs BFSConfig(fault_tolerance=True)")
        fns = self._ft_progs.get(bool(batched))
        if fns is None:
            fns = tuple(jax.jit(self._build_ft(kind, batched))
                        for kind in ("init", "segment", "finalize"))
            self._ft_progs[bool(batched)] = fns
        return fns

    def _build_ft(self, kind: str, batched: bool):
        topo, prog = self.topo, self.program
        telemetry = self.telemetry
        K = jnp.int32(self.ckpt_every)
        from repro.obs import trace as T
        dev = topo.dev_spec

        def init_fn(col_off, row_idx, nnz, *rest):
            extra, arg = rest[:-1], rest[-1]
            graph = LocalGraph2D(col_off=col_off[0, 0], row_idx=row_idx[0, 0],
                                 nnz=nnz[0, 0])
            extra = tuple(e[0, 0] for e in extra)
            i, j = topo.device_coords()

            def one(a):
                st = prog.init(self, graph, extra, a, i, j)
                total = prog.init_total(self, st)
                carry = {"st": st, "total": total,
                         "hi": jnp.uint32(0), "lo": jnp.uint32(0),
                         "active": prog.keep_going(self, st, total)}
                if telemetry:
                    carry["trace"] = T.init_trace()
                return carry

            carry = jax.lax.map(one, arg) if batched else one(arg)
            return jax.tree_util.tree_map(lambda o: o[None, None], carry)

        def seg_fn(col_off, row_idx, nnz, *rest):
            extra, carry = rest[:-1], rest[-1]
            graph = LocalGraph2D(col_off=col_off[0, 0], row_idx=row_idx[0, 0],
                                 nnz=nnz[0, 0])
            extra = tuple(e[0, 0] for e in extra)
            i, j = topo.device_coords()

            def one(c):
                step = prog.make_step(self, graph, extra, i, j)

                def cond(t):
                    return prog.keep_going(self, t[0], t[1]) & (t[4] < K)

                def body(t):
                    st, total, hi, lo, k = t[:5]
                    res = step(st, total)
                    aux = res[3] if len(res) > 3 else None
                    st2, total2, scanned = res[0], res[1], res[2]
                    hi, lo = wide_add(hi, lo, scanned)
                    if not telemetry:
                        return st2, total2, hi, lo, k + 1
                    tr = T.record_level(
                        t[5], frontier=total,
                        front_dev=prog.front_count(st), scanned=scanned,
                        aux=T.normalize_aux(aux))
                    return st2, total2, hi, lo, k + 1, tr

                t = (c["st"], c["total"], c["hi"], c["lo"], jnp.int32(0))
                if telemetry:
                    t += (c["trace"],)
                t = jax.lax.while_loop(cond, body, t)
                out = {"st": t[0], "total": t[1], "hi": t[2], "lo": t[3],
                       "active": prog.keep_going(self, t[0], t[1])}
                if telemetry:
                    out["trace"] = t[5]
                return out

            c = jax.tree_util.tree_map(lambda x: x[0, 0], carry)
            carry = jax.lax.map(one, c) if batched else one(c)
            return jax.tree_util.tree_map(lambda o: o[None, None], carry)

        def fin_fn(carry):
            i, j = topo.device_coords()

            def one(c):
                outs = tuple(prog.finalize(self, c["st"], i, j)) \
                    + (c["hi"], c["lo"])
                if telemetry:
                    outs += T.trace_outputs(c["trace"])
                return outs

            c = jax.tree_util.tree_map(lambda x: x[0, 0], carry)
            outs = jax.lax.map(one, c) if batched else one(c)
            return tuple(o[None, None] for o in outs)

        if kind == "init":
            mapped = topo.shard_map(
                init_fn,
                in_specs=(dev,) * (3 + prog.n_extra) + (P(),),
                out_specs=dev)
        elif kind == "segment":
            mapped = topo.shard_map(
                seg_fn,
                in_specs=(dev,) * (3 + prog.n_extra) + (dev,),
                out_specs=dev)
        else:
            fin_specs = tuple(prog.out_specs(self)) + (dev, dev)
            if telemetry:
                fin_specs += (dev,) * T.N_TRACE_OUTS
            mapped = topo.shard_map(fin_fn, in_specs=(dev,),
                                    out_specs=fin_specs)

        def counted(*args):
            # runs at TRACE time only (jit cache hits skip it), so tests can
            # assert repeated segmented sweeps compile each piece once
            self.trace_count += 1
            return mapped(*args)

        return counted

    def ft_start(self, graph: LocalGraph2D, arg, *extra, batched=False):
        """Init carry for one search (scalar arg) or a leading-axis batch."""
        return self._ft(batched)[0](graph.col_off, graph.row_idx, graph.nnz,
                                    *extra, arg)

    def ft_segment(self, graph: LocalGraph2D, carry, *extra, batched=False):
        """Advance the carry by at most `ckpt_every` levels (pure function:
        the input carry is untouched, so a failed segment retries from it)."""
        return self._ft(batched)[1](graph.col_off, graph.row_idx, graph.nnz,
                                    *extra, carry)

    def ft_finish(self, carry, B=None):
        """Finalize a converged carry through the shared assemble funnel."""
        return self.assemble(self._ft(B is not None)[2](carry), B)

    def ft_active(self, carry) -> bool:
        """Host check: does any search in the carry still have work?"""
        from repro.dist import multihost
        return bool(np.asarray(multihost.fetch(carry["active"])).any())

    def ft_levels_done(self, carry) -> int:
        """Host readout: levels completed so far (max over a batch)."""
        from repro.dist import multihost
        cnt = self.program.level_count(carry["st"])
        return int(np.asarray(multihost.fetch(cnt))[0, 0].max()) - 1

    # -- carry export / import (the checkpoint schema; DESIGN.md sec. 15) ----

    def export_carry(self, carry, *, n=None, B=None) -> dict:
        """Segmented-loop carry -> grid-independent host snapshot.

        `arrays` is a nested dict of numpy arrays (what CheckpointManager
        persists); `meta` is the JSON-able identity the checkpointer keys
        on.  The per-vertex state is exported in GLOBAL vertex-id order and
        sliced to the raw `n`, so the snapshot can re-shard onto any grid
        (`import_carry` re-pads); totals/activity are replicated scalars and
        the (hi, lo) edge accounting exports as one exact integer.
        """
        from repro.dist import multihost
        host = jax.tree_util.tree_map(
            lambda x: np.asarray(multihost.fetch(x)), carry)
        n = int(self.grid.n if n is None else n)
        prog = self.program
        if B is None:
            st_snap = prog.export_state(self, host["st"], n)
        else:
            st_snap = {
                f"b{b}": prog.export_state(
                    self,
                    jax.tree_util.tree_map(lambda x: x[:, :, b], host["st"]),
                    n)
                for b in range(B)}
        hi = host["hi"].astype(np.int64)
        lo = host["lo"].astype(np.int64)
        scanned = (hi.sum(axis=(0, 1)) << 32) + lo.sum(axis=(0, 1))
        arrays = {"st": st_snap,
                  "total": np.asarray(host["total"][0, 0], np.int64),
                  "active": np.asarray(host["active"][0, 0], bool),
                  "scanned": np.asarray(scanned, np.int64)}
        if self.telemetry:
            arrays["trace"] = {k: np.asarray(v)
                               for k, v in host["trace"].items()}
        if B is None:
            levels_done = int(st_snap["levels_done"])
        else:
            levels_done = max(int(st_snap[f"b{b}"]["levels_done"])
                              for b in range(B))
        meta = {"program": prog.name, "codec": self.codec.name,
                "grid": [self.grid.R, self.grid.C], "B": B, "n": n,
                "max_levels": int(self.max_levels),
                "levels_done": levels_done}
        return {"arrays": arrays, "meta": meta}

    def import_carry(self, snapshot: dict, *, B=None):
        """Host snapshot -> device carry on THIS engine's grid (the resume
        half of `export_carry`; the grids need not match -- elastic resume
        re-shards the global state onto the survivor mesh)."""
        arrays = snapshot["arrays"]
        prog = self.program
        if B is None:
            st = prog.import_state(self, arrays["st"])
        else:
            sts = [prog.import_state(self, arrays["st"][f"b{b}"])
                   for b in range(B)]
            st = jax.tree_util.tree_map(
                lambda *xs: np.stack(xs, axis=2), *sts)
        shp = (self.grid.R, self.grid.C) + (() if B is None else (B,))
        total = np.broadcast_to(
            np.asarray(arrays["total"], np.int32), shp).copy()
        active = np.broadcast_to(
            np.asarray(arrays["active"], bool), shp).copy()
        scanned = np.asarray(arrays["scanned"], np.int64)
        hi = np.zeros(shp, np.uint32)
        lo = np.zeros(shp, np.uint32)
        hi[0, 0] = (scanned >> np.int64(32)).astype(np.uint32)
        lo[0, 0] = (scanned & np.int64(0xFFFFFFFF)).astype(np.uint32)
        carry = {"st": st, "total": total, "hi": hi, "lo": lo,
                 "active": active}
        if self.telemetry:
            carry["trace"] = self._import_trace(
                arrays.get("trace"), B, snapshot["meta"]["levels_done"])
        return self._place_carry(carry)

    def _import_trace(self, traw, B, levels_done: int) -> dict:
        """Raw (R0, C0[, B], L) trace channels -> this grid's trace carry.

        Same grid: bit-exact reimport.  Shrunken grid: per-device work
        channels collapse onto device (0, 0) (sums -- global per-level
        figures survive exactly, per-device attribution does not) and the
        psum-replicated channels broadcast from device (0, 0).  A channel
        newer than the snapshot starts blank, as after a snapshot taken
        without telemetry.
        """
        from repro.obs import trace as T

        def blank(c, shape):
            if c == "dir":
                return np.full(shape, -1, np.int32)
            return np.zeros(shape, np.uint32 if c in ("scanned", "wire")
                            else np.int32)

        R, C = self.grid.R, self.grid.C
        shp = (R, C) + (() if B is None else (B,))
        L = T.RECORDED_LEVELS
        if traw is None:
            # resuming a snapshot taken without telemetry: blank history,
            # k advanced so post-resume levels land in the right slots
            tr = {c: blank(c, shp + (L,)) for c in T.TRACE_CHANNELS}
            tr["k"] = np.full(shp, levels_done, np.int32)
            return tr
        traw = dict(traw)
        for c in T.TRACE_CHANNELS:
            if c not in traw:
                traw[c] = blank(c, np.shape(traw["frontier"]))
        src_grid = traw["k"].shape[:2]
        if src_grid == (R, C):
            return {k: np.asarray(v) for k, v in traw.items()}
        tr = {}
        for c in ("front_dev", "scanned", "folded", "wire", "msgs",
                  "map_passes"):
            a = np.asarray(traw[c])
            out = np.zeros(shp + (L,), a.dtype)
            out[0, 0] = a.sum(axis=(0, 1), dtype=np.int64).astype(a.dtype)
            tr[c] = out
        for c in ("frontier", "dir"):
            a = np.asarray(traw[c])
            tr[c] = np.broadcast_to(a[0, 0], shp + (L,)).copy()
        tr["k"] = np.broadcast_to(
            np.asarray(traw["k"])[0, 0], shp).copy().astype(np.int32)
        return tr

    def _place_carry(self, carry):
        """Host (R, C[, B], ...) leaves -> device arrays sharded over this
        topology's mesh (the placement of elastic resume)."""
        from repro.dist import multihost
        mesh, dev = self.topo.mesh, self.topo.dev_spec
        return jax.tree_util.tree_map(
            lambda x: multihost.put_dev(x, mesh, dev), carry)
