"""The shared distributed-BFS engine (DESIGN.md sec. 6).

Since the frontier-program subsystem (DESIGN.md sec. 8) the generic parts --
the `lax.while_loop` over levels, the scalar/batched device programs, the
64-bit (hi, lo)-uint32 edge accounting -- live in
`repro.algos.engine.FrontierEngine`, and BFS itself is ONE frontier program
(`repro.algos.bfs.BFSLevelsProgram`).  `DistBFSEngine` is that pair under
the historical constructor: init, the level loop, the deferred-predecessor
resolution and the per-search accounting behave exactly as before; drivers
remain thin configurations (topology + fold codec + optionally a custom
per-level step).

Per-level step contract (what `step_factory` must produce):

    step(st: BFSState, prev_total: int32) ->
        (new_st: BFSState, total: int32, scanned: uint32)

`prev_total` is the global size of the frontier entering the level (what the
direction-optimising driver's heuristic consumes); `scanned` is this level's
locally scanned edge count.  The default step is the engine's own top-down
expand -> scan -> fold -> update level.

Accounting is 64-bit: totals accumulate in a (hi, lo) uint32 pair because
int32 silently wraps at RMAT scale >= 26 (2*16*2^26 > 2^31 scanned edges per
search) and jnp.int64 is unavailable without jax_enable_x64.
"""
from __future__ import annotations

import jax.numpy as jnp

# Re-exports: these historically lived here and stay importable from here.
from repro.algos.engine import FrontierEngine, wide_add, wide_total  # noqa: F401
from repro.core.types import LocalGraph2D, BFSOutput
from repro.dist.topology import Topology

# The BFS building blocks now live in repro.algos.bfs, which imports
# repro.dist.exchange -- so pulling them in at module scope would re-enter
# this package's own __init__ mid-import.  PEP 562 keeps
# `from repro.dist.engine import topdown_step` (etc.) working lazily.
_BFS_REEXPORTS = ("BFSLevelsProgram", "init_state", "owned_level",
                  "topdown_step")


def __getattr__(name):
    if name in _BFS_REEXPORTS:
        from repro.algos import bfs
        return getattr(bfs, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


class DistBFSEngine(FrontierEngine):
    """Whole-search BFS program over a Topology (single lowering, jitted
    once) -- `BFSLevelsProgram` on the generalized driver.

    Parameters
    ----------
    topo:         Topology binding the processor grid to mesh axes.
    fold_codec:   "list" | "bitmap" | "delta" | FoldCodec instance.
    dedup:        winner-selection method ("scatter" | "sort").
    exchange:     fold exchange strategy ("flat" | "butterfly" | "auto" |
                  an ExchangeStrategy instance; DESIGN.md sec. 14) -- how
                  fold messages route within the processor-row,
                  bit-identical either way.
    step_factory: optional `(engine, graph, extra, i, j, topdown) -> step`
                  hook replacing the default top-down per-level step.
    n_extra:      number of extra per-device (R, C, ...) graph arrays the
                  step consumes (e.g. the CSR twin for bottom-up).
    program:      optional BFS-shaped FrontierProgram overriding the default
                  `BFSLevelsProgram` (the session passes the
                  direction-optimising `DirectionProgram` wrapper here);
                  wins over step_factory/n_extra.
    """

    def __init__(self, topo: Topology, *, fold_codec="list",
                 edge_chunk: int = 8192, max_levels: int | None = None,
                 dedup: str = "scatter", exchange="flat",
                 step_factory=None, n_extra: int = 0,
                 program=None, telemetry: bool = False,
                 fault_tolerance: bool = False, ckpt_every: int = 1):
        from repro.algos.bfs import BFSLevelsProgram

        if program is None:
            program = BFSLevelsProgram(step_factory=step_factory,
                                       n_extra=n_extra)
        self.step_factory = step_factory
        self.n_extra = program.n_extra
        super().__init__(
            topo, program,
            fold_codec=fold_codec, edge_chunk=edge_chunk,
            max_levels=max_levels, dedup=dedup, exchange=exchange,
            telemetry=telemetry, fault_tolerance=fault_tolerance,
            ckpt_every=ckpt_every)

    def topdown_step(self, graph: LocalGraph2D, st, *, i, j):
        """One top-down level (paper Alg. 2 lines 12-18)."""
        from repro.algos.bfs import topdown_step
        return topdown_step(self, graph, st, i=i, j=j)

    def run(self, graph: LocalGraph2D, root, *extra) -> BFSOutput:
        """Search from `root`; extra = the step_factory's per-device arrays.

        Returns global (n,) level/pred in vertex-block order (b = j*R + i,
        i.e. plain global vertex ids), plus the exact 64-bit scanned-edge
        count summed over devices and levels."""
        return super().run(graph, jnp.int32(root), *extra)

    def assemble_batch(self, outs, B: int) -> BFSOutput:
        """Gathered batched device outputs -> global (B, n) BFSOutput."""
        return self.assemble(outs, B)
