"""Distributed BFS with 2D partitioning (paper Alg. 2) on the shared engine.

Mesh mapping (DESIGN.md sec. 5): the processor grid's ROWS span `row_axes`
(e.g. ("pod", "data")) and its COLUMNS span `col_axes` (e.g. ("model",)).
  expand (paper line 13)  = all_gather of frontiers along the row axes
                            (processors in the same grid column);
  fold   (paper line 17)  = all_to_all of discovered vertices along the col
                            axes (processors in the same grid row).
So one BFS level costs 2 x O(sqrt(P)) partner exchanges instead of the 1D
code's O(P) (paper sec. 2.2).

The level loop, init and deferred-predecessor resolution live in
`repro.dist.engine`; what goes on the fold wire is a pluggable codec
(`repro.dist.exchange`, DESIGN.md sec. 4): the paper's 32-bit local indices
("list", sec. 3.3), a 1-bit block bitmap ("bitmap"), or sorted 16-bit deltas
("delta", Romera & Froning 2017).
"""
from __future__ import annotations

from repro.core.types import Grid2D, LocalGraph2D, BFSOutput
from repro.dist.topology import Topology


class BFS2D:
    """DEPRECATED shim over the session API (repro.api).

    Equivalent to `DistGraph(...).session()` with `BFSConfig(...)`; kept so
    pre-session callers keep passing.  Arrays for the graph carry leading
    (R, C) device axes (as produced by `partition_2d`); results come back as
    global (n,) arrays laid out in vertex-block order (b = j*R + i), i.e.
    plain global vertex ids.

    fold_codec selects the fold wire format ("list" | "bitmap" | "delta");
    `fold_bitmap=True` is the deprecated legacy spelling of
    fold_codec="bitmap".
    """

    def __init__(self, grid: Grid2D, mesh, row_axes=("r",), col_axes=("c",),
                 edge_chunk: int = 8192, fold_bitmap: bool = None,
                 max_levels: int | None = None,
                 dedup: str = "scatter", fold_codec=None):
        import warnings

        from repro.api.config import BFSConfig, resolve_fold_codec
        from repro.api.session import build_engine

        warnings.warn(
            "BFS2D is deprecated; use repro.api.DistGraph.from_edges(...)"
            ".session() instead", DeprecationWarning, stacklevel=2)
        fold_codec = resolve_fold_codec(fold_codec, fold_bitmap)
        self.config = BFSConfig(
            grid=grid, fold_codec=fold_codec, edge_chunk=edge_chunk,
            dedup=dedup, max_levels=max_levels, row_axes=tuple(row_axes),
            col_axes=tuple(col_axes))
        self.grid = grid
        self.mesh = mesh
        self.topology = Topology(grid, mesh, row_axes=row_axes,
                                 col_axes=col_axes)
        self.engine = build_engine(self.topology, self.config)
        self._run = self.engine._run   # (col_off, row_idx, nnz, root) -> outs
        self._compiled = {}            # aval-keyed AOT cache, shared across
                                       # every graph run through this shim

    def _session(self, graph: LocalGraph2D):
        from repro.api.session import DistGraph, GraphSession

        dg = DistGraph(self.topology, graph, config=self.config)
        dg._compiled = self._compiled  # executables are data-independent
        return GraphSession(dg, self.config, engine=self.engine)

    def run(self, graph: LocalGraph2D, root) -> BFSOutput:
        return self._session(graph).bfs(root)
