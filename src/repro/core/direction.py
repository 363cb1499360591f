"""DEPRECATED shim: direction-optimising 2D BFS moved into the engine.

Direction optimisation (Beamer et al. [7] + [20]) is now a first-class mode
of the frontier engine: `BFSConfig(direction=True | "adaptive" | "bottomup")`
routes BFS -- and CC / SSSP / multi-source BFS -- through the
`repro.algos.direction.DirectionProgram` wrapper, whose bottom-up parent
search lives in `repro.core.frontier` (DESIGN.md sec. 11).  Nothing on the
hot path imports this module any more.

`BFS2DDirection` remains as a deprecated drop-in for pre-session callers; it
is a thin veneer over `BFSConfig(direction=True)` on a `GraphSession`.
"""
from __future__ import annotations

from repro.core.types import Grid2D, LocalGraph2D, BFSOutput
from repro.dist.topology import Topology


class BFS2DDirection:
    """DEPRECATED shim over the session API (drop-in for BFS2D.run).

    Equivalent to `BFSConfig(direction=True)` on a `GraphSession`; kept so
    pre-session callers keep working.  Use
    `repro.api.DistGraph.from_edges(edges, BFSConfig(direction=True))`.
    """

    def __init__(self, grid: Grid2D, mesh, row_axes=("r",), col_axes=("c",),
                 edge_chunk: int = 8192, alpha: int = 24,
                 max_levels: int | None = None, fold_codec="list"):
        import warnings

        from repro.api.config import BFSConfig
        from repro.api.session import build_engine

        warnings.warn(
            "BFS2DDirection is deprecated; use repro.api.DistGraph/"
            "GraphSession with BFSConfig(direction=True)",
            DeprecationWarning, stacklevel=2)
        self.grid, self.mesh = grid, mesh
        self.alpha = alpha
        self.config = BFSConfig(
            grid=grid, fold_codec=fold_codec, edge_chunk=edge_chunk,
            max_levels=max_levels, direction=True, alpha=alpha,
            row_axes=tuple(row_axes), col_axes=tuple(col_axes))
        self.topology = Topology(grid, mesh, row_axes=row_axes,
                                 col_axes=col_axes)
        self.engine = build_engine(self.topology, self.config)
        self._run = self.engine._run
        self._compiled = {}            # aval-keyed AOT cache, shared across
                                       # every graph run through this shim

    def _session(self, graph: LocalGraph2D, csr: dict):
        from repro.api.session import DistGraph, GraphSession

        dg = DistGraph(self.topology, graph, csr=csr, config=self.config)
        dg._compiled = self._compiled  # executables are data-independent
        return GraphSession(dg, self.config, engine=self.engine)

    def run(self, graph: LocalGraph2D, csr: dict, root) -> BFSOutput:
        return self._session(graph, csr).bfs(root)
