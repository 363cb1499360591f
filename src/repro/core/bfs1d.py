"""The paper's 1D comparison baseline as the DEGENERATE 1 x P grid.

The original 1D code ([1]/[2]) has the two scalability limits the 2D code
removes (paper sec. 2.1): every level is an all-to-all among ALL P
processors (O(P) partner exchanges vs the 2D code's 2 x O(sqrt P)), and
duplicate filtering needs a full-size map (O(n) per device).  Both fall out
of the shared engine at the degenerate 1 x P topology with no separate
driver code: the expand all_gather spans a single processor (identity), the
fold all_to_all spans all P, and the local row space -- hence the visited
bitmap -- is the whole vertex set.

Differences from the seed's hand-rolled 1D driver: vertices are laid out in
owner blocks (`partition_2d` on the 1 x P grid, block j = vertices
[j*S, (j+1)*S)) rather than by the modulo rule, and parents are resolved by
the engine's deferred exchange rather than travelling inline as (u, v)
pairs.  Neither changes the communication structure the 1D-vs-2D comparison
measures (benchmarks/bfs_1d_vs_2d.py): per level the fold still exchanges
O(P) messages of 4*S+4 bytes and the final pred resolution is one more
all-to-all, while the O(n) per-device map cost is unchanged.
"""
from __future__ import annotations

from repro.core.types import LocalGraph2D, BFSOutput
from repro.dist.topology import Topology


class BFS1D:
    """DEPRECATED shim: the 1 x P degenerate grid through the session API.

    Partition the edge list with `partition_2d(edges, bfs.grid)` (the 1 x P
    grid pads n up to a multiple of P); results come back as plain global
    (n,) arrays.  New code should build a `BFSConfig(grid=(1, P),
    row_axes=(), col_axes=axes)` session instead.
    """

    def __init__(self, n: int, mesh, axes=("p",), edge_chunk: int = 8192,
                 max_levels: int | None = None, fold_codec="list"):
        import warnings

        from repro.api.config import BFSConfig
        from repro.api.session import build_engine

        warnings.warn(
            "BFS1D is deprecated; use repro.api.DistGraph/GraphSession with "
            "BFSConfig(grid=(1, P), row_axes=(), col_axes=axes)",
            DeprecationWarning, stacklevel=2)
        self.n = n
        self.mesh = mesh
        self.topology = Topology.one_d(n, mesh, axes)
        self.grid = self.topology.grid
        self.P = self.grid.C
        self.ncl = self.grid.n_cols_local
        self.config = BFSConfig(
            grid=self.grid, fold_codec=fold_codec, edge_chunk=edge_chunk,
            max_levels=max_levels, row_axes=self.topology.row_axes,
            col_axes=self.topology.col_axes)
        self.engine = build_engine(self.topology, self.config)
        self._run = self.engine._run
        self._compiled = {}            # aval-keyed AOT cache, shared across
                                       # every graph run through this shim

    def _session(self, graph: LocalGraph2D):
        from repro.api.session import DistGraph, GraphSession

        dg = DistGraph(self.topology, graph, config=self.config)
        dg._compiled = self._compiled  # executables are data-independent
        return GraphSession(dg, self.config, engine=self.engine)

    def run(self, graph: LocalGraph2D, root) -> BFSOutput:
        return self._session(graph).bfs(root)
