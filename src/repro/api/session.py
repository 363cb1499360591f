"""Two-phase session API: plan/residency vs query (DESIGN.md sec. 7).

Phase 1 -- `DistGraph.from_edges(edges, config)` does everything that is
per-GRAPH and per-LAYOUT: grid resolution, topology/mesh binding, the CSC
partition, and device placement.  The CSR twin (what bottom-up traversal
scans) is planned LAZILY by the first direction-enabled query and cached on
the graph.  The result is a resident graph that answers many queries.

Phase 2 -- `GraphSession.bfs(roots)` runs searches against the resident
graph.  A scalar root returns one `BFSOutput`; a batch of roots executes as
ONE compiled program (the engine's level loop under `lax.map` over the roots
axis) and returns batched outputs.  Executables are AOT-compiled with
`jit(...).lower().compile()` and cached on the DistGraph keyed by
(engine key = codec/direction/..., graph array shapes, batch size), so a
Graph500-style 64-root sweep traces the level loop exactly once.

Host spans (DESIGN.md sec. 13.4): every `GraphSession.bfs` runs under the
profiler span `repro/session/bfs`, with children `repro/session/dispatch`
(checks, placement, executable lookup, the call), `repro/session/compile`
(an AOT miss only) and `repro/session/assemble` (the host waits for the
outputs and builds the answer); planning runs under `repro/plan/<phase>`.
With no profiler running each span is one inert `TraceMe`.
"""
from __future__ import annotations

import contextlib
import time
import warnings
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.algos import (
    BFSLevelsProgram, CCOutput, ConnectedComponentsProgram, DirectionProgram,
    FrontierEngine, MultiBFSOutput, MultiSourceBFSProgram, SSSPOutput,
    SSSPProgram)
from repro.api.config import BFSConfig
from repro.core.partition import (partition_2d, partition_2d_csr,
                                  partition_edge_vals,
                                  partition_edge_vals_csr)
from repro.core.types import BFSOutput, LocalGraph2D
from repro.core.validate import edge_keys, validate_bfs
from repro.dist import multihost
from repro.dist.engine import DistBFSEngine
from repro.dist.topology import Topology


def check_vertex_ids(ids, n: int, what: str = "roots") -> None:
    """Session-boundary input validation (DESIGN.md sec. 12).

    Out-of-range or wrong-dtype vertex ids used to surface as opaque JAX
    errors mid-trace (or, worse, silently wrap once cast to int32); a
    serving layer must reject a bad request before it reaches a compiled
    program.  Raises ValueError naming the graph's n and the expected
    dtype; accepts anything integer-typed convertible to int32.
    """
    arr = np.asarray(ids)
    if not np.issubdtype(arr.dtype, np.integer):
        raise ValueError(
            f"{what} must be integer vertex ids (int32-convertible), got "
            f"dtype {arr.dtype}")
    if arr.size:
        lo, hi = int(arr.min()), int(arr.max())
        if lo < 0 or hi >= n:
            bad = lo if lo < 0 else hi
            raise ValueError(
                f"{what} contain out-of-range vertex id {bad}; this graph "
                f"has n = {n} vertices, valid ids are 0 <= id < {n}")


class AOTCache:
    """Bounded LRU over AOT-compiled executables, with serve-grade stats.

    One entry per (engine key, graph shapes, batch size) -- before the
    bound, a sweep over many batch sizes B (or many engine configs) grew
    the per-DistGraph executable cache without limit.  Eviction recompiles
    on next use, so the bound trades compile time for memory, never
    correctness.  `hits` / `misses` / `evictions` feed `repro.serve`
    accounting; `compiles` / `compile_s` count the misses that lowered and
    compiled, and their seconds (loads from JAX's persistent compilation
    cache included).
    """

    def __init__(self, maxsize: int = 32):
        if maxsize < 1:
            raise ValueError(f"AOTCache needs maxsize >= 1, got {maxsize}")
        self.maxsize = maxsize
        self._entries = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compiles = 0
        self.compile_s = 0.0

    def get(self, key, default=None):
        try:
            value = self._entries[key]
        except KeyError:
            self.misses += 1
            return default
        self._entries.move_to_end(key)
        self.hits += 1
        return value

    def __setitem__(self, key, value):
        self._entries[key] = value
        self._entries.move_to_end(key)
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
            self.evictions += 1

    def __len__(self):
        return len(self._entries)

    def __contains__(self, key):          # no stats: introspection only
        return key in self._entries

    def stats(self) -> dict:
        return {"size": len(self._entries), "maxsize": self.maxsize,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "compiles": self.compiles,
                "compile_s": self.compile_s}


@contextlib.contextmanager
def _planning(plan: dict, phase: str):
    """Time one host planning phase into `plan[phase]` (seconds), under the
    profiler span `repro/plan/<phase>`."""
    with TraceAnnotation(f"repro/plan/{phase}"):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            plan[phase] = plan.get(phase, 0.0) + time.perf_counter() - t0


def build_engine(topology: Topology, config: BFSConfig) -> DistBFSEngine:
    """One engine per (topology, engine_key): the level-loop program with the
    config's codec/chunking/direction baked in, independent of graph DATA."""
    program = None
    if config.direction_mode is not None:
        program = DirectionProgram(BFSLevelsProgram(),
                                   mode=config.direction_mode,
                                   alpha=config.alpha, beta=config.beta)
    return DistBFSEngine(
        topology, fold_codec=config.fold_codec, edge_chunk=config.edge_chunk,
        max_levels=config.max_levels, dedup=config.dedup,
        exchange=config.exchange, program=program,
        telemetry=config.telemetry, fault_tolerance=config.fault_tolerance,
        ckpt_every=config.ckpt_every)


class DistGraph:
    """A resident, partitioned graph: plan once, query many.

    Holds the device-placed CSC blocks (and CSR twin when planned), the
    topology, and the engine + AOT-executable caches every `GraphSession`
    over this graph shares.
    """

    def __init__(self, topology: Topology, csc: LocalGraph2D, *, csr=None,
                 weights=None, edges=None, n: int | None = None,
                 config: BFSConfig = None, csr_weights=None,
                 weights_host=None, aot_cache_size: int = 32):
        self.topology = topology
        self.grid = topology.grid
        self.mesh = topology.mesh
        self.csc = csc
        self.csr = csr
        self.weights = weights       # (R, C, e_max) per-edge values or None
        self.csr_weights = csr_weights   # the CSR-ordered copy (SSSP + dir)
        self.n = int(n) if n is not None else topology.grid.n
        self.config = config if config is not None else BFSConfig()
        # host edge/weight copies retained ONLY while they may still be
        # needed to plan the CSR twin lazily (dropped once CSR exists; see
        # release_edges)
        self._edges = edges if csr is None else None
        self._weights_host = weights_host if csr is None else None
        self._engines = {}           # engine key -> engine (BFS or algo)
        # (engine key, shapes, B) -> executable; bounded LRU so a sweep over
        # many batch sizes / engine configs cannot grow without limit (the
        # deprecated driver shims may swap in a plain shared dict)
        self._compiled = AOTCache(aot_cache_size)
        # host planning seconds by phase: "csc" (partition), "place"
        # (device placement calls), "csr" (the twin, planned lazily)
        self._plan_s = {}

    @classmethod
    def from_edges(cls, edges, config: BFSConfig = None, *, mesh=None,
                   n: int | None = None, weights=None,
                   aot_cache_size: int = 32) -> "DistGraph":
        """Plan a graph into residency: partition + place on the mesh.

        edges: (2, E) [src, dst] array (host or device).  n defaults to
        max vertex id + 1; the grid pads it up to a multiple of R*C.
        weights: optional (E,) per-edge values (uint8 for SSSP), laid out in
        the CSC partition order and made resident alongside the graph.
        aot_cache_size: bound of the per-graph AOT-executable LRU (one
        entry per (engine key, shapes, batch size); see `AOTCache`).
        """
        config = config if config is not None else BFSConfig()
        edges_np = np.asarray(edges)
        if n is None:
            n = int(edges_np.max()) + 1 if edges_np.size else 1
        grid = config.resolve_grid(n, mesh)
        topology = Topology.for_grid(grid, mesh, config.row_axes,
                                     config.col_axes)
        plan = {}
        w_host = None if weights is None else np.asarray(weights)
        with _planning(plan, "csc"):
            lg = partition_2d(edges_np, grid)
            w_part = None if w_host is None \
                else partition_edge_vals(edges_np, w_host, grid)
        # device placement: per-device (R, C, ...) arrays land sharded over
        # the grid axes, each device holding only its own block (in a
        # process group every process materialises only its addressable
        # shards; repro.dist.multihost)
        place = cls._placer(topology)
        with _planning(plan, "place"):
            csc = LocalGraph2D(place(lg.col_off), place(lg.row_idx),
                               place(lg.nnz))
            w = None if w_part is None else place(w_part)
        # the CSR twin is planned LAZILY on the first query that needs it
        # (a direction-enabled session/algo call -> ensure_csr), so planning
        # with direction on costs nothing until bottom-up actually runs
        graph = cls(topology, csc, weights=w, edges=edges_np, n=n,
                    config=config, weights_host=w_host,
                    aot_cache_size=aot_cache_size)
        graph._plan_s.update(plan)
        return graph

    @staticmethod
    def _placer(topology: Topology):
        """Placement fn for per-device (R, C, ...) arrays on this topology
        (sharded over the grid axes, one block per device)."""
        return lambda x: multihost.put_dev(x, topology.mesh,
                                           topology.dev_spec)

    def ensure_csr(self):
        """Plan the CSR twin on demand (the first direction-enabled query);
        also lays the per-edge weights out in CSR order when resident, so
        direction-optimised SSSP can pull over them."""
        if self.csr is None:
            if self._edges is None:
                raise ValueError(
                    "direction=True needs the CSR twin, but this DistGraph "
                    "was built without edges; pass csr= or use from_edges")
            place = self._placer(self.topology)
            with _planning(self._plan_s, "csr"):
                self.csr = {k: place(v)
                            for k, v in partition_2d_csr(self._edges,
                                                         self.grid).items()}
                if self._weights_host is not None:
                    self.csr_weights = place(partition_edge_vals_csr(
                        self._edges, self._weights_host, self.grid))
            self._edges = None       # both layouts resident -> edges done
            self._weights_host = None
        return self.csr

    def release_edges(self):
        """Drop the retained host edge/weight copies (long-lived serving
        graphs that will never open a direction-enabled session)."""
        self._edges = None
        self._weights_host = None

    def cache_stats(self) -> dict:
        """Hit/miss/eviction counters of the AOT-executable cache (surfaced
        in `repro.serve` accounting / the metrics registry).  The deprecated
        driver shims share a plain dict here; stats then degrade to
        size-only."""
        cache = self._compiled
        if isinstance(cache, AOTCache):
            return cache.stats()
        return {"size": len(cache), "maxsize": None, "hits": None,
                "misses": None, "evictions": None, "compiles": None,
                "compile_s": None}

    def stats(self) -> dict:
        """The graph's own counters: host planning seconds by phase
        (`plan`: "csc", "place", "csr" once the twin is planned) and the
        AOT cache's (`aot`, as `cache_stats()`)."""
        return {"plan": dict(self._plan_s), "aot": self.cache_stats()}

    def executable(self, key, lower):
        """The cached executable for `key`; on a miss `lower()` is compiled
        (under the span `repro/session/compile`), cached and counted."""
        compiled = self._compiled.get(key)
        if compiled is None:
            with TraceAnnotation("repro/session/compile"):
                t0 = time.perf_counter()
                compiled = lower().compile()
                seconds = time.perf_counter() - t0
            self._compiled[key] = compiled
            if isinstance(self._compiled, AOTCache):
                self._compiled.compiles += 1
                self._compiled.compile_s += seconds
        return compiled

    def aot_cache_stats(self) -> dict:
        """Deprecated spelling of `cache_stats()` (same dict)."""
        warnings.warn(
            "DistGraph.aot_cache_stats() is deprecated; use "
            "DistGraph.cache_stats() (same dict)", DeprecationWarning,
            stacklevel=2)
        return self.cache_stats()

    def engine_for(self, config: BFSConfig) -> DistBFSEngine:
        key = config.engine_key
        eng = self._engines.get(key)
        if eng is None:
            eng = build_engine(self.topology, config)
            self._engines[key] = eng
        return eng

    def session(self, config: BFSConfig = None) -> "GraphSession":
        """Open a query session (defaults to the planning config)."""
        return GraphSession(self, config if config is not None
                            else self.config)


class GraphSession:
    """Query phase: many BFS searches over one resident DistGraph."""

    def __init__(self, graph: DistGraph, config: BFSConfig = None, *,
                 engine: DistBFSEngine = None):
        self.graph = graph
        self.config = config if config is not None else graph.config
        # exchange="auto" resolves against the PLANNED grid (butterfly on
        # power-of-two C >= 4, flat otherwise), and an explicit strategy is
        # validated here -- so every engine/AOT cache below keys on the
        # concrete strategy, and an impossible request fails at session
        # construction, not mid-trace
        self.config = self.config.resolve_exchange(graph.grid)
        if self.config.grid is not None:
            want = self.config.resolve_grid(graph.n, graph.mesh)
            if want != graph.grid:
                raise ValueError(
                    f"session config asks for a {want.R}x{want.C} grid but "
                    f"the resident graph is planned {graph.grid.R}x"
                    f"{graph.grid.C}; re-plan with DistGraph.from_edges")
        if self.config.direction_mode is not None:
            graph.ensure_csr()
        self.engine = engine if engine is not None \
            else graph.engine_for(self.config)
        # last LevelTrace (scalar) / tuple of traces (batched) any query of
        # THIS session produced; None until a telemetry=True query completes
        self._last_trace = None

    def last_trace(self):
        """The per-level `repro.obs.LevelTrace` of this session's most
        recent query (DESIGN.md sec. 13): a single trace for scalar queries,
        a tuple of B for batched ones.  None unless the session config has
        telemetry=True and a query has run."""
        return self._last_trace

    @property
    def _extra(self) -> tuple:
        if self.config.direction_mode is not None:
            csr = self.graph.csr
            return (csr["row_off"], csr["col_idx"])
        return ()

    def compiled_for(self, B: int):
        """AOT executable for a (B,)-roots sweep, cached on the DistGraph
        keyed by (engine key, graph array shapes, B).

        Public capacity surface: `repro.serve` warms its padding classes
        through this before admitting traffic, so the first live batch of
        each size pays no compile.  Returns the executable (callers rarely
        invoke it directly -- `bfs` is the ergonomic path)."""
        if B < 1:
            raise ValueError(f"batch capacity B must be >= 1, got {B}")
        g = self.graph.csc
        key = (self.config.engine_key, g.col_off.shape, g.row_idx.shape, B)
        return self.graph.executable(
            key, lambda: self.engine._run_batch.lower(
                g.col_off, g.row_idx, g.nnz, *self._extra,
                multihost.arg_aval((B,), jnp.int32, self.graph.mesh)))

    def _run_recoverable(self, eng, arg, *extra, B=None, recovery=None):
        """Fault-tolerant query path: the segmented engine loop under the
        recovery driver (DESIGN.md sec. 15) instead of one whole-search
        executable.  Bit-identical outputs; `recovery` is the RecoveryPlan
        carrying checkpointer / injector / retry policy."""
        from repro.runtime.recovery import run_segmented
        return run_segmented(eng, self.graph.csc, arg, *extra, B=B,
                             n=self.graph.n, plan=recovery)

    def _check_recovery(self, recovery) -> bool:
        if recovery is not None and not self.config.fault_tolerance:
            raise ValueError(
                "recovery= needs a fault-tolerant session; open it with "
                "BFSConfig(fault_tolerance=True)")
        return self.config.fault_tolerance

    def bfs(self, roots, validate=False, recovery=None) -> BFSOutput:
        """Search from a scalar root or a (B,) batch of roots.

        Scalar: global (n,) level/pred (vertex-block order = plain global
        vertex ids, padded to the grid), scalar n_levels, exact int
        edges_scanned.  Batch: (B, n) level/pred, (B,) n_levels, tuple of B
        edges_scanned -- bit-identical to running the roots one by one.

        validate: False (default) | True | (2, E) edge array.  Truthy runs
        the Graph500 rules (`repro.core.validate.validate_bfs`) on every
        root's output against the input edge list -- `True` uses the host
        edges the DistGraph retains while the CSR twin is unplanned; pass
        the array explicitly once they have been released.  Raises
        AssertionError on any rule violation.

        recovery: optional `repro.runtime.RecoveryPlan` (checkpointer /
        loss injector / retry policy) for a fault_tolerance=True session;
        the query then runs the segmented level loop and can resume.
        """
        with TraceAnnotation("repro/session/bfs"):
            return self._bfs(roots, validate, recovery)

    def _bfs(self, roots, validate, recovery) -> BFSOutput:
        scalar = np.ndim(roots) == 0
        with TraceAnnotation("repro/session/dispatch"):
            check_vertex_ids(roots, self.graph.n, "roots")
            roots_np = np.atleast_1d(np.asarray(roots, np.int32))
            if roots_np.ndim != 1:
                raise ValueError(f"roots must be a scalar or 1D batch, got "
                                 f"shape {roots_np.shape}")
            roots_arr = multihost.put_replicated(roots_np, self.graph.mesh)
            B = roots_np.shape[0]
            g = self.graph.csc
            recoverable = self._check_recovery(recovery)
            if recoverable:
                # the segmented loop assembles its own output
                out = self._run_recoverable(self.engine, roots_arr,
                                            *self._extra, B=B,
                                            recovery=recovery)
            else:
                outs = self.compiled_for(B)(
                    g.col_off, g.row_idx, g.nnz, *self._extra, roots_arr)
        with TraceAnnotation("repro/session/assemble"):
            if not recoverable:
                out = self.engine.assemble_batch(outs, B)
            if validate is not False and validate is not None:
                self._validate(out, roots_np, validate)
            if scalar:
                out = BFSOutput(level=out.level[0], pred=out.pred[0],
                                n_levels=out.n_levels[0],
                                edges_scanned=out.edges_scanned[0],
                                directions=None if out.directions is None
                                else out.directions[0],
                                trace=None if out.trace is None
                                else out.trace[0])
        if out.trace is not None:
            self._last_trace = out.trace
        return out

    def _validate(self, out: BFSOutput, roots, validate) -> None:
        """Graph500 rule check of a batched output (see `bfs(validate=)`)."""
        edges = self.graph._edges if isinstance(validate, bool) else validate
        if edges is None:
            raise ValueError(
                "bfs(validate=True) needs the host edge list, but this "
                "DistGraph has released it (CSR planned or release_edges); "
                "pass the edge array: bfs(roots, validate=edges)")
        n = self.graph.n
        level = np.asarray(out.level)
        pred = np.asarray(out.pred)
        keys = edge_keys(edges, n)
        for b, root in enumerate(roots):
            validate_bfs(edges, level[b][:n], pred[b][:n], int(root), keys)

    # ------------------------------------------------------------------
    # Frontier programs beyond BFS (DESIGN.md sec. 8)
    # ------------------------------------------------------------------

    def _algo_engine(self, program, fold_codec, max_levels):
        """Fetch/build the FrontierEngine for `program`, cached on the
        DistGraph like the BFS engines (config codec/chunking apply unless
        overridden per call).  A direction-enabled session wraps the program
        in the direction-optimising driver, so CC / SSSP / multi-source BFS
        inherit the per-level adaptive switch."""
        codec = fold_codec if fold_codec is not None else program.codec_hint
        codec_name = codec if isinstance(codec, str) \
            else getattr(codec, "name", repr(codec))
        if self.config.direction_mode is not None:
            self.graph.ensure_csr()
            program = DirectionProgram(program,
                                       mode=self.config.direction_mode,
                                       alpha=self.config.alpha,
                                       beta=self.config.beta)
        key = self.config.algo_engine_key(program.key, codec_name,
                                          max_levels)
        eng = self.graph._engines.get(key)
        if eng is None:
            eng = FrontierEngine(
                self.graph.topology, program, fold_codec=codec,
                edge_chunk=self.config.edge_chunk, max_levels=max_levels,
                dedup=self.config.dedup, exchange=self.config.exchange,
                telemetry=self.config.telemetry,
                fault_tolerance=self.config.fault_tolerance,
                ckpt_every=self.config.ckpt_every)
            self.graph._engines[key] = eng
        return eng, key

    def _algo_csr_extra(self, *, weights: bool = False) -> tuple:
        """The CSR-twin arrays a direction-enabled algo call appends after
        its regular extras (empty when direction is off)."""
        if self.config.direction_mode is None:
            return ()
        csr = self.graph.ensure_csr()
        if not weights:
            return (csr["row_off"], csr["col_idx"])
        if self.graph.csr_weights is None:
            raise ValueError(
                "direction-optimised sssp needs the CSR-ordered weight "
                "copy; plan the graph with DistGraph.from_edges(edges, "
                "config, weights=w) so ensure_csr can lay it out")
        return (csr["row_off"], csr["col_idx"], self.graph.csr_weights)

    def _algo_compiled(self, eng, key, arg_aval, *extra, batched=False):
        """AOT executable for one frontier program, cached on the DistGraph
        keyed by (engine key, graph array shapes, arg shape)."""
        g = self.graph.csc
        ckey = (key, g.col_off.shape, g.row_idx.shape, batched,
                arg_aval.shape)
        fn = eng._run_batch if batched else eng._run
        return self.graph.executable(
            ckey, lambda: fn.lower(g.col_off, g.row_idx, g.nnz, *extra,
                                   arg_aval))

    def connected_components(self, fold_codec=None,
                             recovery=None) -> CCOutput:
        """Labels of every vertex's connected component (min member id).

        Assumes the planned edge list is symmetrised (as the Graph500-style
        generator produces); on a directed list the label is the smallest
        vertex id with a directed path to each vertex.  fold_codec: None =
        the program's hint ("bitmap"); any codec gives identical labels.
        recovery: see `bfs`.
        """
        max_levels = self.graph.grid.n + 1     # diameter bound
        eng, key = self._algo_engine(ConnectedComponentsProgram(),
                                     fold_codec, max_levels)
        g = self.graph.csc
        extra = self._algo_csr_extra()
        arg = multihost.put_replicated(np.int32(0), self.graph.mesh)
        if self._check_recovery(recovery):
            out = self._run_recoverable(eng, arg, *extra, recovery=recovery)
        else:
            compiled = self._algo_compiled(
                eng, key,
                multihost.arg_aval((), jnp.int32, self.graph.mesh), *extra)
            outs = compiled(g.col_off, g.row_idx, g.nnz, *extra, arg)
            out = eng.assemble(outs, None)
        if out.trace is not None:
            self._last_trace = out.trace
        return out

    def sssp(self, roots, fold_codec=None, recovery=None) -> SSSPOutput:
        """Shortest distances over the planned per-edge uint8 weights.

        Scalar root -> (n,) int32 distances (-1 unreachable); a (B,) batch
        runs as ONE compiled program (lax.map over roots, like `bfs`) ->
        (B, n).  Requires `DistGraph.from_edges(..., weights=)`.
        recovery: see `bfs`.
        """
        if self.graph.weights is None:
            raise ValueError(
                "sssp needs resident per-edge weights; plan the graph with "
                "DistGraph.from_edges(edges, config, weights=w)")
        scalar = np.ndim(roots) == 0
        check_vertex_ids(roots, self.graph.n, "roots")
        roots_np = np.atleast_1d(np.asarray(roots, np.int32))
        if roots_np.ndim != 1:
            raise ValueError(f"roots must be a scalar or 1D batch, got "
                             f"shape {roots_np.shape}")
        roots_arr = multihost.put_replicated(roots_np, self.graph.mesh)
        B = roots_np.shape[0]
        max_levels = self.graph.grid.n + 1     # Bellman-Ford round bound
        eng, key = self._algo_engine(SSSPProgram(), fold_codec, max_levels)
        g, w = self.graph.csc, self.graph.weights
        extra = (w,) + self._algo_csr_extra(weights=True)
        if self._check_recovery(recovery):
            out = self._run_recoverable(eng, roots_arr, *extra, B=B,
                                        recovery=recovery)
        else:
            compiled = self._algo_compiled(
                eng, key,
                multihost.arg_aval((B,), jnp.int32, self.graph.mesh),
                *extra, batched=True)
            out = eng.assemble(
                compiled(g.col_off, g.row_idx, g.nnz, *extra, roots_arr), B)
        if scalar:
            out = SSSPOutput(dist=out.dist[0], n_iters=out.n_iters[0],
                             edges_scanned=out.edges_scanned[0],
                             directions=None if out.directions is None
                             else out.directions[0],
                             trace=None if out.trace is None
                             else out.trace[0])
        if out.trace is not None:
            self._last_trace = out.trace
        return out

    def multi_bfs(self, sources, k: int | None = None,
                  fold_codec=None, recovery=None) -> MultiBFSOutput:
        """Simultaneous BFS from a (K,) source set (ONE shared frontier).

        Returns per-vertex hops to the nearest source and the claiming
        source's index (same-wave ties -> minimum index).  k bounds the
        sweep to k hops: `level >= 0` is then the union k-hop neighborhood
        of the sources (the models/gnn sampling primitive).  Contrast
        `bfs(roots)`, which runs K independent full searches.
        recovery: see `bfs`.
        """
        check_vertex_ids(sources, self.graph.n, "sources")
        sources_np = np.asarray(sources, np.int32)
        if sources_np.ndim != 1 or sources_np.shape[0] == 0:
            raise ValueError(f"sources must be a non-empty 1D array, got "
                             f"shape {sources_np.shape}")
        sources_arr = multihost.put_replicated(sources_np, self.graph.mesh)
        max_levels = int(k) if k is not None else self.config.max_levels
        eng, key = self._algo_engine(MultiSourceBFSProgram(), fold_codec,
                                     max_levels)
        g = self.graph.csc
        extra = self._algo_csr_extra()
        if self._check_recovery(recovery):
            out = self._run_recoverable(eng, sources_arr, *extra,
                                        recovery=recovery)
        else:
            compiled = self._algo_compiled(
                eng, key,
                multihost.arg_aval(sources_np.shape, jnp.int32,
                                   self.graph.mesh), *extra)
            outs = compiled(g.col_off, g.row_idx, g.nnz, *extra,
                            sources_arr)
            out = eng.assemble(outs, None)
        if out.trace is not None:
            self._last_trace = out.trace
        return out
