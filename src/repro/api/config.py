"""BFSConfig: the ONE config object of the session API (DESIGN.md sec. 7).

Every knob that used to be scattered across the `BFS1D` / `BFS2D` /
`BFS2DDirection` constructors collapses here; direction optimisation is a
flag (`direction=True`), not a separate driver class.  The config is frozen
and hashable so it can key engine and AOT-executable caches.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any

from repro.core.types import Grid2D


def resolve_fold_codec(fold_codec=None, fold_bitmap=None):
    """Route the legacy `fold_bitmap` kwarg into the `fold_codec` spelling.

    `fold_bitmap` is deprecated: passing it (either value) warns and, when no
    explicit fold_codec is given, maps True -> "bitmap" / False -> "list".
    """
    if fold_bitmap is not None:
        warnings.warn(
            "fold_bitmap is deprecated; spell the wire format as "
            "BFSConfig(fold_codec='bitmap') (or fold_codec='bitmap' on the "
            "driver shims)", DeprecationWarning, stacklevel=3)
        if fold_codec is None:
            fold_codec = "bitmap" if fold_bitmap else "list"
    return "list" if fold_codec is None else fold_codec


@dataclasses.dataclass(frozen=True)
class BFSConfig:
    """All knobs of a BFS query plan.

    grid:        Grid2D | (R, C) | "RxC" | None.  None derives 1 x D from the
                 bound mesh (or all local devices) at planning time.
    fold_codec:  "list" | "bitmap" | "delta" | FoldCodec instance -- the fold
                 wire format (DESIGN.md sec. 4).
    edge_chunk:  CSC scan chunk size of the expand phase.
    dedup:       winner-selection method ("scatter" | "sort").
    max_levels:  level-loop bound.  None (the default) searches to
                 completion: the engine bounds the loop by the planned
                 grid's n + 1, which no search reaches, so every level and
                 parent is exact on a graph of any depth.  An int stops
                 the search after that many levels (deeper vertices stay
                 at level -1).  Either way the per-level records -- the
                 `LevelTrace` channels and the direction program's
                 `directions` -- hold `repro.obs.trace.RECORDED_LEVELS`
                 (64) levels; levels past it fold into the last slot.
    direction:   Beamer direction optimisation.  False = pure top-down;
                 True or "adaptive" = per-level alpha/beta switch inside the
                 compiled loop; "bottomup" = every level bottom-up (the
                 benchmark sweep's fixed arm).  Any non-False spelling plans
                 the CSR twin lazily on first use.  Outputs are
                 bit-identical to top-down in every mode.
    alpha:       adaptive switch ENTRY threshold (bottom-up when the global
                 frontier exceeds n / alpha).
    beta:        adaptive switch EXIT threshold (back to top-down once the
                 frontier falls below n / beta; beta > alpha gives the
                 hysteresis band that stops boundary thrash).
    row_axes /
    col_axes:    mesh axes the processor grid's rows/columns span.
    exchange:    fold exchange strategy (DESIGN.md sec. 14): "flat" (one
                 all_to_all per fold -- every column sends C-1 direct
                 messages), "butterfly" (log2(C) pairwise ppermute stages
                 over the XOR hypercube -- log2(C) messages per column at
                 (C/2)*log2(C) payload volume), or "auto" (butterfly
                 whenever it strictly reduces message count: power-of-two
                 C >= 4 on a single column axis; flat otherwise).  "auto"
                 is normalised to the resolved name when a session binds
                 the config to a planned grid, so the AOT caches key on the
                 concrete strategy.  Outputs are bit-identical across
                 strategies for every codec and program.
    telemetry:   per-level trace channel (DESIGN.md sec. 13).  When True,
                 every search also returns a `repro.obs.LevelTrace` (per
                 level: global + per-device frontier counts, scanned edges,
                 folded entries, fold wire bytes, direction), readable as
                 `output.trace` / `GraphSession.last_trace()`.  Static: it
                 participates in every engine/AOT cache key, so the off
                 path compiles to exactly the untraced program.  Outputs
                 are bit-identical either way.
    fault_tolerance:  mid-traversal recovery (DESIGN.md sec. 15).  When
                 True, sessions run the level loop in checkpoint-bounded
                 segments (`ckpt_every` levels per jitted segment) so a
                 traversal can snapshot its carry between segments, survive
                 injected device loss, and resume -- same grid or shrunken.
                 Static and cache-keyed like `telemetry`: the off path
                 builds exactly the single-while_loop program, and segmented
                 outputs are bit-identical to it.
    ckpt_every:  levels per resumable segment when fault_tolerance=True
                 (the K of "checkpoint every K levels").
    """
    grid: Any = None
    fold_codec: Any = "list"
    edge_chunk: int = 8192
    dedup: str = "scatter"
    max_levels: int | None = None
    direction: Any = False
    alpha: int = 24
    beta: int = 64
    row_axes: tuple = ("r",)
    col_axes: tuple = ("c",)
    exchange: str = "flat"
    telemetry: bool = False
    fault_tolerance: bool = False
    ckpt_every: int = 1

    def __post_init__(self):
        for f in ("row_axes", "col_axes"):
            v = getattr(self, f)
            if v is not None and not isinstance(v, tuple):
                object.__setattr__(self, f, tuple(v))

    @property
    def codec_name(self) -> str:
        fc = self.fold_codec
        return fc if isinstance(fc, str) else getattr(fc, "name", repr(fc))

    @property
    def direction_mode(self):
        """The direction spelling normalised: None (pure top-down),
        "adaptive" or "bottomup"."""
        d = self.direction
        if d is False or d is None:
            return None
        if d is True:
            return "adaptive"
        if d in ("adaptive", "bottomup"):
            return d
        raise ValueError(
            f"direction={d!r}: expected False | True | 'adaptive' | "
            f"'bottomup'")

    @property
    def expand_path(self) -> str:
        """Always "reference": the scan has one implementation.  Kept, with
        `fold_path`, only because the benchmark's planning log line
        (`bench/harness.py`) prints both."""
        return "reference"

    @property
    def fold_path(self) -> str:
        """Always "reference" (see `expand_path`)."""
        return "reference"

    @property
    def exchange_name(self) -> str:
        """The exchange spelling as a hashable cache-key component ("auto"
        until `resolve_exchange` normalises it against a planned grid)."""
        ex = self.exchange
        return ex if isinstance(ex, str) else getattr(ex, "name", repr(ex))

    def resolve_exchange(self, grid) -> "BFSConfig":
        """This config with exchange="auto" resolved against the planned
        grid (butterfly on power-of-two C >= 4 over one column axis, flat
        otherwise) and an explicit strategy VALIDATED against it -- a
        butterfly request on a grid it cannot route raises the ValueError
        here, at session construction, naming the strategy that works."""
        from repro.dist.strategy import get_exchange

        strat = get_exchange(self.exchange, grid, self.col_axes or ())
        if isinstance(self.exchange, str) and self.exchange != strat.name:
            return dataclasses.replace(self, exchange=strat.name)
        return self

    @property
    def engine_key(self) -> tuple:
        """What makes two configs share one DistBFSEngine (and hence one
        AOT-compile cache line, together with graph shape and batch size).

        Uses the direction MODE, so the spellings of one mode share a key.
        `exchange` keys by name; exchange="auto" needs the planned grid to
        resolve, so `GraphSession` normalises it (via `resolve_exchange`)
        before any cache is keyed."""
        return (self.codec_name, self.direction_mode, self.edge_chunk,
                self.dedup, self.max_levels, self.alpha, self.beta,
                self.row_axes, self.col_axes, self.exchange_name,
                self.telemetry, self.fault_tolerance, self.ckpt_every)

    def algo_engine_key(self, program_key: tuple, codec_name: str,
                        max_levels: int | None) -> tuple:
        """Cache key for a non-BFS frontier-program engine (DESIGN.md
        sec. 8): the program's identity plus the config knobs the engine
        bakes in.  `codec_name`/`max_levels` are per-call (the program's
        codec hint / iteration bound may override the BFS spellings).
        Direction mode / alpha / beta ride in via `program_key` (the
        DirectionProgram wrapper's key)."""
        return ("algo", program_key, codec_name, self.edge_chunk, self.dedup,
                max_levels, self.row_axes, self.col_axes, self.exchange_name,
                self.telemetry, self.fault_tolerance, self.ckpt_every)

    def resolve_grid(self, n: int, mesh=None) -> Grid2D:
        """Concretise the `grid` spelling against n vertices (padding up)."""
        g = self.grid
        if isinstance(g, Grid2D):
            return g
        if g is None:
            if mesh is not None:
                sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
                R = C = 1
                for a in (self.row_axes or ()):
                    R *= sizes[a]
                for a in (self.col_axes or ()):
                    C *= sizes[a]
            else:
                import jax
                R, C = 1, jax.device_count()
        elif isinstance(g, str):
            R, C = (int(x) for x in g.lower().split("x"))
        else:
            R, C = g
        return Grid2D.for_vertices(n, R, C)
