"""In-program per-level traces (DESIGN.md sec. 13).

The paper's whole result is per-level numbers -- frontier sizes, exchanged
bytes, per-phase work.  `LevelTrace` makes those numbers a PRODUCT of the
production path instead of a bench-worker re-derivation: when a session's
`BFSConfig(telemetry=True)`, the `FrontierEngine` threads the per-level
carry built here through its `lax.while_loop` and appends the arrays to the
device outputs, and `assemble_traces` turns the gathered result into one
host `LevelTrace` per search.

Per level, per device, the carry records:

  frontier    global frontier count ENTERING the level (psum-replicated,
              the same total the direction heuristic consumes)
  front_dev   this device's own frontier count entering the level
  scanned     edges scanned this level on this device (the expand stamp)
  folded      entries this device folded to owners (the fold stamp)
  wire        fold wire bytes this device sent (the exchange stamp): the
              exchange strategy's scaling of the codec's static
              `wire_bytes(grid)` for set folds, plus the count-proportional
              value-channel bytes for value folds -- on the flat route this
              is exactly the PR 5 `wire_bytes_values_sent` accounting
  msgs        point-to-point fold messages this device sent (the exchange
              strategy's `msgs_per_exchange`: C-1 flat, log2(C) butterfly)
  dir         direction the level ran (0 top-down / 1 bottom-up)
  map_passes  window passes of the top-down map's slot search this level
              on this device (`frontier.slot_passes` summed over chunks;
              0 on bottom-up levels and in value programs): what picking
              slot values in the search costs

The stamps are work counters, not wall times: inside one compiled program
there is no host clock, and counters are what the paper's Fig. 5/6 plot
anyway; wall-clock spans live at the serve layer (`repro.obs.spans`).
Telemetry is OFF by default and keyed into every engine/AOT cache -- the
off path compiles to exactly the untraced program, and the traced outputs
are bit-identical to it (pure extra reductions, asserted in CI).
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Levels every per-level record holds: each trace channel here and the
# direction program's `dirs`.  The record's length is fixed, whatever the
# level loop's bound, so a search run to completion on a deep graph carries
# no n-long arrays: levels past the record fold into its last slot (each
# writes slot min(k, RECORDED_LEVELS - 1)), while the counter `k` keeps
# counting every level.
RECORDED_LEVELS = 64

# Channel order of the trace arrays the engine appends after (hi, lo);
# plus one trailing per-device level counter `k`.
TRACE_CHANNELS = ("frontier", "front_dev", "scanned", "folded", "wire",
                  "msgs", "dir", "map_passes")
N_TRACE_OUTS = len(TRACE_CHANNELS) + 1


# ----------------------------------------------------------------------------
# Device side: the while_loop carry (jnp imported lazily to keep this module
# importable by host-only tooling)
# ----------------------------------------------------------------------------

def init_trace() -> dict:
    """Fresh per-search trace carry (one per device, inside shard_map)."""
    import jax.numpy as jnp
    L = RECORDED_LEVELS
    return {
        "frontier": jnp.zeros((L,), jnp.int32),
        "front_dev": jnp.zeros((L,), jnp.int32),
        "scanned": jnp.zeros((L,), jnp.uint32),
        "folded": jnp.zeros((L,), jnp.int32),
        "wire": jnp.zeros((L,), jnp.uint32),
        "msgs": jnp.zeros((L,), jnp.int32),
        "dir": jnp.full((L,), -1, jnp.int32),
        "map_passes": jnp.zeros((L,), jnp.int32),
        "k": jnp.int32(0),
    }


def normalize_aux(aux: "dict | None") -> dict:
    """Fill the optional step-aux channel (legacy 3-tuple steps -> zeros)."""
    import jax.numpy as jnp
    aux = aux or {}
    return {
        "folded": jnp.asarray(aux.get("folded", 0), jnp.int32),
        "wire": jnp.asarray(aux.get("wire", 0), jnp.uint32),
        "msgs": jnp.asarray(aux.get("msgs", 0), jnp.int32),
        "dir": jnp.asarray(aux.get("dir", 0), jnp.int32),
        "map_passes": jnp.asarray(aux.get("map_passes", 0), jnp.int32),
    }


def record_level(tr: dict, *, frontier, front_dev, scanned, aux) -> dict:
    """Record one level at slot min(k, L-1); returns the advanced carry."""
    import jax.numpy as jnp
    L = tr["dir"].shape[0]
    k = jnp.minimum(tr["k"], L - 1)
    return {
        "frontier": tr["frontier"].at[k].set(
            jnp.asarray(frontier, jnp.int32)),
        "front_dev": tr["front_dev"].at[k].set(
            jnp.asarray(front_dev, jnp.int32)),
        "scanned": tr["scanned"].at[k].set(
            jnp.asarray(scanned, jnp.uint32)),
        "folded": tr["folded"].at[k].set(aux["folded"]),
        "wire": tr["wire"].at[k].set(aux["wire"]),
        "msgs": tr["msgs"].at[k].set(aux["msgs"]),
        "dir": tr["dir"].at[k].set(aux["dir"]),
        "map_passes": tr["map_passes"].at[k].set(aux["map_passes"]),
        "k": tr["k"] + 1,
    }


def trace_outputs(tr: dict) -> tuple:
    """The carry as the engine's extra device outputs (fixed order)."""
    return tuple(tr[c] for c in TRACE_CHANNELS) + (tr["k"],)


# ----------------------------------------------------------------------------
# Host side
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class LevelTrace:
    """One search's per-level telemetry, global + per-device.

    Arrays are truncated to the levels actually run, at most
    RECORDED_LEVELS (the last entry then holds the deepest level run);
    `*_dev` arrays carry a leading P = R*C device axis in vertex-block
    device order.
    """
    program: str
    codec: str
    grid: tuple                 # (R, C)
    n_levels: int
    frontier: np.ndarray        # (n_levels,) int64 global frontier entering
    frontier_dev: np.ndarray    # (P, n_levels) int64 per-device frontier
    scanned: np.ndarray         # (n_levels,) int64 global edges scanned
    scanned_dev: np.ndarray
    folded: np.ndarray          # (n_levels,) int64 global folded entries
    folded_dev: np.ndarray
    wire_bytes: np.ndarray      # (n_levels,) int64 global fold wire bytes
    wire_dev: np.ndarray
    msgs: np.ndarray            # (n_levels,) int64 global fold messages sent
    msgs_dev: np.ndarray
    direction: np.ndarray       # (n_levels,) int32: 0 top-down / 1 bottom-up
    map_passes: np.ndarray      # (n_levels,) int64 global map window passes
    map_passes_dev: np.ndarray

    @property
    def total_wire_bytes(self) -> int:
        return int(self.wire_bytes.sum())

    @property
    def total_msgs(self) -> int:
        return int(self.msgs.sum())

    @property
    def total_scanned(self) -> int:
        return int(self.scanned.sum())

    def levels(self) -> list:
        """Per-level dict rows (what benches/CI serialize)."""
        return [
            {"level": k, "frontier": int(self.frontier[k]),
             "scanned": int(self.scanned[k]),
             "folded": int(self.folded[k]),
             "wire_bytes": int(self.wire_bytes[k]),
             "msgs": int(self.msgs[k]),
             "dir": int(self.direction[k]),
             "map_passes": int(self.map_passes[k])}
            for k in range(self.n_levels)]

    def to_dict(self) -> dict:
        return {"program": self.program, "codec": self.codec,
                "grid": list(self.grid), "n_levels": self.n_levels,
                "levels": self.levels()}


def _one_trace(chans, k, *, grid, program, codec) -> LevelTrace:
    L = chans["dir"].shape[-1]
    n = min(int(k), L)
    i64 = np.int64
    f_dev = chans["front_dev"][:, :n].astype(i64)
    s_dev = chans["scanned"][:, :n].astype(i64)
    c_dev = chans["folded"][:, :n].astype(i64)
    w_dev = chans["wire"][:, :n].astype(i64)
    m_dev = chans["msgs"][:, :n].astype(i64)
    p_dev = chans["map_passes"][:, :n].astype(i64)
    return LevelTrace(
        program=program, codec=codec, grid=(grid.R, grid.C), n_levels=n,
        frontier=chans["frontier"][0, :n].astype(i64), frontier_dev=f_dev,
        scanned=s_dev.sum(axis=0), scanned_dev=s_dev,
        folded=c_dev.sum(axis=0), folded_dev=c_dev,
        wire_bytes=w_dev.sum(axis=0), wire_dev=w_dev,
        msgs=m_dev.sum(axis=0), msgs_dev=m_dev,
        direction=np.asarray(chans["dir"][0, :n], np.int32),
        map_passes=p_dev.sum(axis=0), map_passes_dev=p_dev)


def assemble_traces(traw, B, *, grid, program: str, codec: str):
    """Gathered trace outputs -> LevelTrace (B=None) or a tuple of B.

    `traw` is the engine's trailing N_TRACE_OUTS device outputs; every
    channel gathers to (R, C, [B,] RECORDED_LEVELS) and `k` to (R, C[, B]).
    `frontier`/`dir` are psum-replicated so device 0's row is global truth;
    the work channels are per-device and sum to the global figures.
    """
    arrs = [np.asarray(a) for a in traw[:-1]]
    kk = np.asarray(traw[-1])
    L = arrs[0].shape[-1]
    if B is None:
        chans = {c: a.reshape(-1, L)
                 for c, a in zip(TRACE_CHANNELS, arrs)}
        return _one_trace(chans, kk.reshape(-1)[0], grid=grid,
                          program=program, codec=codec)
    per_b = [{c: a.reshape(-1, B, L)[:, b, :]
              for c, a in zip(TRACE_CHANNELS, arrs)} for b in range(B)]
    ks = kk.reshape(-1, B)[0]
    return tuple(_one_trace(per_b[b], ks[b], grid=grid, program=program,
                            codec=codec) for b in range(B))
