"""From a profiler trace to device busy time, time per scope, exposed
collective time, and the host's doing in each idle gap.

A TPU trace (`.xplane.pb`, read with `jax.profiler.ProfileData`) holds, for
each chip, a plane `/device:TPU:<id>` whose line "XLA Ops" has one event per
executed HLO instruction, named by the instruction's text
(`%fusion.12 = s32[...] fusion(...)`), and whose line "XLA Modules" has one
event per program run (`jit_counted(<fingerprint>)`).  The events carry no
scope, so the scope of an instruction is looked up in the program's own
compiled text, where `metadata={op_name=".../repro/expand/..."}` records the
`jax.named_scope` it was traced under.  The host plane `/host:CPU` holds the
benchmark's `TraceAnnotation` spans (`bench/window`, `bench/search`,
`bench/copy_out`) on the same clock.

Time is counted in three ways, on each chip:
- busy: the union of the intervals of the "XLA Ops" events;
- per scope and per op path: self time, an event's interval less the part
  that events nested inside it cover (a `while` contains its body's ops);
- exposed collective: the self time of collective instructions (all-gather,
  all-to-all, all-reduce, collective-permute, reduce-scatter) less the part
  that other instructions overlap.
Each is clipped to the `bench/window` span, and the figures of the chips
are averaged.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import re

import numpy as np

WINDOW_SPAN = "bench/window"
SPAN_PREFIX = "bench/"
GAPS_KEPT = 10
NO_SCOPE = "(no repro scope)"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")
_EVENT = re.compile(r"^%?([\w.\-]+)\s*=")
_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
_SCOPE = re.compile(r"(repro/[\w.\-]+)")
_NOISE = re.compile(r"(while/body/|while/cond/|closed_call/)")
_COLLECTIVE = re.compile(
    r"^(all-gather|all-to-all|all-reduce|collective-permute|reduce-scatter)"
    r"(-start|-done)?(\.\d+)?$")


def op_names(hlo_text: str) -> tuple[str, dict]:
    """(module name, {instruction: op_name}) of one compiled program's text."""
    m = _HLO_MODULE.search(hlo_text)
    names = {}
    for line in hlo_text.splitlines():
        hit = _INSTR.match(line)
        if hit:
            names[hit.group(1)] = hit.group(2)
    return (m.group(1) if m else ""), names


def scope_of(op_name: str) -> str:
    hit = _SCOPE.search(op_name)
    return hit.group(1) if hit else NO_SCOPE


def op_path(op_name: str) -> str:
    """An op's scope path, from its `repro/` scope on where it has one,
    without the control-flow steps (`while/body/`, `closed_call/`)."""
    hit = _SCOPE.search(op_name)
    path = op_name[hit.start():] if hit else op_name.split("/", 1)[-1]
    return _NOISE.sub("", path)


def is_collective(instr: str) -> bool:
    return bool(_COLLECTIVE.match(instr))


# ----------------------------------------------------------------------------
# Interval arithmetic on sorted (start, end) arrays
# ----------------------------------------------------------------------------

def union(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Disjoint, sorted (k, 2) intervals covering the given ones."""
    if starts.size == 0:
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    first = np.flatnonzero(np.r_[True, s[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, s.size - 1]
    return np.stack([s[first], reach[last]], axis=1)


def covered(intervals: np.ndarray, lo: float, hi: float) -> float:
    """Length of the part of [lo, hi] that disjoint intervals cover."""
    if intervals.size == 0:
        return 0.0
    s = np.clip(intervals[:, 0], lo, hi)
    e = np.clip(intervals[:, 1], lo, hi)
    return float(np.sum(e - s))


def overlap_each(starts, ends, intervals: np.ndarray) -> np.ndarray:
    """For each [start, end], the length that disjoint sorted intervals
    cover."""
    if intervals.size == 0 or starts.size == 0:
        return np.zeros(starts.size)
    lens = intervals[:, 1] - intervals[:, 0]
    before = np.concatenate([[0.0], np.cumsum(lens)])

    def upto(t):
        """Covered length of (-inf, t]."""
        k = np.searchsorted(intervals[:, 0], t, side="right")
        prev = np.maximum(k - 1, 0)
        part = np.clip(t - intervals[prev, 0], 0.0, lens[prev])
        return np.where(k > 0, before[prev] + part, 0.0)

    return upto(ends) - upto(starts)


def self_times(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Each event's duration less the time events nested in it take
    (events sorted by start; nesting as on one trace line)."""
    self_t = ends - starts
    stack = []
    for k in range(starts.size):
        s, e = starts[k], ends[k]
        while stack and ends[stack[-1]] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            self_t[parent] -= min(e, ends[parent]) - s
        stack.append(k)
    return self_t


# ----------------------------------------------------------------------------
# The trace
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceOps:
    starts: np.ndarray       # ns, sorted
    ends: np.ndarray
    instr: list              # instruction names
    module: list             # program (module) names, "" where unknown


@dataclasses.dataclass
class Trace:
    devices: dict            # device id -> DeviceOps
    spans: list              # (start, end, name) host spans "bench/..."


def _device_id(plane_name: str):
    hit = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return int(hit.group(1)) if hit else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        dev = _device_id(plane.name)
        if dev is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules = [(ev.start_ns, ev.end_ns, ev.name.split("(")[0])
                           for ev in line.events]
            elif line.name == "XLA Ops":
                ops = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events]
        ops.sort(key=lambda o: (o[0], -o[1]))   # a parent before its child
        modules.sort()
        starts = np.array([o[0] for o in ops], np.float64)
        ends = np.array([o[1] for o in ops], np.float64)
        instr = []
        for o in ops:
            hit = _EVENT.match(o[2])
            instr.append(hit.group(1) if hit else o[2])
        m_starts = np.array([m[0] for m in modules], np.float64)
        m_ends = np.array([m[1] for m in modules], np.float64)
        k = np.searchsorted(m_starts, starts, side="right") - 1
        inside = (k >= 0) & (starts < m_ends[np.maximum(k, 0)]) \
            if modules else np.zeros(starts.size, bool)
        module = [modules[kk][2] if ok else "" for kk, ok in zip(k, inside)]
        devices[dev] = DeviceOps(starts, ends, instr, module)
    return Trace(devices=devices, spans=sorted(spans))


def load_dir(trace_dir: str) -> Trace:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return load(paths[-1])


# ----------------------------------------------------------------------------
# The reduction
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                 # mean over the chips
    scope_s: dict                 # "repro/expand" -> s, mean over the chips
    op_s: dict                    # op path -> s, mean over the chips
    collective_s: float
    collective_exposed_s: float
    gaps: list                    # (span label, s), longest first

    def breakdown(self, k: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:k]]}


def _label(spans: list, t: float) -> str:
    """The innermost benchmark span (other than the window) around t."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and name != WINDOW_SPAN:
            if best is None or e - s < best[1] - best[0]:
                best = (s, e, name)
    return best[2] if best else "between benchmark spans"


def reduce(trace: Trace, device_ids: list, programs: dict) -> TraceSummary:
    """programs: {module name: {instruction: op_name}} for scope lookup."""
    windows = [(s, e) for s, e, name in trace.spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    busy, scope, ops = [], collections.Counter(), collections.Counter()
    coll, coll_exposed, gaps = [], [], []
    for dev in device_ids:
        d = trace.devices.get(dev)
        if d is None:
            raise ValueError(f"the trace has no plane for TPU {dev}")
        keep = (d.ends > lo) & (d.starts < hi)
        idx = np.flatnonzero(keep)
        s = np.clip(d.starts[idx], lo, hi)
        e = np.clip(d.ends[idx], lo, hi)
        u = union(s, e)
        busy.append(covered(u, lo, hi))
        st = self_times(s, e)
        is_coll = np.array([is_collective(d.instr[k]) for k in idx], bool)
        for j, k in enumerate(idx):
            names = programs.get(d.module[k], {})
            op_name = names.get(d.instr[k])
            if op_name is None:
                path = f"{d.module[k] or '?'}:{d.instr[k].split('.')[0]}"
                scope[NO_SCOPE] += st[j]
            else:
                path = op_path(op_name)
                scope[scope_of(op_name)] += st[j]
            ops[path] += st[j]
        other = union(s[~is_coll], e[~is_coll])
        cs, ce = s[is_coll], e[is_coll]
        coll.append(float(np.sum(st[is_coll])))
        coll_exposed.append(float(np.sum(
            np.maximum(st[is_coll] - overlap_each(cs, ce, other), 0.0))))
        bounds = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
        length = bounds[:, 1] - bounds[:, 0]
        for g in np.argsort(-length)[:GAPS_KEPT]:
            if length[g] > 0:
                mid = (bounds[g, 0] + bounds[g, 1]) / 2
                gaps.append((_label(trace.spans, mid), float(length[g])))
    n = len(device_ids)
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=float(np.mean(busy)) / 1e9,
        scope_s={k: v / n / 1e9 for k, v in scope.items()},
        op_s={k: v / n / 1e9 for k, v in ops.items()},
        collective_s=float(np.mean(coll)) / 1e9,
        collective_exposed_s=float(np.mean(coll_exposed)) / 1e9,
        gaps=[(name, g / 1e9) for name, g in gaps])
