"""The program's own host spans in a profiler trace, and the device idle
time inside them.

The program marks its host work with `TraceAnnotation` spans under
`repro/`: `repro/session/bfs` around each search call, with the children
`repro/session/dispatch`, `repro/session/compile` (an executable cache
miss) and `repro/session/assemble` (the host waits for the outputs), and
`repro/plan/<phase>` while a graph is planned.  `trace_reduce` reads the
benchmark's own `bench/` spans and the device ops; this module adds the
program's spans beside them:

- `program_spans`: the (start, end, name) spans under `repro/`;
- `span_idle_s`: for each span name, the seconds inside its intervals in
  which the device ran nothing, clipped to the `bench/window` span and
  averaged over the chips;
- `idle_gaps`: the longest idle gaps of the window, each labelled by the
  innermost span, program or benchmark, around its middle.
"""
from __future__ import annotations

import collections

import numpy as np

import trace_reduce as T

PREFIX = "repro/"


def program_spans(path: str) -> list:
    """Sorted (start_ns, end_ns, name) of the host spans under `repro/`."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if T._device_id(plane.name) is not None:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    return sorted(spans)


def _window(trace: T.Trace) -> tuple:
    windows = [(s, e) for s, e, name in trace.spans if name == T.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {T.WINDOW_SPAN!r} span")
    return windows[0]


def _busy(trace: T.Trace, dev: int, lo: float, hi: float) -> np.ndarray:
    d = trace.devices.get(dev)
    if d is None:
        raise ValueError(f"the trace has no plane for TPU {dev}")
    keep = (d.ends > lo) & (d.starts < hi)
    return T.union(np.clip(d.starts[keep], lo, hi),
                   np.clip(d.ends[keep], lo, hi))


def span_idle_s(trace: T.Trace, spans: list, device_ids: list) -> dict:
    """{span name: device-idle seconds inside its intervals}, clipped to
    the window and averaged over the chips."""
    lo, hi = _window(trace)
    idle = collections.Counter()
    for dev in device_ids:
        u = _busy(trace, dev, lo, hi)
        for s, e, name in spans:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                idle[name] += (e - s) - T.covered(u, s, e)
    n = len(device_ids)
    return {name: v / n / 1e9 for name, v in idle.items()}


def idle_gaps(trace: T.Trace, spans: list, device_ids: list,
              k: int = T.GAPS_KEPT) -> list:
    """The `k` longest idle gaps of each chip in the window, longest first,
    as (label, seconds): the innermost span other than the window, program
    or benchmark, around the gap's middle."""
    lo, hi = _window(trace)
    every = sorted(trace.spans + spans)
    gaps = []
    for dev in device_ids:
        u = _busy(trace, dev, lo, hi)
        bounds = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
        length = bounds[:, 1] - bounds[:, 0]
        for g in np.argsort(-length)[:k]:
            if length[g] > 0:
                mid = (bounds[g, 0] + bounds[g, 1]) / 2
                gaps.append((T._label(every, mid), float(length[g]) / 1e9))
    return sorted(gaps, key=lambda g: -g[1])
