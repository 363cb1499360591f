"""Device time per search of the top-down expand's per-level prelude: the
self time of the op paths under `repro/expand/workload` (the frontier's
degrees, their prefix sum and the slot table the map searches), dense over
the gathered frontier slots on every level, a part of `expand_ms`.  A
program without that scope reads nothing."""

PATH = "repro/expand/workload"


def read(run):
    t = run.trace
    if t is None or not run.per_root:
        return None
    s = sum(sec for path, sec in t.op_s.items()
            if path == PATH or path.startswith(PATH + "/"))
    if s <= 0:
        return None
    return 1e3 * s / len(run.per_root)
