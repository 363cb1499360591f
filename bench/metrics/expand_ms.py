"""Device time under the program's `repro/expand` scope per search,
averaged over the cell's chips.  In a direction-optimised session the
bottom-up scan runs under the same scope."""

SCOPE = "repro/expand"


def read(run):
    t = run.trace
    s = t.scope_s.get(SCOPE, 0.0) if t is not None else 0.0
    if s <= 0 or not run.per_root:
        return None
    return 1e3 * s / len(run.per_root)
