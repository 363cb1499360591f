"""Device time under the program's `repro/loop` scope per search, averaged
over the cell's chips: the level loop's own control (initial state, the
exit test, the direction switch's predicate, the 64-bit edge count).  A
program without that scope reads nothing."""

SCOPE = "repro/loop"


def read(run):
    t = run.trace
    s = t.scope_s.get(SCOPE, 0.0) if t is not None else 0.0
    if s <= 0 or not run.per_root:
        return None
    return 1e3 * s / len(run.per_root)
