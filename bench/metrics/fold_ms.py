"""Device time under the program's `repro/fold` scope per search, averaged
over the cell's chips: on a grid, packing the discoveries into blocks and
the all-to-all along the processor row that delivers them."""

SCOPE = "repro/fold"


def read(run):
    t = run.trace
    s = t.scope_s.get(SCOPE, 0.0) if t is not None else 0.0
    if s <= 0 or not run.per_root:
        return None
    return 1e3 * s / len(run.per_root)
