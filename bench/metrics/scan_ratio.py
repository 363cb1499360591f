"""Edges the program scanned over the directed edges of the searched
components: 1 for a top-down search, below 1 where bottom-up levels stop
early.  A count, so it repeats exactly."""


def read(run):
    scanned = [r["scanned"] for r in run.per_root]
    if not scanned or any(s is None for s in scanned):
        return None
    return sum(scanned) / sum(r["directed_edges"] for r in run.per_root)
