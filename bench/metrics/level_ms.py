"""Device busy time per iteration of the level loop in the traced
searches: `busy_s` over the iterations they ran.  A search runs its
answer's deepest level + 1 iterations (the last finds nothing), counted
from the levels copied out of the window's searches, so it reads what one
level costs whatever its frontier."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    iterations = sum(int(level.max()) + 1 for s in run.window.searches
                     for level in s.level)
    if iterations <= 0:
        return None
    return 1e3 * t.busy_s / iterations
