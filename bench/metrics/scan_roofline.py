"""Share of the HBM roofline that the top-down scan reaches.

The bytes a top-down search must move (`reference.graph500.topdown_bytes`,
from the graph and the reference levels alone) over the chips' peak HBM
bandwidth, divided by the device time under `repro/expand`.  BFS does no
arithmetic worth counting, so bandwidth bounds it.  A bottom-up search skips
edges, so the byte count does not hold for a direction-optimised session and
the reader leaves it out there."""

SCOPE = "repro/expand"


def read(run):
    t = run.trace
    if t is None or run.cell.config["session"].get("direction"):
        return None
    expand_s = t.scope_s.get(SCOPE, 0.0)
    if expand_s <= 0 or not run.per_root:
        return None
    need = sum(r["topdown_bytes"] for r in run.per_root)
    least_s = need / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / expand_s
