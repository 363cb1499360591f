"""Device time of the bottom-up scan per search, averaged over the cell's
chips: the self time of the op paths under `repro/expand/bottomup`, a part
of `expand_ms`.  A program without that scope reads nothing.

It leaves out the loop XLA's gather expander makes of the scan's windowed
slot search (the vmapped gather of `edge_slots`): its `dynamic-slice`,
`constant_dynamic-slice_fusion` and `dynamic-update-slice` carry no
`op_name` and count as unscoped, though they run inside the while of
`repro/expand/bottomup/.../gather`."""

PATH = "repro/expand/bottomup"


def read(run):
    t = run.trace
    if t is None or not run.per_root:
        return None
    s = sum(sec for path, sec in t.op_s.items()
            if path == PATH or path.startswith(PATH + "/"))
    if s <= 0:
        return None
    return 1e3 * s / len(run.per_root)
