"""Graph500-counted traversed edges per second over the whole window.

The input edge tuples inside each searched component (counted by the
reference, never by the program), summed over every search of the window,
over the time from the window's start to the last answer on the host."""


def read(run):
    w = run.window
    if not run.per_root or w.t_end <= w.t_start:
        return None
    return sum(r["input_edges"] for r in run.per_root) / (w.t_end - w.t_start)
