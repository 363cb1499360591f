"""Seconds from process start to the first timed search: runtime start,
graph generation, host planning, compile or cache load, one warm-up
search."""


def read(run):
    return run.setup_s
