"""Device time of the collectives per search, averaged over the cell's
chips: the self time of the op paths whose last step is a collective, as
JAX names it in the compiled `op_name` (`all_to_all`, `psum`,
`all_gather`, ...) or as HLO names an instruction that has none
(`all-reduce`, ...).  A TPU core runs one op at a time, so nothing but
the loop that holds a collective runs beside it: its self time is time
the chip spends exposed to the exchange.  A program that ran no
collective reads nothing.

`TraceSummary.collective_exposed_s` is not used: it finds collectives by
HLO opcode in the instruction's name, where the TPU program names them
after the JAX primitive (`all_to_all.36`, `psum.26`), and it counts the
`while` around a collective as overlap, so it reads next to nothing."""

import re

_COLLECTIVE = re.compile(
    r"(?:^|[/:])(?:all_to_all|all_gather|psum|psum_scatter|ppermute|"
    r"pmax|pmin|all-to-all|all-gather|all-reduce|collective-permute|"
    r"reduce-scatter)(?:-start|-done)?$")


def read(run):
    t = run.trace
    if t is None or not run.per_root:
        return None
    s = sum(sec for path, sec in t.op_s.items() if _COLLECTIVE.search(path))
    if s <= 0:
        return None
    return 1e3 * s / len(run.per_root)
