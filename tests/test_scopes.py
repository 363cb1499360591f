"""Every instruction of the compiled search program lies under exactly one
layer scope (DESIGN.md sec. 13).

The scopes (`jax.named_scope`) survive into each instruction's
`metadata={op_name=...}`, where the profiler's readers look them up: the
first `repro/` segment names the layer, so one layer scope must never
enclose another.  Sub-scopes of a layer are plain names (`repro/expand/
bottomup`).  What carries no `repro/` segment is the control flow's own
plumbing (the `lax.map` over roots, the `while_loop`s, a `cond`'s index)
and the parameters of reducer and comparator computations.
"""
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.api import BFSConfig, DistGraph
from repro.graphgen import rmat_edges

LAYERS = ("expand", "fold", "update", "loop", "finalize")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")
_PLUMBING = re.compile(
    r"^jit\(\w+\)(/(shard_map|while|body|cond|closed_call|branch_\d+_fun"
    r"|convert_element_type))*$")
SCALE, EF = 8, 8


def layer_counts(hlo: str) -> dict:
    """{first `repro/` segment: instructions}; asserts the scope rules."""
    counts = {}
    for line in hlo.splitlines():
        hit = _INSTR.match(line)
        if not hit:
            continue
        instr, op_name = hit.groups()
        layers = re.findall(r"repro/(\w+)", op_name)
        if not layers:
            assert "/" not in op_name or _PLUMBING.match(op_name), \
                f"{instr} lies under no layer scope: {op_name}"
            continue
        assert layers[0] in LAYERS, f"{instr}: {op_name}"
        assert not set(layers[1:]) & set(LAYERS), \
            f"{instr}: a layer scope inside another: {op_name}"
        counts[layers[0]] = counts.get(layers[0], 0) + 1
    return counts


@pytest.fixture(scope="module")
def edges():
    return np.asarray(rmat_edges(jax.random.key(0), SCALE, EF))


def program_text(edges, direction: bool) -> str:
    cfg = BFSConfig(grid=(1, 1), edge_chunk=512, direction=direction)
    sess = DistGraph.from_edges(edges, cfg, n=1 << SCALE).session()
    return sess.compiled_for(1).as_text()


def test_topdown_program_scopes(edges):
    counts = layer_counts(program_text(edges, False))
    assert {"expand", "update", "loop", "finalize"} <= set(counts)


def test_direction_program_scopes(edges):
    hlo = program_text(edges, True)
    counts = layer_counts(hlo)
    assert set(LAYERS) <= set(counts)
    assert "repro/expand/bottomup/" in hlo
    # the post-fold merge of the bottom-up step is the update's
    assert re.search(r"repro/update/[^\"]*take_along_axis", hlo)


def test_expand_sub_scopes(edges):
    hlo = program_text(edges, False)
    for sub in ("exchange", "workload", "map", "filter", "mark"):
        assert re.search(rf"repro/expand/(while/body/)?{sub}/", hlo), sub


def test_2x2_program_scopes(tmp_path):
    out = tmp_path / "2x2.hlo"
    script = os.path.join(os.path.dirname(__file__), "dist", "run_scopes.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, script, "2", "2", str(out)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    hlo = out.read_text()
    counts = layer_counts(hlo)
    assert set(LAYERS) <= set(counts)
    # the fold's all-to-all and the pred resolution's exchange are scoped
    assert re.search(r"all-to-all\(.*op_name=\"[^\"]*repro/fold/", hlo)
    assert re.search(r"all-to-all\(.*op_name=\"[^\"]*repro/finalize/", hlo)
