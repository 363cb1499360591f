"""The batched BFS program compiled for a described TPU v5e host.

Nothing here runs on a chip.  `get_topology_desc` describes a v5e 2x2 host
and each test compiles `engine._run_batch` for it from ShapeDtypeStructs,
without planning a graph, so what the TPU compiler refuses fails here at no
chip time.  The paths compiled are the ones "auto" resolves to on TPU
(`repro.kernels.select.AUTO_PATH`).

The topology is described inside the module fixture and nowhere else: a
process that loads the TPU library keeps it, and its lock, until it exits,
so describing it at import, in a skipif or in conftest.py would leave the
other test workers without it.  The persistent compilation cache is off
around these compiles: a compile for a described chip is written to it but
cannot be read back without one.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api import BFSConfig
from repro.api.session import build_engine
from repro.core.types import Grid2D
from repro.dist.compat import make_mesh
from repro.dist.topology import Topology
from repro.kernels.select import AUTO_PATH

# chip_smoke.py's one-chip graph: R-MAT scale 20, edge factor 16,
# symmetrised to 2^25 directed edges, all of them on the 1x1 grid's device
SMOKE_SCALE, SMOKE_EDGES = 20, 2 ** 25
# the four-chip grids compile at a small scale: their point is the collectives
MULTI_SCALE = 12
EDGE_CHUNK, B = 1 << 16, 8
V5E_HBM = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def compile_batch(topo, R, C, *, scale, e_max, direction=False,
                  exchange="flat"):
    """(resolved config, compiled `_run_batch`) for an R x C grid."""
    n = 1 << scale
    grid = Grid2D.for_vertices(n, R, C)
    mesh = make_mesh((R, C), ("r", "c"), devices=topo.devices[:R * C])
    path = AUTO_PATH["tpu"]
    config = BFSConfig(grid=grid, edge_chunk=EDGE_CHUNK, direction=direction,
                       exchange=exchange, expand=path, fold=path,
                       bottomup=path).resolve_exchange(grid)
    topology = Topology.for_grid(grid, mesh)
    engine = build_engine(topology, config)
    dev = NamedSharding(mesh, topology.dev_spec)

    def blocks(*shape):
        return jax.ShapeDtypeStruct((R, C) + shape, jnp.int32, sharding=dev)

    args = [blocks(grid.n_cols_local + 1), blocks(e_max), blocks()]
    if direction:
        args += [blocks(grid.n_rows_local + 1), blocks(e_max)]
    roots = jax.ShapeDtypeStruct((B,), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    return config, engine._run_batch.lower(*args, roots).compile()


@pytest.fixture(scope="module")
def one_chip(topo):
    """{direction: compiled 1x1 program at the chip smoke's shapes}, each
    compiled once for the tests below."""
    return {d: compile_batch(topo, 1, 1, scale=SMOKE_SCALE,
                             e_max=SMOKE_EDGES, direction=d)[1]
            for d in (False, True)}


@pytest.mark.parametrize("direction", [False, True],
                         ids=["topdown", "direction"])
def test_one_chip_smoke_shapes_compile(one_chip, direction):
    compiled = one_chip[direction]
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert need < V5E_HBM, mem
    assert "tpu_custom_call" not in compiled.as_text()   # no Pallas kernel


_GATHER = re.compile(r"=\s*\w+\[(\d+)\][^=]*\sgather\(.*op_name=\"([^\"]*)\"")


def lane_gathers(hlo: str, scope: str) -> int:
    """Gathers of one value per lane of a chunk under `scope`, outside the
    slot search's vmapped tile loop."""
    return sum(1 for m in map(_GATHER.search, hlo.splitlines())
               if m and int(m.group(1)) == EDGE_CHUNK
               and scope in m.group(2) and "vmap()" not in m.group(2))


def test_one_chip_map_gathers_one_value_per_lane(one_chip):
    """The top-down map picks each lane's frontier column and address base
    in the slot search: row_idx[addr] is its one per-lane gather.  The
    bottom-up scan's four (row_off[r], cumul[r], col_idx[addr], the
    frontier bit) are untouched."""
    topdown, direction = (one_chip[d].as_text() for d in (False, True))
    assert lane_gathers(topdown, "repro/expand/while/body/map/") == 1
    assert lane_gathers(direction, "repro/expand/bottomup/") == 4


def test_2x2_flat_folds_with_all_to_all(topo):
    E = 2 * 16 << MULTI_SCALE
    config, compiled = compile_batch(topo, 2, 2, scale=MULTI_SCALE,
                                     e_max=E // 4 + E // 16)
    assert config.exchange == "flat"
    hlo = compiled.as_text()
    assert "all-to-all" in hlo and "collective-permute" not in hlo


def test_1x4_butterfly_folds_with_collective_permute(topo):
    E = 2 * 16 << MULTI_SCALE
    config, compiled = compile_batch(topo, 1, 4, scale=MULTI_SCALE,
                                     e_max=E // 4 + E // 16, exchange="auto")
    assert config.exchange == "butterfly"
    hlo = compiled.as_text()
    assert "collective-permute" in hlo and "all-to-all" not in hlo
