"""Multi-host bootstrap and placement (DESIGN.md sec. 14).

One host stops at its PCIe root: scaling the processor grid past a single
machine needs (a) a process group whose devices form ONE global mesh and
(b) arrays placed as global `jax.Array`s so the engine's shard_map spans
every host.  This module is the whole multi-host surface:

  initialize()    `jax.distributed.initialize` plus the CPU-backend gloo
                  collectives switch (the CPU backend cannot run
                  multi-process collectives on its default implementation).
  global_mesh()   a mesh over `jax.devices()` -- ALL processes' devices in
                  process order, so every host constructs the identical
                  mesh deterministically.
  put_dev()       host (R, C, ...) array -> array sharded over the grid
                  axes: each device holds only its own block, and each
                  process materialises only its addressable shards (the
                  host copy must be identical on every process, which the
                  deterministic planner guarantees).
  put_replicated()  host scalar/vector -> fully-replicated array on the
                  mesh (search roots, source sets).
  fetch()         global array -> host numpy, `process_allgather`-ing the
                  non-addressable shards (identity in single-process runs).

Placement is the same in one process and in many: `DistGraph` and the
engine call these helpers unconditionally, so the graph lands block by block
on the mesh's devices and a query moves no graph data.  The two-process harness `tests/dist/run_multihost.py` drives a
real multi-host BFS/CC/SSSP through this module and asserts bit-identity
with the single-process reference.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import compat


def initialize(coordinator_address: str, num_processes: int,
               process_id: int, local_device_ids=None) -> None:
    """Join the process group (call ONCE, before any array lands on device).

    This flips the CPU-backend collectives implementation to gloo first:
    the default CPU collectives cannot run multi-process, and the switch
    must precede `jax.distributed.initialize`.  (Probing the backend here
    would itself initialize it -- too late -- so the flag is set blind; it
    only affects the CPU backend.)
    """
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass                       # newer jaxlibs pick a working default
    kw = {}
    if local_device_ids is not None:
        kw["local_device_ids"] = local_device_ids
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kw)


def global_mesh(axis_shapes, axis_names):
    """The deterministic global mesh: `jax.devices()` (all processes, in
    process order) reshaped to the grid axes.  Every process builds the
    same mesh, so NamedShardings agree across hosts by construction."""
    return compat.make_mesh(tuple(axis_shapes), tuple(axis_names),
                            devices=jax.devices())


def put_dev(x, mesh, spec: P):
    """Host array -> array sharded by `spec` over `mesh`.

    Every device receives only its own block, in a single process too, so
    the sharded program never moves graph data between devices.  In a
    process group every process holds the identical host copy and
    materialises only its addressable shards."""
    x = np.asarray(x)
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(x.shape, sharding,
                                        lambda idx: x[idx])


def put_replicated(x, mesh):
    """Host array -> array replicated over every device of `mesh` (search
    args)."""
    return put_dev(x, mesh, P())


def arg_aval(shape, dtype, mesh):
    """ShapeDtypeStruct for AOT-lowering a replicated search argument: it
    carries its sharding, so the executable binds the arrays
    `put_replicated` makes."""
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, P()))


def fetch(x):
    """Global array -> host value.  Identity when fully addressable (every
    single-process array); otherwise an all-gather of the remote shards so
    each process assembles the complete global output."""
    if getattr(x, "is_fully_addressable", True):
        return x
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(x, tiled=True)


def fetch_all(xs) -> tuple:
    """`fetch` over a tuple of outputs (the engine's assemble funnel)."""
    return tuple(fetch(x) for x in xs)
