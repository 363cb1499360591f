"""Distributed-engine layer shared by every BFS driver (DESIGN.md sec. 6).

Layering:
  compat    -- the shard_map / make_mesh call sites
  topology  -- mesh + processor-grid geometry (1D = degenerate 1 x P grid)
  exchange  -- expand/fold collectives with pluggable fold wire codecs
  strategy  -- pluggable fold exchange routes (flat / butterfly)
  multihost -- process-group bootstrap + global-array placement
  engine    -- the level loop / init / deferred-pred resolution / accounting

Re-exports are PEP 562 LAZY: `jax.distributed.initialize` must run before
any JAX computation, and the engine chain materialises jnp constants at
import time -- so `from repro.dist import multihost` (the first thing a
multi-host worker does) must not drag the engine in eagerly.
"""
_EXPORTS = {
    "shard_map": "repro.dist.compat",
    "make_mesh": "repro.dist.compat",
    "Topology": "repro.dist.topology",
    "FOLD_CODECS": "repro.dist.exchange",
    "get_fold_codec": "repro.dist.exchange",
    "DistBFSEngine": "repro.dist.engine",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    target = _EXPORTS.get(name)
    if target is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib
    return getattr(importlib.import_module(target), name)
