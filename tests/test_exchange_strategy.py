"""Exchange-strategy layer coverage (DESIGN.md sec. 14).

  * flat vs butterfly routing delivers byte-identical received arrays for
    random payloads (property-tested over power-of-two C), hence identical
    fold outputs for EVERY codec (the wire arrays routed here are exactly
    the codecs' encoded messages);
  * "auto" resolution + validation rules (butterfly on power-of-two C >= 4
    over one column axis; explicit butterfly on an invalid grid raises a
    ValueError naming flat);
  * `BFSConfig.resolve_exchange` normalises "auto" at session construction
    and the resolved name participates in every engine/AOT cache key (no
    cross-strategy executable reuse, no retrace within a strategy);
  * the accounting formulas (msgs_per_exchange / wire_bytes /
    value_extra_bytes) behind the BENCH flat-vs-butterfly crossover.

The staged ppermute program itself is collective-counted in
tests/test_fold_codecs.py and EXECUTED (with cross-strategy bit-identity)
in tests/dist/run_multihost.py and the multi-device CI smokes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from repro.api import BFSConfig, DistGraph
from repro.core.types import Grid2D
from repro.dist import exchange as X
from repro.dist import strategy as ES
from repro.graphgen import rmat_edges


# ----------------------------------------------------------------------------
# Routing equality (the bit-identity contract, mesh-less)
# ----------------------------------------------------------------------------

def _route_both(x_all):
    return (ES.emulate_exchange(x_all, "flat"),
            ES.emulate_exchange(x_all, "butterfly"))


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 4), st.integers(1, 33), st.integers(0, 10_000))
def test_butterfly_routes_like_flat_property(logc, K, seed):
    """recv[j, m] = sent[m, j] for both strategies, byte for byte, at every
    power-of-two C (including the degenerate C=1 and C=2 single-stage)."""
    C = 1 << logc
    rng = np.random.default_rng(seed)
    x_all = rng.integers(-(1 << 31), 1 << 31, (C, C, K), np.int64) \
        .astype(np.int32)
    flat, bfly = _route_both(x_all)
    want = np.swapaxes(x_all, 0, 1)
    assert (flat == want).all()
    assert (bfly == want).all()
    assert flat.dtype == bfly.dtype == x_all.dtype


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 2), st.integers(1, 64), st.integers(0, 10_000))
def test_codec_wire_messages_route_identically(logc, S, seed):
    """For every fold codec: encode each column's buckets, route the
    encoded wire arrays through both strategies, and the received messages
    are byte-identical -- so decode (hence the whole fold) cannot differ.
    The butterfly is store-and-forward: codec payloads are re-fused into
    stage messages but never re-encoded."""
    C = 1 << logc
    rng = np.random.default_rng(seed)
    wires = {"list": [], "bitmap": [], "delta": []}
    for j in range(C):
        dst = np.full((C, S), -1, np.int32)
        cnts = []
        for m in range(C):
            k = int(rng.integers(0, S + 1))
            dst[m, :k] = np.sort(rng.choice(S, size=k, replace=False)) \
                + m * S
            cnts.append(k)
        ids, cnt = jnp.asarray(dst), jnp.asarray(cnts, jnp.int32)
        wires["list"].append(np.asarray(ids))
        wires["bitmap"].append(np.asarray(X.BitmapFold.encode(ids, cnt, S)))
        wires["delta"].append(np.asarray(X.DeltaFold.encode(ids, cnt, S)))
    for name, per_col in wires.items():
        x_all = np.stack(per_col)                # (C, C, ...) encoded wire
        x_flat = x_all.reshape(C, C, -1)
        flat, bfly = _route_both(x_flat)
        assert (flat == bfly).all(), name
        assert flat.dtype == bfly.dtype, name


# ----------------------------------------------------------------------------
# Resolution + validation rules
# ----------------------------------------------------------------------------

def _grid(R, C):
    return Grid2D.for_vertices(R * C * 8, R, C)


@pytest.mark.parametrize("C,want", [(1, "flat"), (2, "flat"), (3, "flat"),
                                    (4, "butterfly"), (6, "flat"),
                                    (8, "butterfly"), (16, "butterfly")])
def test_auto_resolution_rule(C, want):
    """auto = butterfly exactly when it strictly reduces message count:
    power-of-two C >= 4 (log2(C) < C-1) over a single column axis."""
    assert ES.resolve_exchange_name("auto", _grid(1, C), ("c",)) == want
    # multi-axis columns force flat regardless of C
    assert ES.resolve_exchange_name("auto", _grid(1, C),
                                    ("c1", "c2")) == "flat"


def test_explicit_butterfly_validation_errors_name_flat():
    with pytest.raises(ValueError, match="power-of-two.*flat"):
        ES.get_exchange("butterfly", _grid(1, 3), ("c",))
    with pytest.raises(ValueError, match="ONE column.*flat"):
        ES.get_exchange("butterfly", _grid(1, 4), ("c1", "c2"))
    with pytest.raises(ValueError, match="unknown exchange"):
        ES.get_exchange("hypercube", _grid(1, 4), ("c",))
    # instances validate too
    with pytest.raises(ValueError, match="flat"):
        ES.get_exchange(ES.ButterflyExchange(), _grid(1, 6), ("c",))
    assert ES.get_exchange("butterfly", _grid(1, 4), ("c",)).name \
        == "butterfly"
    assert ES.get_exchange("flat", _grid(1, 3), ("c",)).name == "flat"


def test_config_resolves_auto_and_keys_on_exchange():
    cfg = BFSConfig(exchange="auto")
    assert cfg.exchange_name == "auto"
    assert cfg.resolve_exchange(_grid(1, 4)).exchange == "butterfly"
    assert cfg.resolve_exchange(_grid(1, 2)).exchange == "flat"
    # a pinned strategy is validated (not rewritten) by resolve_exchange
    pinned = BFSConfig(exchange="butterfly")
    assert pinned.resolve_exchange(_grid(1, 4)).exchange == "butterfly"
    with pytest.raises(ValueError, match="flat"):
        pinned.resolve_exchange(_grid(1, 3))
    # the exchange name is part of both engine cache keys
    flat, bfly = BFSConfig(exchange="flat"), BFSConfig(exchange="butterfly")
    assert flat.engine_key != bfly.engine_key
    assert flat.algo_engine_key(("cc",), "bitmap", 10) \
        != bfly.algo_engine_key(("cc",), "bitmap", 10)


# ----------------------------------------------------------------------------
# Accounting (the BENCH crossover numbers)
# ----------------------------------------------------------------------------

def test_message_and_byte_accounting():
    flat, bfly = ES.FlatExchange(), ES.ButterflyExchange()
    # message counts: C-1 vs log2(C) -- equal at C=2, strictly fewer from 4
    assert [flat.msgs_per_exchange(c) for c in (1, 2, 4, 8)] == [0, 1, 3, 7]
    assert [bfly.msgs_per_exchange(c) for c in (1, 2, 4, 8)] == [0, 1, 2, 3]
    # set-fold bytes: flat ships C-1 of C buckets once; butterfly ships C/2
    # buckets log2(C) times -- equal at C=4, more volume from C=8
    fb = 800                                     # 8 buckets x 100 bytes
    assert flat.wire_bytes(fb, 8) == fb
    assert bfly.wire_bytes(fb, 8) == (fb // 8) * 4 * 3      # 1200 > 800
    assert bfly.wire_bytes(400, 4) == (400 // 4) * 2 * 2 == 400
    # value-channel bytes: flat = 4 per entry; butterfly = 4 per entry per
    # hop, hops = popcount(j ^ d) (own bucket never travels)
    cnt = jnp.asarray([5, 3, 2, 7], jnp.int32)
    assert int(flat.value_extra_bytes(cnt, jnp.int32(1), 4)) == 4 * 17
    hops = [bin(1 ^ d).count("1") for d in range(4)]        # j = 1
    want = 4 * sum(c * h for c, h in zip([5, 3, 2, 7], hops))
    assert int(bfly.value_extra_bytes(cnt, jnp.int32(1), 4)) == want
    assert hops[1] == 0                          # own bucket: zero hops


# ----------------------------------------------------------------------------
# AOT cache-key participation (no cross-strategy reuse, no retrace within)
# ----------------------------------------------------------------------------

def test_exchange_keys_aot_cache_no_cross_reuse():
    """Two sessions over ONE resident graph differing only in `exchange`
    get separate engines and separate compiled executables; within one
    strategy a repeat query hits the cache without retracing; outputs are
    bit-identical across strategies."""
    edges = np.asarray(rmat_edges(jax.random.key(2), 8, 8))
    g = DistGraph.from_edges(
        edges, BFSConfig(grid=(1, 1), edge_chunk=256),
        n=256)
    s_flat = g.session(BFSConfig(grid=(1, 1), edge_chunk=256,
                                 exchange="flat"))
    s_bfly = g.session(BFSConfig(grid=(1, 1), edge_chunk=256,
                                 exchange="butterfly"))
    assert s_flat.engine is not s_bfly.engine
    assert s_flat.engine.exchange.name == "flat"
    assert s_bfly.engine.exchange.name == "butterfly"

    out_f = s_flat.bfs(3)
    misses = g.cache_stats()["misses"]
    traces = s_bfly.engine.trace_count
    out_b = s_bfly.bfs(3)
    # the butterfly query could NOT reuse the flat executable
    assert g.cache_stats()["misses"] == misses + 1
    # ... and a repeat butterfly query hits without retracing
    out_b2 = s_bfly.bfs(3)
    assert g.cache_stats()["misses"] == misses + 1
    assert s_bfly.engine.trace_count == traces + 1
    for a, b in ((out_f, out_b), (out_b, out_b2)):
        assert (np.asarray(a.level) == np.asarray(b.level)).all()
        assert (np.asarray(a.pred) == np.asarray(b.pred)).all()
        assert a.edges_scanned == b.edges_scanned
