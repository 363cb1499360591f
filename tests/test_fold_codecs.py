"""Fold wire-format coverage (DESIGN.md sec. 4 + 10).

  * pack/unpack bitmap round-trip at non-multiple-of-32 block sizes;
  * delta encode/decode round-trip (pure, no mesh);
  * level/pred equality across fold_codec in {list, bitmap, delta} on the
    same R-MAT graph (multi-device equality runs in tests/dist/);
  * wire-size ordering: bitmap < delta < list for one fold exchange;
  * ONE col_all_to_all per fold (and per value-fold) per level, counted on
    the traced jaxpr of every program x codec (the single-message gate);
  * the Pallas fold kernels (prefix-sum compaction, bitmap pack/unpack,
    delta encode/decode) bit-identical to the reference jnp formulas,
    property-tested incl. S not divisible by 32 and empty/full buckets;
  * fold-path selection rules (REPRO_FOLD, resolved engine cache keys) and
    the delta S > 65536 error surfacing through GraphSession/BFSConfig;
  * the compat shim is the only module touching the version-specific
    shard_map / AxisType jax API surface.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.api import BFSConfig, DistGraph
from repro.core import frontier as F
from repro.core import Grid2D, partition_2d, bfs_reference_py, validate_bfs
from repro.core.bfs2d import BFS2D
from repro.core.types import LocalGraph2D
from repro.dist import exchange as X
from repro.dist.compat import make_mesh
from repro.graphgen import rmat_edges, build_csc
from repro.kernels.select import FOLD_ENV, resolve_fold_path


@pytest.fixture(scope="module")
def fold_ops():
    """The Pallas fold-kernel bundle in interpret mode (CPU-runnable)."""
    from repro.kernels import make_fold_ops

    return make_fold_ops(path="pallas-interpret")


@pytest.mark.parametrize("S", [1, 7, 31, 32, 33, 63, 64, 65, 96, 127])
def test_pack_bitmap_roundtrip_odd_sizes(S):
    rng = np.random.default_rng(S)
    m = rng.random((4, S)) < 0.4
    packed = F.pack_bitmap(jnp.asarray(m))
    assert packed.dtype == jnp.uint32
    assert packed.shape == (4, (S + 31) // 32)
    got = np.asarray(F.unpack_bitmap(packed, S))
    assert got.shape == m.shape
    assert (got == m).all()


def test_pack_bitmap_pad_bits_are_zero():
    m = jnp.ones((1, 33), bool)                  # 31 pad bits in word 2
    packed = np.asarray(F.pack_bitmap(m))
    assert packed[0, 0] == 0xFFFFFFFF and packed[0, 1] == 1


def test_delta_codec_pure_roundtrip():
    """encode -> decode recovers each bucket's id set, sorted ascending."""
    S, C, j = 64, 4, 2
    rng = np.random.default_rng(0)
    dst = np.full((C, S), -1, np.int32)
    cnts = []
    for m in range(C):
        k = rng.integers(0, S + 1)
        t = rng.choice(S, size=k, replace=False)
        dst[m, :k] = m * S + t                   # unsorted local-row ids
        cnts.append(k)
    cnt = jnp.asarray(cnts, jnp.int32)
    gaps = X.DeltaFold.encode(jnp.asarray(dst), cnt, S)
    assert gaps.dtype == jnp.uint16
    # pretend every bucket was received by column j (sender-agnostic wire)
    verts, out_cnt = X.DeltaFold.decode(gaps, cnt, jnp.int32(j), S)
    verts = np.asarray(verts)
    for m in range(C):
        want = np.sort(dst[m, :cnts[m]] % S) + j * S
        assert (verts[m, :cnts[m]] == want).all()
        assert (verts[m, cnts[m]:] == -1).all()
    assert (np.asarray(out_cnt) == np.asarray(cnt)).all()


def test_delta_codec_rejects_wide_blocks():
    with pytest.raises(ValueError):
        X.get_fold_codec("delta", Grid2D(1, 1, 1 << 17))


def test_wire_bytes_ordering():
    grid = Grid2D(2, 4, 1 << 12)
    b = {name: X.get_fold_codec(name, grid).wire_bytes(grid)
         for name in X.FOLD_CODECS}
    assert b["bitmap"] < b["delta"] < b["list"]
    assert b["delta"] <= b["list"] // 2 + 4 * grid.C   # 16- vs 32-bit payload


def test_fold_codecs_identical_levels_and_preds():
    """Acceptance: delta == list (== bitmap) on an R-MAT graph, bit-exact."""
    scale, ef, root = 10, 8, 3
    edges = rmat_edges(jax.random.key(1), scale, ef)
    n = 1 << scale
    co, ri = build_csc(edges, n)
    ref, _ = bfs_reference_py(co, ri, root, n)
    mesh = make_mesh((1, 1), ("r", "c"))
    grid = Grid2D.for_vertices(n, 1, 1)
    lg = partition_2d(np.asarray(edges), grid)
    g = LocalGraph2D(jnp.asarray(lg.col_off), jnp.asarray(lg.row_idx),
                     jnp.asarray(lg.nnz))
    outs = {}
    for codec in ("list", "bitmap", "delta"):
        out = BFS2D(grid, mesh, edge_chunk=4096, fold_codec=codec).run(g, root)
        assert (np.asarray(out.level)[:n] == ref).all(), codec
        validate_bfs(np.asarray(edges), np.asarray(out.level)[:n],
                     np.asarray(out.pred)[:n], root)
        outs[codec] = out
    for codec in ("bitmap", "delta"):
        assert (np.asarray(outs[codec].level) ==
                np.asarray(outs["list"].level)).all(), codec
        assert (np.asarray(outs[codec].pred) ==
                np.asarray(outs["list"].pred)).all(), codec
        assert outs[codec].edges_scanned == outs["list"].edges_scanned


# ----------------------------------------------------------------------------
# Wire-format roundtrips at the frontier-density extremes (satellite: empty,
# full and single-vertex frontiers across list/bitmap/delta).  The exchange
# is emulated without a mesh: each row of the canonical bucket array plays
# the part of one sender's bucket for column j, exactly what the receiver
# sees after the all_to_all.
# ----------------------------------------------------------------------------

I32_MAX = int(np.iinfo(np.int32).max)


def _canonical_buckets(subsets, vals_rng, S, j):
    """Per-sender subsets of [0, S) -> canonical (ids, cnt, vals) arrays
    (ascending, front-packed, id = j*S + t) as `algos.program.pack_blocks`
    produces them."""
    C = len(subsets)
    ids = np.full((C, S), -1, np.int32)
    vals = np.full((C, S), I32_MAX, np.int32)
    cnt = np.zeros((C,), np.int32)
    for m, T in enumerate(subsets):
        T = np.sort(np.asarray(sorted(T), dtype=np.int32))
        ids[m, :len(T)] = j * S + T
        vals[m, :len(T)] = vals_rng.integers(0, 1 << 30, size=len(T))
        cnt[m] = len(T)
    return jnp.asarray(ids), jnp.asarray(cnt), jnp.asarray(vals)


def _emulate_fold_values(codec_name, ids, cnt, vals, S, j, ops=None):
    """Receiver-side (ids, cnt, vals) for one emulated fold exchange."""
    if codec_name == "list":
        return np.asarray(ids), np.asarray(cnt), np.asarray(vals)
    if codec_name == "bitmap":
        words = X.BitmapFold.encode(ids, cnt, S, ops)
        ri, rc = X.BitmapFold.decode(words, jnp.int32(j), S, ops)
        return np.asarray(ri), np.asarray(rc), np.asarray(vals)
    gaps = X.DeltaFold.encode(ids, cnt, S, ops)
    assert gaps.dtype == jnp.uint16
    ri, rc = X.DeltaFold.decode(gaps, cnt, jnp.int32(j), S, ops)
    return np.asarray(ri), np.asarray(rc), np.asarray(vals)


def _assert_roundtrip(subsets, S, j, seed=0, ops=None):
    ids, cnt, vals = _canonical_buckets(subsets, np.random.default_rng(seed),
                                        S, j)
    got = {c: _emulate_fold_values(c, ids, cnt, vals, S, j, ops)
           for c in X.FOLD_CODECS}
    for name, (ri, rc, rv) in got.items():
        assert (rc == np.asarray(cnt)).all(), name
        for m, T in enumerate(subsets):
            want = j * S + np.sort(np.asarray(sorted(T), dtype=np.int32))
            k = len(T)
            assert (ri[m, :k] == want).all(), (name, m)
            assert (ri[m, k:] == -1).all(), (name, m)
        # the values channel stays aligned with the delivered id order
        assert (rv == np.asarray(vals)).all(), name


@pytest.mark.parametrize("path", ["reference", "pallas-interpret"])
@pytest.mark.parametrize("S", [1, 32, 33, 64])
@pytest.mark.parametrize("kind", ["empty", "single", "full", "mixed"])
def test_fold_values_roundtrip_extremes(S, kind, path, request):
    """Deterministic coverage of the density extremes (runs with or without
    hypothesis): empty frontier, single-vertex frontier, full frontier --
    on both the reference formulas and the Pallas fold kernels."""
    ops = request.getfixturevalue("fold_ops") if path != "reference" else None
    C, j = 4, 2
    rng = np.random.default_rng(S)
    if kind == "empty":
        subsets = [set() for _ in range(C)]
    elif kind == "single":
        subsets = [{int(rng.integers(0, S))} for _ in range(C)]
    elif kind == "full":
        subsets = [set(range(S)) for _ in range(C)]
    else:   # one empty, one single, one full, one random
        subsets = [set(), {int(rng.integers(0, S))}, set(range(S)),
                   set(rng.choice(S, size=int(rng.integers(0, S + 1)),
                                  replace=False).tolist())]
    _assert_roundtrip(subsets, S, j, seed=S, ops=ops)


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 96), st.integers(0, 3), st.integers(0, 10_000))
def test_fold_values_roundtrip_property(S, j, seed):
    """Random per-sender subsets: every codec delivers the identical
    canonical (ids, cnt) set and keeps the values channel aligned."""
    rng = np.random.default_rng(seed)
    C = j + 1 + int(rng.integers(0, 3))
    subsets = [set(rng.choice(S, size=int(rng.integers(0, S + 1)),
                              replace=False).tolist()) for _ in range(C)]
    _assert_roundtrip(subsets, S, j, seed=seed)


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 80), st.integers(0, 10_000))
def test_set_fold_encode_decode_property(S, seed):
    """The plain (set-only) bitmap/delta encode/decode pair recovers each
    bucket's id set sorted ascending, at any density including 0 and S."""
    rng = np.random.default_rng(seed)
    C, j = 3, 1
    dst = np.full((C, S), -1, np.int32)
    cnts = []
    for m in range(C):
        k = int(rng.integers(0, S + 1))
        t = rng.choice(S, size=k, replace=False)
        dst[m, :k] = j * S + t       # unsorted, as expand produces them
        cnts.append(k)
    cnt = jnp.asarray(cnts, jnp.int32)
    for name in ("bitmap", "delta"):
        if name == "bitmap":
            ri, rc = X.BitmapFold.decode(
                X.BitmapFold.encode(jnp.asarray(dst), cnt, S), jnp.int32(j),
                S)
        else:
            ri, rc = X.DeltaFold.decode(
                X.DeltaFold.encode(jnp.asarray(dst), cnt, S), cnt,
                jnp.int32(j), S)
        ri = np.asarray(ri)
        assert (np.asarray(rc) == np.asarray(cnt)).all(), name
        for m in range(C):
            want = np.sort(dst[m, :cnts[m]])
            assert (ri[m, :cnts[m]] == want).all(), (name, m)
            assert (ri[m, cnts[m]:] == -1).all(), (name, m)


# ----------------------------------------------------------------------------
# Pallas fold kernels (DESIGN.md sec. 10): property-tested bit-identity of
# the prefix-sum compaction, bitmap pack/unpack and delta encode/decode
# against the reference jnp formulas, incl. S not divisible by 32 and
# empty/full buckets.
# ----------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.integers(1, 4), st.integers(1, 97), st.integers(0, 10_000))
def test_compact_rows_matches_argsort_property(N, S, seed):
    """The rank-select compaction kernel front-packs exactly like the
    reference stable-argsort path, at any density including 0 and S."""
    from repro.kernels import make_fold_ops

    ops = make_fold_ops(path="pallas-interpret")
    rng = np.random.default_rng(seed)
    density = rng.choice([0.0, 0.25, 0.75, 1.0])
    mask = rng.random((N, S)) < density
    a = rng.integers(-5, 1 << 30, (N, S)).astype(np.int32)
    b = rng.integers(-5, 1 << 30, (N, S)).astype(np.int32)
    (pa, pb), cnt = ops.compact_rows(mask, (a, b), (-1, 7))
    pa, pb, cnt = np.asarray(pa), np.asarray(pb), np.asarray(cnt)
    for r in range(N):
        va, vb = a[r][mask[r]], b[r][mask[r]]
        k = len(va)
        assert cnt[r] == k
        assert (pa[r, :k] == va).all() and (pa[r, k:] == -1).all()
        assert (pb[r, :k] == vb).all() and (pb[r, k:] == 7).all()


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 97), st.integers(0, 10_000))
def test_fold_kernel_bitmap_roundtrip_property(S, seed):
    """pack_bits/unpack_bits == pack_bitmap/unpack_bitmap bit for bit at
    any S (incl. not divisible by 32); roundtrip recovers the mask."""
    from repro.kernels import make_fold_ops

    ops = make_fold_ops(path="pallas-interpret")
    rng = np.random.default_rng(seed)
    mask = rng.random((3, S)) < rng.choice([0.0, 0.3, 1.0])
    words = ops.pack_bits(jnp.asarray(mask))
    ref = F.pack_bitmap(jnp.asarray(mask))
    assert words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(words), np.asarray(ref))
    back = ops.unpack_bits(words, S)
    np.testing.assert_array_equal(np.asarray(back), mask)
    np.testing.assert_array_equal(np.asarray(F.unpack_bitmap(ref, S)), mask)


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 80), st.integers(0, 10_000))
def test_fold_kernel_delta_roundtrip_property(S, seed):
    """Kernel delta encode/decode == the reference formulas on random
    buckets at any density (empty and full included), and the decode
    recovers each bucket's sorted id set."""
    from repro.kernels import make_fold_ops

    ops = make_fold_ops(path="pallas-interpret")
    rng = np.random.default_rng(seed)
    C, j = 3, 1
    dst = np.full((C, S), -1, np.int32)
    cnts = []
    for m in range(C):
        k = int(rng.integers(0, S + 1)) if m else rng.choice([0, S])
        t = rng.choice(S, size=k, replace=False)
        dst[m, :k] = j * S + t
        cnts.append(k)
    cnt = jnp.asarray(cnts, jnp.int32)
    g_ref = X.DeltaFold.encode(jnp.asarray(dst), cnt, S)
    g_ker = X.DeltaFold.encode(jnp.asarray(dst), cnt, S, ops)
    assert g_ker.dtype == jnp.uint16
    np.testing.assert_array_equal(np.asarray(g_ker), np.asarray(g_ref))
    r_ref, _ = X.DeltaFold.decode(g_ref, cnt, jnp.int32(j), S)
    r_ker, _ = X.DeltaFold.decode(g_ker, cnt, jnp.int32(j), S, ops)
    np.testing.assert_array_equal(np.asarray(r_ker), np.asarray(r_ref))
    for m in range(C):
        want = np.sort(dst[m, :cnts[m]])
        assert (np.asarray(r_ker)[m, :cnts[m]] == want).all()


def test_fold_kernel_program_helpers_match(fold_ops, rng):
    """pack_blocks / owned_to_front / compact_blocks / expand_exchange_values
    compaction: kernel path == reference path on the same inputs."""
    from repro.algos import program as PR

    grid = Grid2D(1, 4, 4 * 33)                 # S = 33: not a word multiple
    S, C = grid.S, grid.C
    improved = rng.random(C * S) < 0.3
    vals = rng.integers(0, 1 << 20, C * S).astype(np.int32)
    a = PR.pack_blocks(jnp.asarray(improved), jnp.asarray(vals), grid)
    b = PR.pack_blocks(jnp.asarray(improved), jnp.asarray(vals), grid,
                       ops=fold_ops)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    changed = rng.random(S) < 0.4
    ov = rng.integers(0, 1 << 20, S).astype(np.int32)
    a = PR.owned_to_front(jnp.asarray(changed), jnp.asarray(ov), 2, S)
    b = PR.owned_to_front(jnp.asarray(changed), jnp.asarray(ov), 2, S,
                          ops=fold_ops)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    blocks = rng.integers(0, 100, (3, 7)).astype(np.int32)
    cnts = rng.integers(0, 8, 3).astype(np.int32)
    a = F.compact_blocks(jnp.asarray(blocks), jnp.asarray(cnts))
    b = F.compact_blocks(jnp.asarray(blocks), jnp.asarray(cnts),
                         ops=fold_ops)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert int(a[1]) == int(b[1])


# ----------------------------------------------------------------------------
# The single-message gate: ONE col_all_to_all per fold per level, counted on
# the traced jaxpr of the whole engine program (acceptance criterion).
# ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def _collectives_graph():
    edges = np.asarray(rmat_edges(jax.random.key(5), 8, 8))
    w = np.random.default_rng(0).integers(1, 256, size=edges.shape[1]) \
        .astype(np.uint8)
    return DistGraph.from_edges(
        edges, BFSConfig(grid=(1, 1), edge_chunk=256, expand="reference",
                         fold="reference"), n=256, weights=w)


@pytest.mark.parametrize("codec", ["list", "bitmap", "delta"])
def test_one_all_to_all_per_fold(_collectives_graph, codec):
    """A whole BFS program contains exactly TWO all_to_all collectives (one
    fused fold in the level loop + the final resolve_preds), and each value
    program exactly ONE -- for every codec.  The pre-overhaul layouts (a
    separate count collective, a dense value-channel collective) would show
    3-4 here."""
    from repro.algos import (ConnectedComponentsProgram,
                             MultiSourceBFSProgram, SSSPProgram)

    g = _collectives_graph
    cs = g.csc
    sess = g.session(BFSConfig(grid=(1, 1), edge_chunk=256, fold_codec=codec,
                               expand="reference", fold="reference"))
    jx = str(jax.make_jaxpr(sess.engine._run.__wrapped__)(
        cs.col_off, cs.row_idx, cs.nnz, jnp.int32(0)))
    assert jx.count("all_to_all") == 2, codec
    for program, extra in ((ConnectedComponentsProgram(), ()),
                           (SSSPProgram(), (g.weights,)),
                           (MultiSourceBFSProgram(), ())):
        eng, _ = sess._algo_engine(program, codec, 8)
        arg = jnp.zeros((3,), jnp.int32) \
            if program.name == "multi_bfs" else jnp.int32(0)
        jx = str(jax.make_jaxpr(eng._run.__wrapped__)(
            cs.col_off, cs.row_idx, cs.nnz, *extra, arg))
        assert jx.count("all_to_all") == 1, (codec, program.name)


@pytest.fixture(scope="module")
def _butterfly_graph():
    """A 1x4 grid on a DUPLICATE-device mesh: the same single CPU device in
    every slot traces shard_map collectives fine (the program is only ever
    `make_jaxpr`-traced here, never executed), which lets the C=4 butterfly
    lower without --xla_force_host_platform_device_count.  No array can be
    placed on such a mesh, so the partition stays host-side: tracing needs
    only its shapes."""
    from repro.core.partition import partition_edge_vals
    from repro.dist.compat import make_mesh as mk
    from repro.dist.topology import Topology

    dev = jax.devices()[0]
    fake = mk((1, 4), ("r", "c"), devices=[dev] * 4)
    edges = np.asarray(rmat_edges(jax.random.key(5), 8, 8))
    w = np.random.default_rng(0).integers(1, 256, size=edges.shape[1]) \
        .astype(np.uint8)
    grid = Grid2D.for_vertices(256, 1, 4)
    lg = partition_2d(edges, grid)
    return DistGraph(
        Topology.for_grid(grid, fake), lg, edges=edges, n=256,
        weights=partition_edge_vals(edges, w, grid),
        config=BFSConfig(grid=(1, 4), edge_chunk=256, expand="reference",
                         fold="reference"))


@pytest.mark.parametrize("codec", ["list", "bitmap", "delta"])
@pytest.mark.parametrize("exchange", ["flat", "butterfly"])
def test_exchange_collective_counts(_butterfly_graph, codec, exchange):
    """The exchange-strategy gate on the traced jaxpr at C=4: the flat
    route keeps exactly one all_to_all per fold (two for BFS: the level
    loop + resolve_preds) and zero ppermutes; the butterfly route replaces
    EVERY all_to_all with log2(C)=2 ppermute stages -- for every codec and
    every program."""
    from repro.algos import (ConnectedComponentsProgram,
                             MultiSourceBFSProgram, SSSPProgram)

    g = _butterfly_graph
    cs = g.csc
    sess = g.session(BFSConfig(grid=(1, 4), edge_chunk=256, fold_codec=codec,
                               expand="reference", fold="reference",
                               exchange=exchange))
    stages = 2                                   # log2(C) at C = 4
    jx = str(jax.make_jaxpr(sess.engine._run.__wrapped__)(
        cs.col_off, cs.row_idx, cs.nnz, jnp.int32(0)))
    want_a2a, want_pp = (2, 0) if exchange == "flat" else (0, 2 * stages)
    assert jx.count("all_to_all") == want_a2a, (exchange, codec)
    assert jx.count("ppermute") == want_pp, (exchange, codec)
    for program, extra in ((ConnectedComponentsProgram(), ()),
                           (SSSPProgram(), (g.weights,)),
                           (MultiSourceBFSProgram(), ())):
        eng, _ = sess._algo_engine(program, codec, 8)
        arg = jnp.zeros((3,), jnp.int32) \
            if program.name == "multi_bfs" else jnp.int32(0)
        jx = str(jax.make_jaxpr(eng._run.__wrapped__)(
            cs.col_off, cs.row_idx, cs.nnz, *extra, arg))
        want_a2a, want_pp = (1, 0) if exchange == "flat" else (0, stages)
        assert jx.count("all_to_all") == want_a2a, (exchange, codec,
                                                    program.name)
        assert jx.count("ppermute") == want_pp, (exchange, codec,
                                                 program.name)


# ----------------------------------------------------------------------------
# Fold-path selection rules, cache keys, engine parity, delta block-size
# error surfacing (DESIGN.md sec. 10)
# ----------------------------------------------------------------------------

def test_resolve_fold_path_rules(monkeypatch):
    monkeypatch.delenv(FOLD_ENV, raising=False)
    assert resolve_fold_path("reference") == "reference"
    assert resolve_fold_path("pallas-interpret") == "pallas-interpret"
    assert resolve_fold_path("auto", platform="cpu") == "reference"
    # the TPU compiler refuses the kernels: auto takes the jnp scan there,
    # an explicit "pallas" is passed through to the compiler, and the
    # interpreter is refused off CPU
    assert resolve_fold_path("auto", platform="tpu") == "reference"
    assert resolve_fold_path("pallas", platform="tpu") == "pallas"
    with pytest.raises(ValueError, match="CPU only"):
        resolve_fold_path("pallas-interpret", platform="tpu")
    assert resolve_fold_path(None, platform="gpu") == "pallas"
    monkeypatch.setenv(FOLD_ENV, "pallas-interpret")
    assert resolve_fold_path("auto", platform="cpu") == "pallas-interpret"
    with pytest.raises(ValueError, match=FOLD_ENV):
        resolve_fold_path("auto", platform="tpu")
    # explicit spellings are NOT overridden by the environment
    assert resolve_fold_path("reference") == "reference"
    monkeypatch.setenv(FOLD_ENV, "nonsense")
    with pytest.raises(ValueError, match="REPRO_FOLD"):
        resolve_fold_path("auto")
    monkeypatch.delenv(FOLD_ENV)
    with pytest.raises(ValueError, match="fold="):
        resolve_fold_path("zstd")


def test_config_keys_use_resolved_fold_path(monkeypatch):
    monkeypatch.delenv(FOLD_ENV, raising=False)
    ref = BFSConfig(fold="reference")
    pal = BFSConfig(fold="pallas-interpret")
    auto = BFSConfig()
    assert ref.engine_key != pal.engine_key
    expected = resolve_fold_path("auto")
    assert auto.fold_path == expected
    if expected == "reference":
        assert auto.engine_key == ref.engine_key
    monkeypatch.setenv(FOLD_ENV, "pallas-interpret")
    assert auto.fold_path == "pallas-interpret"
    assert auto.engine_key == pal.engine_key      # env re-keys "auto"
    k1 = auto.algo_engine_key(("cc",), "bitmap", 10)
    monkeypatch.delenv(FOLD_ENV)
    assert auto.algo_engine_key(("cc",), "bitmap", 10) != k1


@pytest.mark.parametrize("codec", ["list", "bitmap", "delta"])
def test_fold_paths_bit_identical_through_session(_collectives_graph, codec):
    """BFS + CC through the session: fold="pallas-interpret" ==
    fold="reference", bit for bit (levels, preds, labels, exact counters).
    The full program x codec x path matrix runs in the REPRO_FOLD CI leg."""
    g = _collectives_graph
    outs = {}
    for path in ("reference", "pallas-interpret"):
        s = g.session(BFSConfig(grid=(1, 1), edge_chunk=256,
                                fold_codec=codec, expand="reference",
                                fold=path))
        assert s.engine.fold_path == path
        assert (s.engine.fold_ops is None) == (path == "reference")
        out = s.bfs(jnp.asarray([3, 11], jnp.int32))
        cc = s.connected_components(fold_codec=codec)
        outs[path] = (np.asarray(out.level), np.asarray(out.pred),
                      out.edges_scanned, np.asarray(cc.labels),
                      cc.edges_scanned)
    a, b = outs["reference"], outs["pallas-interpret"]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_delta_block_size_error_names_working_codecs():
    """S > 65536 with fold_codec="delta" must fail at session/engine build
    with an error naming the codecs that DO work at that block size."""
    edges = np.array([[0, 1], [1, 2]])
    n = 1 << 17                                  # 1x1 grid -> S = 131072
    g = DistGraph.from_edges(
        edges, BFSConfig(grid=(1, 1), expand="reference"), n=n)
    with pytest.raises(ValueError) as ei:
        g.session(BFSConfig(grid=(1, 1), fold_codec="delta",
                            expand="reference"))
    msg = str(ei.value)
    assert "delta" in msg and "65536" in msg
    assert "bitmap" in msg and "list" in msg     # the codecs that DO work
    # and the working codecs really do build at this block size
    g.session(BFSConfig(grid=(1, 1), fold_codec="bitmap",
                        expand="reference"))


def test_compat_is_only_direct_importer():
    """No module outside dist/compat.py may touch the version-specific API."""
    root = os.path.join(os.path.dirname(__file__), "..")
    bad = re.compile(r"jax\.shard_map|jax\.experimental\.shard_map"
                     r"|from jax\.sharding import [^\n]*AxisType"
                     r"|jax\.sharding\.AxisType")
    offenders = []
    for base, _, files in os.walk(root):
        if any(part in base for part in
               (".git", ".pytest_cache", "__pycache__", "bench_out")):
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(base, fn)
            if path.endswith(os.path.join("dist", "compat.py")):
                continue
            with open(path) as f:
                if bad.search(f.read()):
                    offenders.append(os.path.relpath(path, root))
    assert not offenders, f"direct jax API use outside compat: {offenders}"
