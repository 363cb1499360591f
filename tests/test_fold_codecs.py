"""Fold wire-format coverage (DESIGN.md sec. 4 + 10), against numpy
oracles.

  * pack/unpack bitmap round-trip at non-multiple-of-32 block sizes;
  * delta encode/decode round-trip (pure, no mesh);
  * level/pred equality across fold_codec in {list, bitmap, delta} on the
    same R-MAT graph (multi-device equality runs in tests/dist/);
  * wire-size ordering: bitmap < delta < list for one fold exchange;
  * ONE col_all_to_all per fold (and per value-fold) per level, counted on
    the traced jaxpr of every program x codec (the single-message gate);
  * the fold's compactions (`compact_blocks`, `pack_blocks`,
    `owned_to_front`), bitmap pack/unpack and delta encode/decode against
    numpy, property-tested incl. S not divisible by 32 and empty/full
    buckets;
  * BFS and CC through the session equal the host references under every
    codec, and the delta S > 65536 error surfaces through
    GraphSession/BFSConfig;
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


def _emulate_fold_values(codec_name, ids, cnt, vals, S, j):
    """Receiver-side (ids, cnt, vals) for one emulated fold exchange."""
    if codec_name == "list":
        return np.asarray(ids), np.asarray(cnt), np.asarray(vals)
    if codec_name == "bitmap":
        words = X.BitmapFold.encode(ids, cnt, S)
        ri, rc = X.BitmapFold.decode(words, jnp.int32(j), S)
        return np.asarray(ri), np.asarray(rc), np.asarray(vals)
    gaps = X.DeltaFold.encode(ids, cnt, S)
    assert gaps.dtype == jnp.uint16
    ri, rc = X.DeltaFold.decode(gaps, cnt, jnp.int32(j), S)
    return np.asarray(ri), np.asarray(rc), np.asarray(vals)


def _assert_roundtrip(subsets, S, j, seed=0):
    ids, cnt, vals = _canonical_buckets(subsets, np.random.default_rng(seed),
                                        S, j)
    got = {c: _emulate_fold_values(c, ids, cnt, vals, S, j)
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


@pytest.mark.parametrize("j", [0, 3], ids=["first-col", "last-col"])
@pytest.mark.parametrize("S", [1, 32, 33, 64])
@pytest.mark.parametrize("kind", ["empty", "single", "full", "mixed"])
def test_fold_values_roundtrip_extremes(S, kind, j):
    """Deterministic coverage of the density extremes (runs with or without
    hypothesis): empty frontier, single-vertex frontier, full frontier --
    received by the first and the last column of a C = 4 grid row, the
    `j*S` offset of the owned rows at both ends."""
    C = 4
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
    _assert_roundtrip(subsets, S, j, seed=S)


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
# The fold's compactions, bitmap and delta formulas against numpy, incl. S
# not divisible by 32 and empty/full buckets.
# ----------------------------------------------------------------------------

def _np_pack_bits(mask):
    """(N, S) bool -> (N, ceil(S/32)) uint32, bit s of word w = mask[32w+s]."""
    N, S = mask.shape
    W = (S + 31) // 32
    bits = np.zeros((N, 32 * W), bool)
    bits[:, :S] = mask
    return np.packbits(bits, axis=1, bitorder="little").view("<u4") \
        .astype(np.uint32)


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 4), st.integers(1, 97), st.integers(0, 10_000))
def test_compact_rows_matches_argsort_property(N, S, seed):
    """`compact_blocks` (the expand exchange's compaction) concatenates
    each block's valid prefix in block order, pads with `fill`, and counts
    them, at any density including 0 and S."""
    rng = np.random.default_rng(seed)
    density = rng.choice([0.0, 0.25, 0.75, 1.0])
    cnts = (rng.random(N) * density * (S + 1)).astype(np.int32).clip(0, S)
    vals = rng.integers(-5, 1 << 30, (N, S)).astype(np.int32)
    out, total = F.compact_blocks(jnp.asarray(vals), jnp.asarray(cnts),
                                  fill=7)
    want = np.concatenate([vals[r, :cnts[r]] for r in range(N)])
    k = want.shape[0]
    out = np.asarray(out)
    assert int(total) == k and out.shape == (N * S,)
    assert (out[:k] == want).all() and (out[k:] == 7).all()


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 97), st.integers(0, 10_000))
def test_fold_kernel_bitmap_roundtrip_property(S, seed):
    """pack_bitmap == numpy little-endian bit packing at any S (incl. not
    divisible by 32, pad bits zero); unpack_bitmap recovers the mask."""
    rng = np.random.default_rng(seed)
    mask = rng.random((3, S)) < rng.choice([0.0, 0.3, 1.0])
    words = F.pack_bitmap(jnp.asarray(mask))
    assert words.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(words), _np_pack_bits(mask))
    np.testing.assert_array_equal(np.asarray(F.unpack_bitmap(words, S)),
                                  mask)


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 80), st.integers(0, 10_000))
def test_fold_kernel_delta_roundtrip_property(S, seed):
    """DeltaFold.encode == numpy first-order gaps of each bucket's sorted
    offsets (slot 0 absolute, pads 0) on random buckets at any density
    (empty and full included), and decode recovers each bucket's sorted
    id set."""
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
    gaps = X.DeltaFold.encode(jnp.asarray(dst), cnt, S)
    assert gaps.dtype == jnp.uint16
    want = np.zeros((C, S), np.uint16)
    for m in range(C):
        ts = np.sort(dst[m, :cnts[m]] % S)
        want[m, :cnts[m]] = np.diff(ts, prepend=0)
    np.testing.assert_array_equal(np.asarray(gaps), want)
    ri, rc = X.DeltaFold.decode(gaps, cnt, jnp.int32(j), S)
    ri = np.asarray(ri)
    assert (np.asarray(rc) == np.asarray(cnts)).all()
    for m in range(C):
        assert (ri[m, :cnts[m]] == np.sort(dst[m, :cnts[m]])).all()
        assert (ri[m, cnts[m]:] == -1).all()


def test_fold_kernel_program_helpers_match(rng):
    """pack_blocks / owned_to_front / compact_blocks against numpy sets:
    ascending front-packed ids with aligned values and their counts."""
    from repro.algos import program as PR

    grid = Grid2D(1, 4, 4 * 33)                 # S = 33: not a word multiple
    S, C = grid.S, grid.C
    I32_MAX = int(np.iinfo(np.int32).max)
    improved = rng.random(C * S) < 0.3
    vals = rng.integers(0, 1 << 20, C * S).astype(np.int32)
    ids, cnt, vs = (np.asarray(x) for x in PR.pack_blocks(
        jnp.asarray(improved), jnp.asarray(vals), grid))
    for m in range(C):
        rows = m * S + np.flatnonzero(improved[m * S:(m + 1) * S])
        k = rows.size
        assert cnt[m] == k
        assert (ids[m, :k] == rows).all() and (ids[m, k:] == -1).all()
        assert (vs[m, :k] == vals[rows]).all()
        assert (vs[m, k:] == I32_MAX).all()
    changed = rng.random(S) < 0.4
    ov = rng.integers(0, 1 << 20, S).astype(np.int32)
    front, pay, nc = (np.asarray(x) for x in PR.owned_to_front(
        jnp.asarray(changed), jnp.asarray(ov), 2, S))
    t = np.flatnonzero(changed)
    assert int(nc) == t.size
    assert (front[:t.size] == 2 * S + t).all() and (front[t.size:] == -1).all()
    assert (pay[:t.size] == ov[t]).all() and (pay[t.size:] == I32_MAX).all()
    blocks = rng.integers(0, 100, (3, 7)).astype(np.int32)
    cnts = rng.integers(0, 8, 3).astype(np.int32)
    out, total = F.compact_blocks(jnp.asarray(blocks), jnp.asarray(cnts))
    want = np.concatenate([blocks[r, :cnts[r]] for r in range(3)])
    out = np.asarray(out)
    assert int(total) == want.size
    assert (out[:want.size] == want).all() and (out[want.size:] == -1).all()


# ----------------------------------------------------------------------------
# The single-message gate: ONE col_all_to_all per fold per level, counted on
# the traced jaxpr of the whole engine program (acceptance criterion).
# ----------------------------------------------------------------------------

def _collectives_edges():
    return np.asarray(rmat_edges(jax.random.key(5), 8, 8))


@pytest.fixture(scope="module")
def _collectives_graph():
    edges = _collectives_edges()
    w = np.random.default_rng(0).integers(1, 256, size=edges.shape[1]) \
        .astype(np.uint8)
    return DistGraph.from_edges(
        edges, BFSConfig(grid=(1, 1), edge_chunk=256), n=256, weights=w)


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
    sess = g.session(BFSConfig(grid=(1, 1), edge_chunk=256, fold_codec=codec))
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
        config=BFSConfig(grid=(1, 4), edge_chunk=256))


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
# Session answers against the host references, delta block-size error
# surfacing (DESIGN.md sec. 10)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ["list", "bitmap", "delta"])
def test_fold_paths_bit_identical_through_session(_collectives_graph, codec):
    """BFS + CC through the session under each codec equal the host
    references: levels, preds (the smallest frontier parent), scanned
    edges and component labels."""
    from repro.algos import cc_reference

    g = _collectives_graph
    edges, n = _collectives_edges(), 256
    deg = np.bincount(edges[0], minlength=n)
    co, ri = build_csc(edges, n)
    s = g.session(BFSConfig(grid=(1, 1), edge_chunk=256, fold_codec=codec))
    roots = [3, 11]
    out = s.bfs(jnp.asarray(roots, jnp.int32))
    for b, root in enumerate(roots):
        level = np.asarray(out.level)[b, :n]
        pred = np.asarray(out.pred)[b, :n]
        ref, _ = bfs_reference_py(co, ri, root, n)
        np.testing.assert_array_equal(level, ref)
        validate_bfs(edges, level, pred, root)
        for v in np.flatnonzero(level > 0):
            parents = edges[0][(edges[1] == v)
                               & (level[edges[0]] == level[v] - 1)]
            assert pred[v] == parents.min(), v
        assert out.edges_scanned[b] == int(deg[level >= 0].sum())
    cc = s.connected_components(fold_codec=codec)
    np.testing.assert_array_equal(np.asarray(cc.labels)[:n],
                                  cc_reference(edges, n))


def test_delta_block_size_error_names_working_codecs():
    """S > 65536 with fold_codec="delta" must fail at session/engine build
    with an error naming the codecs that DO work at that block size."""
    edges = np.array([[0, 1], [1, 2]])
    n = 1 << 17                                  # 1x1 grid -> S = 131072
    g = DistGraph.from_edges(
        edges, BFSConfig(grid=(1, 1)), n=n)
    with pytest.raises(ValueError) as ei:
        g.session(BFSConfig(grid=(1, 1), fold_codec="delta"))
    msg = str(ei.value)
    assert "delta" in msg and "65536" in msg
    assert "bitmap" in msg and "list" in msg     # the codecs that DO work
    # and the working codecs really do build at this block size
    g.session(BFSConfig(grid=(1, 1), fold_codec="bitmap"))


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
