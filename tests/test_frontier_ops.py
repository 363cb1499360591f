import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import frontier as F


@given(st.data())
@settings(max_examples=40, deadline=None)
def test_bucket_append_matches_python(data):
    n = data.draw(st.integers(1, 64))
    nb = data.draw(st.integers(1, 6))
    cap = 64
    vals = data.draw(st.lists(st.integers(0, 1000), min_size=n, max_size=n))
    tgt = data.draw(st.lists(st.integers(0, nb - 1), min_size=n, max_size=n))
    take = data.draw(st.lists(st.booleans(), min_size=n, max_size=n))
    dst = jnp.full((nb, cap), -1, jnp.int32)
    cnt = jnp.zeros((nb,), jnp.int32)
    dst, cnt = F.bucket_append(dst, cnt, jnp.asarray(vals, jnp.int32),
                               jnp.asarray(tgt, jnp.int32),
                               jnp.asarray(take), nb)
    for b in range(nb):
        want = [v for v, t, k in zip(vals, tgt, take) if k and t == b]
        got = np.asarray(dst[b])[:int(cnt[b])].tolist()
        assert got == want


def test_bucket_append_appends_at_offset():
    dst = jnp.full((2, 8), -1, jnp.int32)
    cnt = jnp.zeros((2,), jnp.int32)
    v1 = jnp.asarray([10, 11, 12], jnp.int32)
    t1 = jnp.asarray([0, 1, 0], jnp.int32)
    dst, cnt = F.bucket_append(dst, cnt, v1, t1, jnp.ones(3, bool), 2)
    v2 = jnp.asarray([20, 21], jnp.int32)
    t2 = jnp.asarray([0, 1], jnp.int32)
    dst, cnt = F.bucket_append(dst, cnt, v2, t2, jnp.ones(2, bool), 2)
    assert np.asarray(dst[0])[:3].tolist() == [10, 12, 20]
    assert np.asarray(dst[1])[:2].tolist() == [11, 21]
    assert np.asarray(cnt).tolist() == [3, 2]


@given(st.data())
@settings(max_examples=40, deadline=None)
def test_winner_dedup_first_wins(data):
    n = data.draw(st.integers(1, 64))
    nr = 32
    v = data.draw(st.lists(st.integers(0, nr - 1), min_size=n, max_size=n))
    elig = data.draw(st.lists(st.booleans(), min_size=n, max_size=n))
    win = np.asarray(F.winner_dedup(jnp.asarray(v, jnp.int32),
                                    jnp.asarray(elig), nr))
    seen = set()
    for s in range(n):
        expect = elig[s] and v[s] not in seen
        if elig[s]:
            seen.add(v[s])
        assert win[s] == expect


@given(S=st.integers(1, 130))
@settings(max_examples=20, deadline=None)
def test_bitmap_roundtrip(S):
    rng = np.random.default_rng(S)
    m = rng.random((3, S)) < 0.3
    packed = F.pack_bitmap(jnp.asarray(m))
    assert packed.dtype == jnp.uint32
    got = np.asarray(F.unpack_bitmap(packed, S))
    assert (got == m).all()


def test_compact_blocks():
    vals = jnp.asarray([[1, 2, -1], [3, -1, -1]], jnp.int32)
    cnts = jnp.asarray([2, 1], jnp.int32)
    out, total = F.compact_blocks(vals, cnts)
    assert int(total) == 3
    assert np.asarray(out)[:3].tolist() == [1, 2, 3]
    assert (np.asarray(out)[3:] == -1).all()


def test_exclusive_cumsum():
    x = jnp.asarray([3, 0, 2], jnp.int32)
    assert np.asarray(F.exclusive_cumsum(x)).tolist() == [0, 3, 3, 5]


@pytest.mark.parametrize("S,density,offset", [
    (1024, 0.1, 0),        # random, a power of two
    (1000, 0.5, 0),        # random, not a power of two
    (37, 0.3, 2 * 37),     # offset i * S of the grid's row i = 2
    (130, 0.0, 130),       # all false
    (130, 1.0, 3 * 130),   # all true
    (1, 1.0, 5),
    (1, 0.0, 0),
])
def test_compact_mask_matches_flatnonzero(S, density, offset):
    mask = np.random.default_rng(S).random(S) < density
    # traced, as the steps call it with the device's row i
    out, cnt = jax.jit(F.compact_mask)(jnp.asarray(mask), jnp.int32(offset))
    t = np.flatnonzero(mask)
    want = np.full(S, -1, np.int32)
    want[:t.size] = t + offset
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), want)
    assert int(cnt) == t.size
