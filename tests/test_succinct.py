"""Unit + property tests for the succinct layer (bitvector/EF/delta/k2)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.succinct import (
    BitVector,
    EliasFano,
    K2Tree,
    delta_decode,
    delta_encode,
    gamma_decode,
    gamma_encode,
    pack_bits,
    unpack_bits,
)


# ---------------- bitvector ----------------
def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    for n in [0, 1, 31, 32, 33, 100, 1024, 4097]:
        bits = rng.integers(0, 2, n).astype(np.uint8)
        assert np.array_equal(unpack_bits(pack_bits(bits), n), bits)


def test_rank_select_against_naive():
    rng = np.random.default_rng(1)
    bits = rng.integers(0, 2, 1000).astype(np.uint8)
    bv = BitVector(bits)
    cum = np.concatenate([[0], np.cumsum(bits)])
    for i in [0, 1, 31, 32, 33, 500, 999, 1000]:
        assert int(bv.rank1(i)) == cum[i]
        assert int(bv.rank0(i)) == i - cum[i]
    ones = np.flatnonzero(bits)
    got = bv.select1(np.arange(len(ones)))
    assert np.array_equal(got, ones)


def test_rank_batched():
    bits = np.array([1, 0, 1, 1, 0, 0, 1], dtype=np.uint8)
    bv = BitVector(bits)
    idx = np.arange(8)
    expect = np.concatenate([[0], np.cumsum(bits)])
    assert np.array_equal(bv.rank1(idx), expect)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.booleans(), min_size=1, max_size=300))
def test_bitvector_properties(bools):
    bits = np.array(bools, dtype=np.uint8)
    bv = BitVector(bits)
    assert np.array_equal(bv.to_numpy(), bits)
    n_ones = int(bits.sum())
    assert bv.n_ones == n_ones
    if n_ones:
        sel = bv.select1(np.arange(n_ones))
        # rank(select(j)) == j and bit at select(j) is 1
        assert np.array_equal(bv.rank1(sel), np.arange(n_ones))
        assert np.all(bv.access(sel) == 1)


# ---------------- elias-fano ----------------
@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=10_000), min_size=0, max_size=200))
def test_elias_fano_roundtrip(vals):
    vals = np.sort(np.array(vals, dtype=np.int64))
    ef = EliasFano(vals)
    assert np.array_equal(ef.to_numpy(), vals)


def test_elias_fano_access_and_rank():
    vals = np.array([2, 3, 5, 7, 11, 13, 24, 24, 60], dtype=np.int64)
    ef = EliasFano(vals)
    assert int(ef.access(4)) == 11
    assert np.array_equal(ef.access(np.array([0, 8])), np.array([2, 60]))
    assert ef.rank_leq(24) == 8
    assert ef.rank_leq(1) == 0
    assert ef.rank_leq(100) == 9


def test_elias_fano_rejects_too_small_universe():
    vals = np.array([2, 5, 9], dtype=np.int64)
    # universe must exceed the max value: == max and < max both mis-split
    for bad in (9, 4, 0):
        with pytest.raises(ValueError, match="universe"):
            EliasFano(vals, universe=bad)
    with pytest.raises(ValueError, match="non-negative"):
        EliasFano(np.array([-1, 3], dtype=np.int64))
    # boundary: universe == max + 1 is the tightest legal value
    ef = EliasFano(vals, universe=10)
    assert np.array_equal(ef.to_numpy(), vals)
    # an explicit universe on an empty sequence is always fine
    assert EliasFano(np.array([], dtype=np.int64), universe=0).n == 0


def test_elias_fano_compresses_dense_runs():
    vals = np.repeat(np.arange(100), 50)  # 5000 values, universe 100
    ef = EliasFano(vals)
    assert ef.size_in_bytes() < 5000 * 4  # far smaller than raw int32


# ---------------- gamma / delta ----------------
@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=2**40), min_size=0, max_size=200))
def test_delta_roundtrip(vals):
    vals = np.array(vals, dtype=np.uint64)
    words, nbits = delta_encode(vals)
    out = delta_decode(words, nbits, len(vals))
    assert np.array_equal(out, vals)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=2**30), min_size=1, max_size=100))
def test_gamma_roundtrip(vals):
    vals = np.array(vals, dtype=np.uint64)
    words, nbits = gamma_encode(vals)
    assert np.array_equal(gamma_decode(words, nbits, len(vals)), vals)


def test_delta_is_compact_for_small_values():
    vals = np.ones(1000, dtype=np.uint64)  # delta(1) = 1 bit
    words, nbits = delta_encode(vals)
    assert nbits == 1000


# ---------------- k2 tree ----------------
def _random_matrix(rng, n, m, density):
    nnz = max(1, int(n * m * density))
    r = rng.integers(0, n, nnz)
    c = rng.integers(0, m, nnz)
    return r, c


@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("shape", [(8, 8), (10, 17), (64, 3), (1, 1), (100, 100)])
def test_k2_dense_roundtrip(k, shape):
    rng = np.random.default_rng(42)
    n, m = shape
    r, c = _random_matrix(rng, n, m, 0.05)
    t = K2Tree(r, c, n, m, k=k)
    dense = np.zeros((n, m), dtype=np.uint8)
    dense[r, c] = 1
    assert np.array_equal(t.to_dense(), dense)


def test_k2_row_col_queries():
    rng = np.random.default_rng(7)
    n, m = 50, 70
    r, c = _random_matrix(rng, n, m, 0.03)
    t = K2Tree(r, c, n, m)
    dense = np.zeros((n, m), dtype=np.uint8)
    dense[r, c] = 1
    for i in range(n):
        assert np.array_equal(t.row(i), np.flatnonzero(dense[i]))
    for j in range(m):
        assert np.array_equal(t.col(j), np.flatnonzero(dense[:, j]))
    for i in range(0, n, 7):
        for j in range(0, m, 11):
            assert t.access(i, j) == dense[i, j]


def test_k2_empty():
    t = K2Tree(np.zeros(0), np.zeros(0), 16, 16)
    assert t.n_points == 0
    assert len(t.row(3)) == 0
    assert t.access(0, 0) == 0


def test_k2_batched_rows_cols_vs_dense():
    """rows_many/cols_many: one traversal for many lines == dense oracle,
    including out-of-range and duplicate queries."""
    rng = np.random.default_rng(3)
    n, m = 37, 61
    r, c = _random_matrix(rng, n, m, 0.06)
    t = K2Tree(r, c, n, m)
    dense = np.zeros((n, m), dtype=np.uint8)
    dense[r, c] = 1

    qs = np.array([0, 5, 5, -1, 36, 200, 12], dtype=np.int64)
    idx, cols = t.rows_many(qs)
    for qi in range(len(qs)):
        got = cols[idx == qi]
        want = np.flatnonzero(dense[qs[qi]]) if 0 <= qs[qi] < n else np.zeros(0)
        assert np.array_equal(got, want), f"row query {qi} ({qs[qi]})"

    qs = np.array([60, 0, 3, 3, -5], dtype=np.int64)
    idx, rows_ = t.cols_many(qs)
    for qi in range(len(qs)):
        got = rows_[idx == qi]
        want = np.flatnonzero(dense[:, qs[qi]]) if 0 <= qs[qi] < m else np.zeros(0)
        assert np.array_equal(got, want), f"col query {qi} ({qs[qi]})"

    # full-matrix batched expansion == to_dense == dense
    assert np.array_equal(t.to_dense(), dense)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_k2_batched_matches_scalar(seed):
    rng = np.random.default_rng(seed)
    n, m = 26, 19
    r, c = _random_matrix(rng, n, m, 0.08)
    t = K2Tree(r, c, n, m, k=int(rng.integers(2, 4)))
    qs = rng.integers(0, n, 8).astype(np.int64)
    idx, cols = t.rows_many(qs)
    for qi in range(len(qs)):
        assert np.array_equal(cols[idx == qi], t.row(int(qs[qi])))


# ---------------- device rank (XLA on CPU here; the same program on a TPU) ----------------
def _levels(rng, sizes):
    return [BitVector(rng.integers(0, 2, n).astype(np.uint8)) for n in sizes]


def test_device_rank_parity():
    """DeviceLevels.rank1 == BitVector.rank1 on every level of a
    concatenated layout, including i == n and odd batch sizes."""
    from repro.core.succinct.device_rank import DeviceLevels

    rng = np.random.default_rng(11)
    levels = _levels(rng, [4097, 64, 1, 3000])
    dev = DeviceLevels(levels)
    for t, bv in enumerate(levels):
        for q in (1, 33, 997):
            pos = np.concatenate([rng.integers(0, bv.n + 1, q), [0, bv.n]])
            assert np.array_equal(dev.rank1(t, pos), bv.rank1(pos)), (t, q)


def test_device_rank_bucket_padding():
    """Positions pad to power-of-two buckets (>= MIN_POSITIONS) and the
    word array to a power-of-two width; answers at bucket edges and for an
    empty batch are unaffected by the pad."""
    from repro.core.succinct import device_rank as dr

    assert [dr.position_bucket(q) for q in (0, 1, 256, 257, 4096, 4097)] == \
        [256, 256, 256, 512, 4096, 8192]
    assert [dr.width_bucket(w) for w in (1, 1024, 1025)] == [1024, 1024, 2048]
    rng = np.random.default_rng(5)
    levels = _levels(rng, [40_000, 777])
    dev = dr.DeviceLevels(levels)
    assert len(dev.words) == dr.width_bucket(sum(len(lv.words) + 1 for lv in levels))
    for q in (0, 255, 256, 257, 511, 513):
        pos = rng.integers(0, levels[0].n + 1, q)
        assert np.array_equal(dev.rank1(0, pos), levels[0].rank1(pos)), q
    assert dev.rank1(1, np.zeros(0, np.int64)).shape == (0,)


def test_device_rank_rejects_out_of_range_and_int32_overflow():
    """Indices the device would clamp or wrap raise on the host instead."""
    from types import SimpleNamespace

    from repro.core.succinct.device_rank import DeviceLevels

    bv = BitVector(np.ones(100, dtype=np.uint8))
    dev = DeviceLevels([bv])
    with pytest.raises(IndexError):
        dev.rank1(0, np.array([101]))
    with pytest.raises(IndexError):
        dev.rank1(0, np.array([-1, 3]))
    huge = SimpleNamespace(n=2**31, n_ones=0, words=np.zeros(1, np.uint32),
                           word_ranks=np.zeros(2, np.int64))
    with pytest.raises(OverflowError):
        DeviceLevels([bv, huge])


def _on_device(monkeypatch):
    """Take the TPU branch of the platform choice on this backend."""
    from repro.core.succinct import device_rank

    monkeypatch.setattr(device_rank, "enabled", lambda: True)


def test_k2_rows_many_on_device_matches_host(monkeypatch):
    """A multi-level tree placed on the device answers rows_many/cols_many
    exactly as the host tree, with the descent's wide rank batches counted
    on the device; a tree loaded through from_levels is placed too."""
    rng = np.random.default_rng(9)
    n, m = 300, 500
    r, c = _random_matrix(rng, n, m, 0.02)
    host = K2Tree(r, c, n, m)
    assert host.device is None
    _on_device(monkeypatch)
    dev = K2Tree(r, c, n, m)
    assert dev.device is not None and dev.h >= 8
    qs = np.concatenate([rng.integers(0, n, 200), [-1, n + 3]]).astype(np.int64)
    for a, b in zip(dev.rows_many(qs), host.rows_many(qs)):
        assert np.array_equal(a, b)
    for a, b in zip(dev.cols_many(qs), host.cols_many(qs)):
        assert np.array_equal(a, b)
    assert dev.rank_calls["descent", "device"] > 0
    assert host.rank_calls["descent", "device"] == 0
    loaded = K2Tree.from_levels(n, m, dev.k, dev.h, dev.n_points,
                                [lv.words for lv in dev.levels],
                                [lv.n for lv in dev.levels])
    assert loaded.device is not None
    for a, b in zip(loaded.rows_many(qs), host.rows_many(qs)):
        assert np.array_equal(a, b)
    assert loaded.rank_calls["descent", "device"] > 0


def test_small_rank_batches_stay_on_host_and_are_counted(monkeypatch):
    """Below DEVICE_MIN_BATCH a rank runs on the host even with the levels
    on the device, and the split is visible per call site."""
    from repro.core.succinct.device_rank import DEVICE_MIN_BATCH

    _on_device(monkeypatch)
    rng = np.random.default_rng(2)
    r, c = _random_matrix(rng, 64, 64, 0.05)
    t = K2Tree(r, c, 64, 64)
    t.row(int(r[0]))
    assert t.rank_calls["descent", "device"] == 0
    assert t.rank_calls["descent", "host"] > 0
    t.access(int(r[0]), int(c[0]))
    assert t.rank_calls["access", "host"] > 0
    big = np.arange(64, dtype=np.int64).repeat(DEVICE_MIN_BATCH)
    t.rows_many(big)
    assert t.rank_calls["descent", "device"] > 0


def test_device_rank_failure_raises(monkeypatch):
    """A device error surfaces to the caller: no numpy answer stands in."""
    from repro.core.succinct import device_rank

    _on_device(monkeypatch)
    rng = np.random.default_rng(4)
    r, c = _random_matrix(rng, 128, 128, 0.05)
    t = K2Tree(r, c, 128, 128)

    def broken(*args):
        raise RuntimeError("device lost")

    monkeypatch.setattr(device_rank.DeviceLevels, "rank1", broken)
    with pytest.raises(RuntimeError, match="device lost"):
        t.rows_many(np.arange(128, dtype=np.int64))
    assert t.rank_calls["descent", "host"] == 0


def test_rank_placement_follows_platform(monkeypatch):
    """The platform alone decides: a CPU backend keeps the levels on the
    host, a TPU backend places them; no environment knob is consulted."""
    import jax

    from repro.core.succinct import device_rank

    assert jax.default_backend() == "cpu"
    assert not device_rank.enabled()
    assert K2Tree(np.array([1]), np.array([2]), 8, 8).device is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert device_rank.enabled()


def test_kernel_bitvec_rank_arbitrary_batch_sizes():
    """The XLA rank op answers any batch size, at any level offset."""
    import jax.numpy as jnp

    from repro.kernels.bitvec_rank import bitvec_rank

    rng = np.random.default_rng(0)
    bits = rng.integers(0, 2, 2048).astype(np.uint8)
    bv = BitVector(bits)
    words = np.concatenate([bv.words, np.zeros(1, np.uint32)])
    ranks = bv.word_ranks.astype(np.int32)
    for q in [1, 7, 64, 100, 1023]:
        pos = rng.integers(0, bv.n + 1, q).astype(np.int32)
        out = bitvec_rank(jnp.asarray(words), jnp.asarray(ranks), jnp.asarray(pos))
        assert np.array_equal(np.asarray(out), bv.rank1(pos.astype(np.int64)))
        # the same level behind 5 leading words of another level
        out = bitvec_rank(jnp.asarray(np.concatenate([np.full(5, 7, np.uint32), words])),
                          jnp.asarray(np.concatenate([np.full(5, 9, np.int32), ranks])),
                          jnp.asarray(pos), 5)
        assert np.array_equal(np.asarray(out), bv.rank1(pos.astype(np.int64)))


@settings(max_examples=25, deadline=None)
@given(
    st.lists(st.tuples(st.integers(0, 30), st.integers(0, 30)), min_size=0, max_size=60),
    st.sampled_from([2, 3, 4]),
)
def test_k2_property(points, k):
    n = m = 31
    r = np.array([p[0] for p in points], dtype=np.int64)
    c = np.array([p[1] for p in points], dtype=np.int64)
    t = K2Tree(r, c, n, m, k=k)
    dense = np.zeros((n, m), dtype=np.uint8)
    if len(points):
        dense[r, c] = 1
    assert np.array_equal(t.to_dense(), dense)
    assert t.n_points == int(dense.sum())
