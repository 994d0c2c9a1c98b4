"""Per-kernel allclose vs the pure-jnp oracles: shape/dtype sweeps plus
hypothesis-generated segment ids.  Kernels execute in interpret mode (CPU
container; TPU is the lowering target)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:        # the property test is hypothesis-driven; everything else runs
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention_ref import flash_attention_ref
from repro.kernels.segment_reduce import segment_reduce, segment_sum
from repro.kernels.segment_reduce_ref import (segment_reduce_ref,
                                              segment_sum_ref)
from repro.kernels.tile_matmul import tile_matmul
from repro.kernels.tile_matmul_ref import tile_matmul_ref

rng = np.random.default_rng(0)


@pytest.mark.parametrize("n,d,k,dtype", [
    (64, 16, 8, np.float32),
    (200, 33, 17, np.float32),
    (128, 8, 128, np.float32),
    (100, 24, 10, np.bfloat16) if hasattr(np, "bfloat16") else
    (100, 24, 10, np.float32),
])
def test_segment_sum_shapes(n, d, k, dtype):
    ids = rng.integers(0, k, n).astype(np.int32)
    vals = rng.standard_normal((n, d)).astype(np.float32)
    a = segment_sum(jnp.asarray(ids), jnp.asarray(vals), k, bn=32, bk=16,
                    bd=16)
    b = segment_sum_ref(jnp.asarray(ids), jnp.asarray(vals), k)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)


def test_segment_sum_out_of_range_dropped():
    ids = np.array([0, 5, 99, -1, 2], np.int32)  # 99/-1 out of range
    vals = np.ones((5, 4), np.float32)
    a = segment_sum(jnp.asarray(ids), jnp.asarray(vals), 6)
    b = segment_sum_ref(jnp.asarray(ids), jnp.asarray(vals), 6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def _property_cases():
    """Randomized (n, d, k, seed) cases: hypothesis-generated when the
    package is available, a fixed seeded sweep otherwise."""
    if _HAVE_HYPOTHESIS:
        return None
    r = np.random.default_rng(2024)
    return [(int(r.integers(1, 60)), int(r.integers(1, 12)),
             int(r.integers(1, 20)), int(r.integers(0, 2**31 - 1)))
            for _ in range(20)]


def _check_segment_sum_case(n, d, k, seed):
    r = np.random.default_rng(seed)
    ids = r.integers(0, k, n).astype(np.int32)
    vals = r.standard_normal((n, d)).astype(np.float32)
    a = segment_sum(jnp.asarray(ids), jnp.asarray(vals), k, bn=16, bk=8, bd=8)
    b = segment_sum_ref(jnp.asarray(ids), jnp.asarray(vals), k)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)


if _HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 60), st.integers(1, 12), st.integers(1, 20),
           st.integers(0, 2**31 - 1))
    def test_segment_sum_property(n, d, k, seed):
        _check_segment_sum_case(n, d, k, seed)
else:
    @pytest.mark.parametrize("n,d,k,seed", _property_cases())
    def test_segment_sum_property(n, d, k, seed):
        _check_segment_sum_case(n, d, k, seed)


# ---------------------------------------------------------------------------
# generalized segment_reduce: natural [N]/[N, D] values, min/max via the
# one-hot select path, exact-int accumulation, K/D not multiples of the
# block sizes, and the negative-key sentinel
# ---------------------------------------------------------------------------

def test_segment_reduce_1d_values():
    ids = rng.integers(0, 7, 50).astype(np.int32)
    vals = rng.standard_normal(50).astype(np.float32)
    a = segment_reduce(jnp.asarray(ids), jnp.asarray(vals), 7, bn=16, bk=4)
    b = segment_reduce_ref(jnp.asarray(ids), jnp.asarray(vals), 7)
    assert a.shape == (7,)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("op", ["+", "min", "max"])
@pytest.mark.parametrize("n,d,k", [(100, 33, 17), (65, 1, 5), (31, 9, 13)])
def test_segment_reduce_ops_nonmultiple_blocks(op, n, d, k):
    # K and D deliberately NOT multiples of bk/bd: the pad rows/columns
    # must never leak the ⊕ identity into kept outputs
    ids = rng.integers(0, k, n).astype(np.int32)
    vals = rng.standard_normal((n, d)).astype(np.float32)
    a = segment_reduce(jnp.asarray(ids), jnp.asarray(vals), k, op=op,
                       bn=16, bk=8, bd=8)
    b = segment_reduce_ref(jnp.asarray(ids), jnp.asarray(vals), k, op=op)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("op", ["+", "min", "max"])
def test_segment_reduce_negative_and_oob_sentinel(op):
    ids = np.array([0, 3, -1, 99, 2, -7, 1], np.int32)  # -1/-7/99 drop
    vals = np.arange(1.0, 8.0, dtype=np.float32)
    a = segment_reduce(jnp.asarray(ids), jnp.asarray(vals), 5, op=op,
                       bn=4, bk=4)
    b = segment_reduce_ref(jnp.asarray(ids), jnp.asarray(vals), 5, op=op)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_segment_reduce_exact_int_accumulation():
    # 16777217 = 2**24 + 1 is not representable in fp32: a fp32-rounding
    # path would sum 16777216 + 1; the exact-int path must return 2**24+2
    ids = jnp.asarray(np.zeros(2, np.int32))
    vals = jnp.asarray(np.array([2**24 + 1, 1], np.int32))
    a = segment_reduce(ids, vals, 1)
    assert a.dtype == jnp.int32
    assert int(a[0]) == 2**24 + 2
    # min/max on ints keep the integer dtype too
    m = segment_reduce(ids, vals, 1, op="max")
    assert m.dtype == jnp.int32 and int(m[0]) == 2**24 + 1


@pytest.mark.parametrize("d", [1, 3])
def test_segment_reduce_int_sum_wraps_like_scatter(d):
    # the int + path accumulates in int32: full-range values (negatives,
    # sums that overflow) must equal the wrapping int32 scatter-add bit for
    # bit, across several id blocks
    r = np.random.default_rng(5)
    n, k = 3000, 37
    ids = r.integers(-3, k + 3, n).astype(np.int32)
    vals = r.integers(-2**31, 2**31 - 1, (n, d), dtype=np.int64) \
        .astype(np.int32)
    vals = vals[:, 0] if d == 1 else vals
    a = segment_reduce(jnp.asarray(ids), jnp.asarray(vals), k, bn=1024,
                       bk=128)
    b = segment_reduce_ref(jnp.asarray(ids), jnp.asarray(vals), k)
    assert a.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("m,k,n,bm", [(64, 32, 48, 32), (100, 70, 90, 32),
                                      (33, 17, 9, 16), (128, 128, 128, 128)])
def test_tile_matmul_shapes(m, k, n, bm):
    a = rng.standard_normal((m, k)).astype(np.float32)
    b = rng.standard_normal((k, n)).astype(np.float32)
    c = tile_matmul(jnp.asarray(a), jnp.asarray(b), bm=bm, bn=bm, bk=bm)
    np.testing.assert_allclose(np.asarray(c), a @ b, rtol=1e-4, atol=1e-3)


def test_tile_matmul_bf16():
    a = rng.standard_normal((64, 64)).astype(np.float32)
    b = rng.standard_normal((64, 64)).astype(np.float32)
    c = tile_matmul(jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16),
                    bm=32, bn=32, bk=32)
    np.testing.assert_allclose(np.asarray(c), a @ b, rtol=5e-2, atol=5e-1)


def test_tile_matmul_masked():
    a = rng.standard_normal((96, 64)).astype(np.float32)
    b = rng.standard_normal((64, 80)).astype(np.float32)
    mask = rng.integers(0, 2, (3, 2)).astype(np.float32)  # bm=32, bk=32
    c = tile_matmul(jnp.asarray(a), jnp.asarray(b),
                    tile_mask=jnp.asarray(mask), bm=32, bn=32, bk=32)
    r = tile_matmul_ref(jnp.asarray(a), jnp.asarray(b), jnp.asarray(mask),
                        bm=32, bk=32)
    np.testing.assert_allclose(np.asarray(c), np.asarray(r), rtol=1e-4,
                               atol=1e-3)


@pytest.mark.parametrize("bh,sq,sk,hd,bq", [(2, 64, 64, 16, 32),
                                            (4, 128, 128, 32, 64),
                                            (1, 32, 32, 8, 32)])
def test_flash_attention_causal(bh, sq, sk, hd, bq):
    q = rng.standard_normal((bh, sq, hd)).astype(np.float32)
    k = rng.standard_normal((bh, sk, hd)).astype(np.float32)
    v = rng.standard_normal((bh, sk, hd)).astype(np.float32)
    a = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        bq=bq, bk=32)
    b = flash_attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-3)


def test_flash_attention_non_causal():
    q = rng.standard_normal((2, 64, 16)).astype(np.float32)
    k = rng.standard_normal((2, 64, 16)).astype(np.float32)
    v = rng.standard_normal((2, 64, 16)).astype(np.float32)
    a = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        bq=32, bk=32, causal=False)
    b = flash_attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            causal=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("b,s,d,n,bd,bk", [(2, 32, 16, 4, 8, 8),
                                           (1, 64, 32, 8, 16, 16)])
def test_selective_scan_kernel(b, s, d, n, bd, bk):
    from repro.kernels.selective_scan import selective_scan
    from repro.kernels.selective_scan_ref import selective_scan_ref
    r = np.random.default_rng(0)
    a = jnp.asarray(np.exp(-np.abs(r.standard_normal((b, s, d, n)))),
                    jnp.float32)
    bx = jnp.asarray(r.standard_normal((b, s, d, n)) * 0.1, jnp.float32)
    c = jnp.asarray(r.standard_normal((b, s, n)), jnp.float32)
    y = selective_scan(a, bx, c, bd=bd, bk=bk)
    yr = selective_scan_ref(a, bx, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-3,
                               atol=2e-3)
