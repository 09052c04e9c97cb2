"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Everything here drives Pallas kernels (interpret mode off-TPU); skip with
# `-m "not pallas"` on hosts without TPU/interpret support.
pytestmark = pytest.mark.pallas

from repro.kernels import ref
from repro.kernels.dom_admit import dom_admit_pallas
from repro.kernels.dom_release import dom_release_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.inchash import inchash_pallas
from repro.kernels.ops import dom_release_ref_order
from repro.kernels.ssd_scan import ssd_scan_pallas

RNG = np.random.default_rng(7)


def _r(*shape, dtype=jnp.float32, scale=0.5):
    return jnp.asarray(RNG.normal(0, scale, shape), dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,Hq,Hk,D,causal,window,bq,bk", [
    (128, 4, 2, 16, True, None, 32, 32),
    (96, 4, 1, 32, True, None, 32, 32),     # MQA, padded seq
    (128, 2, 2, 16, False, None, 64, 32),   # bidirectional
    (256, 4, 2, 16, True, 64, 32, 32),      # sliding window (banded)
    (64, 8, 8, 64, True, None, 64, 64),     # MHA wider head
])
def test_flash_attention_kernel(S, Hq, Hk, D, causal, window, bq, bk, dtype):
    q, k, v = _r(2, S, Hq, D, dtype=dtype), _r(2, S, Hk, D, dtype=dtype), _r(2, S, Hk, D, dtype=dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=bq, block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,S,H,P,N,chunk", [
    (2, 64, 3, 4, 8, 16),
    (1, 40, 2, 8, 4, 16),     # padded chunk tail
    (2, 128, 4, 16, 16, 32),
])
def test_ssd_scan_kernel(b, S, H, P, N, chunk, dtype):
    x = _r(b, S, H, P, dtype=dtype)
    dt = jnp.abs(_r(b, S, H, scale=0.3)).astype(dtype) + jnp.asarray(0.01, dtype)
    A = (-jnp.abs(_r(H)) - 0.1).astype(jnp.float32)
    B = _r(b, S, N, dtype=dtype)
    C = _r(b, S, N, dtype=dtype)
    y = ssd_scan_pallas(x, dt, A, B, C, chunk=chunk, interpret=True)
    want = ref.ssd_scan_ref(x, dt, A, B, C)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# dom release
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [8, 64, 100, 256])
def test_dom_release_kernel(n):
    deadlines = jnp.asarray(RNG.uniform(0, 1, n), jnp.float32)
    admitted = jnp.asarray(RNG.random(n) < 0.8)
    now = jnp.float32(0.6)
    order, count = dom_release_pallas(deadlines, admitted, now, interpret=True)
    want_order, want_count = dom_release_ref_order(deadlines, admitted, now)
    assert int(count) == int(want_count)
    k = int(count)
    # release order must be identical: the (hi, lo, idx) key sort is exact
    # and index-stable, so this holds for duplicates too, not just w.p. 1
    np.testing.assert_array_equal(np.asarray(order[:k]), np.asarray(want_order[:k]))
    assert bool((np.asarray(order[k:]) == -1).all())


def test_dom_release_kernel_f64_duplicates_and_1ns_gaps():
    """float64 inputs with exact duplicate deadlines and 1ns separations
    straddling `now`: the int32 (hi, lo) key words preserve the full f64
    order, and equal deadlines release in index order (stable argsort)."""

    with jax.enable_x64(True):
        base = np.sort(RNG.uniform(1.0, 2.0, 32))
        d = np.repeat(base, 4) + np.tile([0.0, 0.0, 1e-9, 2e-9], 32)
        d = d[RNG.permutation(d.size)]
        now = np.float64(base[16] + 1e-9)     # cuts inside a 1ns cluster
        admitted = RNG.random(d.size) < 0.9
        order, count = dom_release_pallas(
            jnp.asarray(d), jnp.asarray(admitted), jnp.asarray(now),
            interpret=True)
        want_order, want_count = dom_release_ref_order(d, admitted, now)
        k = int(count)
        assert k == int(want_count)
        np.testing.assert_array_equal(np.asarray(order[:k]),
                                      np.asarray(want_order[:k]))
        assert bool((np.asarray(order[k:]) == -1).all())


def test_dom_release_released_are_sorted():
    n = 128
    deadlines = jnp.asarray(RNG.uniform(0, 1, n), jnp.float32)
    admitted = jnp.ones(n, bool)
    order, count = dom_release_pallas(deadlines, admitted, jnp.float32(0.5), interpret=True)
    k = int(count)
    rel = np.asarray(deadlines)[np.asarray(order[:k])]
    assert (np.diff(rel) >= 0).all()
    assert (rel <= 0.5).all()


# ---------------------------------------------------------------------------
# dom admit (fused bitonic event sort + watermark prefix-max)
# ---------------------------------------------------------------------------
def _admit_oracle(deadlines, arrivals):
    from repro.core.vectorized import dom_admit_watermark_np

    return dom_admit_watermark_np(np.asarray(deadlines, np.float64),
                                  np.asarray(arrivals, np.float64))


@pytest.mark.parametrize("n,R", [(8, 1), (33, 3), (64, 2), (100, 3), (256, 5)])
def test_dom_admit_kernel(n, R):
    """Kernel admission == float64 watermark oracle with duplicate
    deadlines and arrival ties (grid values k/64): the exact (hi, lo) key
    encoding plus the integer aux tie-break must line up -- no rounding
    happens anywhere."""
    d = RNG.integers(0, 4 * 64, n) / 64.0
    a = RNG.integers(0, 6 * 64, (n, R)) / 64.0
    a[RNG.random((n, R)) < 0.15] = np.inf
    got = dom_admit_pallas(jnp.asarray(d, jnp.float32),
                           jnp.asarray(a.T, jnp.float32), interpret=True)
    np.testing.assert_array_equal(np.asarray(got).T, _admit_oracle(d, a))


def test_dom_admit_kernel_realistic_owd():
    """A realistic OWD spread, fed RAW as float64 -- no span shift, no
    downcast: the kernel bitcasts the caller-precision times to exact
    int32 key words, so absolute epoch-scale inputs are handled as-is."""

    n = 128
    send = np.sort(RNG.uniform(0, 5e-3, n)) + np.arange(n) * 1e-6
    d = send + 120e-6
    a = send[:, None] + RNG.lognormal(np.log(60e-6), 0.6, (n, 3))
    a[RNG.random((n, 3)) < 0.02] = np.inf
    with jax.enable_x64(True):
        got = dom_admit_pallas(jnp.asarray(d), jnp.asarray(a.T),
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got).T, _admit_oracle(d, a))


def test_dom_admit_kernel_sub_f32_resolution_ties():
    """Deadline/arrival gaps far below float32 resolution at the working
    magnitude: a float32 downcast would collapse them (the old design's
    documented tie window); the f64 (hi, lo) keys keep the exact order."""

    base = np.sort(RNG.uniform(1.0, 5.0, 64))
    d = np.repeat(base, 4) + np.tile([0.0, 1e-9, 2e-9, 3e-9], 64)
    d = d[RNG.permutation(d.size)]
    a = (d + RNG.uniform(-2e-9, 2e-9, d.size))[:, None] \
        + np.array([0.0, 1e-9, 5e-9])
    a[RNG.random(a.shape) < 0.1] = np.inf
    # the scenario is meaningful: f32 cannot represent these separations
    assert (np.float32(base[0]) == np.float32(base[0] + 1e-9))
    with jax.enable_x64(True):
        got = dom_admit_pallas(jnp.asarray(d), jnp.asarray(a.T),
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got).T, _admit_oracle(d, a))


def test_dom_admit_kernel_all_dropped_receiver():
    d = np.arange(12) / 8.0
    a = np.full((12, 2), np.inf)
    a[:, 1] = (np.arange(12) + 2) / 8.0
    got = dom_admit_pallas(jnp.asarray(d, jnp.float32),
                           jnp.asarray(a.T, jnp.float32), interpret=True)
    got = np.asarray(got).T
    assert not got[:, 0].any()                  # dropped receiver admits none
    np.testing.assert_array_equal(got, _admit_oracle(d, a))


def test_time_keys_f32_pair_is_exact_on_float32_pairs():
    """The TPU holds a float64 as a float32 (hi, lo) pair, and compiled
    kernels encode times by that split.  On values that are such pairs
    (|lo| < ulp(hi)/2) the split keys give the exact total order: equal
    keys iff equal values, lexicographic key order == value order."""
    from repro.kernels.timekeys import time_sort_keys

    hi = RNG.uniform(-1.0, 1.0, 3000).astype(np.float32)
    lo = (RNG.uniform(-0.49, 0.49, 3000) * np.spacing(hi)).astype(np.float32)
    x = hi.astype(np.float64) + lo.astype(np.float64)
    x[::5] = x[1::5][: x[::5].size]             # exact duplicates
    # neighbours 2^-40 apart: beyond float32, within the pair's reach
    x[:64] = 0.5 + np.arange(64).astype(np.float32) * np.float32(2.0 ** -40)
    x[-3:] = np.inf
    with jax.enable_x64(True):
        khi, klo = time_sort_keys(jnp.asarray(x), f32_pair=True)
    khi, klo = np.asarray(khi), np.asarray(klo)
    np.testing.assert_array_equal(np.lexsort((klo, khi)),
                                  np.argsort(x, kind="stable"))
    srt = np.argsort(x, kind="stable")
    same_key = (khi[srt][1:] == khi[srt][:-1]) & (klo[srt][1:] == klo[srt][:-1])
    np.testing.assert_array_equal(same_key, x[srt][1:] == x[srt][:-1])


# ---------------------------------------------------------------------------
# inchash
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,block", [(16, 16), (100, 32), (256, 64), (1000, 256)])
def test_inchash_kernel(n, block):
    d = jnp.asarray(RNG.integers(0, 2**31, n), jnp.uint32)
    c = jnp.asarray(RNG.integers(0, 1000, n), jnp.uint32)
    r = jnp.asarray(RNG.integers(0, 2**20, n), jnp.uint32)
    h, pf = inchash_pallas(d, c, r, block=block, interpret=True)
    want_h, want_pf = ref.inchash_ref(d, c, r)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(want_h))
    np.testing.assert_array_equal(np.asarray(pf), np.asarray(want_pf))


def test_inchash_matches_python_protocol_hash():
    """Kernel hashes == the 32-bit mirror used by the Python protocol."""
    from repro.core.hashing import entry_hash32_np

    d = np.asarray(RNG.integers(0, 2**31, 64), np.uint32)
    c = np.asarray(RNG.integers(0, 100, 64), np.uint32)
    r = np.asarray(RNG.integers(0, 2**20, 64), np.uint32)
    h, _ = inchash_pallas(jnp.asarray(d), jnp.asarray(c), jnp.asarray(r),
                          block=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(h), entry_hash32_np(d, c, r))
