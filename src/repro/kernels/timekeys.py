"""Order-isomorphic two-word int32 sort keys for float event times.

The Pallas DOM kernels sort event times with bitonic compare-exchange
networks.  Comparing IEEE doubles in-kernel would need f64 lane support;
the old design downcast to span-relative float32 and carried a documented
sub-resolution tie window.  Instead every time is encoded as an (hi, lo)
pair of int32 words whose *lexicographic signed comparison* reproduces the
exact float64 total order for non-NaN inputs:

  bits   = bitcast(x, u64)
  mono   = bits ^ 0x8000..0  if x >= 0 else  ~bits    (monotone u64 map)
  hi, lo = mono's 32-bit words, each mapped u32 -> signed-i32 order
           by XOR 0x80000000

All three steps fuse into one arithmetic shift and two XORs per word.  The
encoding is exact: distinct doubles get distinct key pairs and ties are
exactly float64 ties, so kernel sort order equals the float64 tiers'
order unconditionally -- there is no precision caveat and no tie window.

Conventions shared by the kernels:

  * every non-finite input (the +inf "dropped" convention) maps to the
    +inf key ``(HI_INF, LO_INF)``;
  * ``(I32_MAX, I32_MAX)`` sorts strictly above the +inf key and is free
    for pow2-padding lanes;
  * ``(I32_MIN, I32_MIN)`` sorts strictly below every double and seeds
    watermark prefix maxima (the -inf analogue).

float32 inputs are accepted too (single-word bits, zero low word): the
same transform gives the exact float32 total order.  The only refinement
over IEEE ``<`` in either width is that -0.0 keys below +0.0 instead of
comparing equal -- time values are never signed zeros.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

I32_MIN = -0x80000000
I32_MAX = 0x7FFFFFFF
# encoded +inf: float64 +inf has bit pattern 0x7FF00000_00000000; the
# sign-branch is a no-op and the low word maps to I32_MIN.
HI_INF = 0x7FF00000
LO_INF = I32_MIN


def _f32_key(v):
    """Signed-int32 key of float32 `v`: integer order == float order."""
    bits = jax.lax.bitcast_convert_type(v, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(I32_MAX))


def time_sort_keys(x, *, f32_pair: bool = False):
    """Encode float times as (hi, lo) int32 words.

    Lexicographic signed comparison of the pairs equals the exact IEEE
    total order of the input dtype (non-NaN).  Non-finite inputs all map
    to the +inf key ``(HI_INF, LO_INF)``.

    ``f32_pair`` encodes a float64 by its float32 split instead of its
    IEEE bits: hi = x rounded to float32, lo = the float32 remainder.  The
    TPU stores float64 as exactly such a pair (XLA's X64 split) and cannot
    bitcast it, so kernels compiled for the TPU use this form.  It is
    exact for the TPU's float64 but not for IEEE doubles, whose 53-bit
    mantissa the two float32 words cannot hold.
    """
    if x.dtype == jnp.float64 and f32_pair:
        hi32 = x.astype(jnp.float32)
        lo32 = (x - hi32.astype(jnp.float64)).astype(jnp.float32)
        # canonical +0.0 remainder, so equal values get equal keys
        lo32 = jnp.where(lo32 == 0, jnp.float32(0), lo32)
        hi_k, lo_k = _f32_key(hi32), _f32_key(lo32)
    else:
        if x.dtype == jnp.float64:
            bits = jax.lax.bitcast_convert_type(x, jnp.int32)  # LE words
            lo, hi = bits[..., 0], bits[..., 1]
        else:
            hi = jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                              jnp.int32)
            lo = jnp.zeros_like(hi)
        s = hi >> 31                              # 0 (x >= 0) or -1 (x < 0)
        hi_k = hi ^ (s & jnp.int32(I32_MAX))
        lo_k = (lo ^ s) ^ jnp.int32(I32_MIN)
    isfin = jnp.isfinite(x)
    return (jnp.where(isfin, hi_k, jnp.int32(HI_INF)),
            jnp.where(isfin, lo_k, jnp.int32(LO_INF)))


def lex_gt(a, b):
    """Lexicographic ``a > b`` over equal-length tuples of int arrays."""
    gt = None
    eq = None
    for ak, bk in zip(a, b):
        g = ak > bk
        gt = g if gt is None else gt | (eq & g)
        eq = (ak == bk) if eq is None else eq & (ak == bk)
    return gt


__all__ = ["time_sort_keys", "lex_gt",
           "I32_MIN", "I32_MAX", "HI_INF", "LO_INF"]
