"""Pallas TPU kernel: deadline-ordered release (the DOM early-buffer drain).

At serving rates of 10^6 req/s the hot loop of a DOM receiver is "given the
admitted message set, emit the release order by deadline" -- an O(n log^2 n)
bitonic sorting network over (deadline-key, msg-id) tuples, laid out as
(rows, 128) int32 tiles and exchanged between XOR partners by lane/row
rotations (repro.kernels.dom_admit; no data-dependent gathers, which TPUs
hate).

Deadlines are compared as exact two-word int32 keys
(repro.kernels.timekeys) with the message index as the final sort key, so
the emitted order is EXACTLY the stable argsort of the float64 deadlines --
ties break by message id, identically to the float64 tiers; no precision
caveat.  Non-released lanes (deadline > clock_now, or not admitted) are
masked to the above-everything pad key and sort to the tail.  Output: msg
indices in release order + the count (a scalar, written to SMEM).

Oracle: masked stable argsort (repro.kernels.ops.dom_release_ref_order).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dom_admit import (
    LANES,
    _bitonic_sort_multi,
    _flat_iota,
    padded_size,
    to_tiles,
)
from repro.kernels.timekeys import HI_INF, I32_MAX, LO_INF, time_sort_keys


def _dom_release_kernel(dhi_ref, dlo_ref, admitted_ref, now_ref,
                        order_ref, count_ref):
    # Pure int32 body: (hi, lo) encoded deadline keys; the clock's key
    # words are two SMEM scalars.
    dhi = dhi_ref[...]
    dlo = dlo_ref[...]
    now_hi = now_ref[0]
    now_lo = now_ref[1]
    # released = admitted & (d <= now), lexicographic over the key pair
    d_le_now = (dhi < now_hi) | ((dhi == now_hi) & (dlo <= now_lo))
    released = (admitted_ref[...] != 0) & d_le_now
    top = jnp.int32(I32_MAX)
    khi = jnp.where(released, dhi, top)
    klo = jnp.where(released, dlo, top)
    idx = _flat_iota(dhi.shape)
    # message id is the final sort key: ties (and the masked tail) order by
    # id, making the released prefix the exact stable argsort by deadline
    (khi_s, _, idx_s), _ = _bitonic_sort_multi((khi, klo, idx), ())
    # the released messages are the sorted prefix; the count is its length
    # (a max, not a sum: Mosaic lowers an int32 sum through a re-trace that
    # promotes it to int64 under x64)
    released_s = khi_s != top
    order_ref[...] = jnp.where(released_s, idx_s, -1)
    count_ref[0] = jnp.max(jnp.where(released_s, idx + 1, 0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def dom_release_pallas(deadlines, admitted, clock_now, *, interpret=False):
    """deadlines [n] float, admitted [n] int8/bool, clock_now [] float.

    Returns (order [n] int32: message ids in release order, -1 padded;
             count [] int32). n is padded internally to whole rows of
             128 lanes (`padded_size`).
    Keys are exact int32 (hi, lo) words at the caller's input precision;
    the released prefix equals the stable argsort of the deadlines.
    """
    n = deadlines.shape[0]
    dhi, dlo = time_sort_keys(deadlines, f32_pair=not interpret)
    nhi, nlo = time_sort_keys(jnp.asarray(clock_now).reshape(1),
                              f32_pair=not interpret)
    n_pad = padded_size(n, interpret)
    with jax.enable_x64(False):
        adm = admitted.astype(jnp.int32)
        if n_pad != n:
            dhi = jnp.pad(dhi, (0, n_pad - n), constant_values=HI_INF)
            dlo = jnp.pad(dlo, (0, n_pad - n), constant_values=LO_INF)
            adm = jnp.pad(adm, (0, n_pad - n))
        vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        order, count = pl.pallas_call(
            _dom_release_kernel,
            in_specs=[vmem, vmem, vmem, smem],
            out_specs=[vmem, smem],
            out_shape=[jax.ShapeDtypeStruct((n_pad // LANES, LANES),
                                            jnp.int32),
                       jax.ShapeDtypeStruct((1,), jnp.int32)],
            interpret=interpret,
        )(to_tiles(dhi), to_tiles(dlo), to_tiles(adm),
          jnp.concatenate([nhi, nlo]))
    # Padded lanes are never released (admitted=0), so they sort to the tail
    # as -1 markers; slicing to n restores the caller's shape contract.
    return order.reshape(n_pad)[:n], count[0]


__all__ = ["dom_release_pallas"]
