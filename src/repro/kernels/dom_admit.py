"""Pallas TPU kernel: fused DOM early-buffer admission (event watermark).

The production admission algorithm (repro.core.vectorized, watermark
formulation) is sort + prefix-max: replay each receiver's 2N-event stream
(test at arrival a_i, watermark update at candidate release max(d_i, a_i))
in (time, aux) order and admit i iff d_i exceeds the running deadline
prefix-max just before its test event.  This kernel fuses the whole thing
on-device per receiver:

  bitonic event sort  ->  log-step exclusive prefix max  ->  bitonic unsort

so the pallas compute tier runs admission without borrowing the jit scan.

Layout: every event array is a 2-D ``(rows, 128)`` int32 tile stack, flat
index ``i = row * 128 + lane``.  Both the bitonic network and the prefix
max move data only between XOR partners ``i ^ stride`` (power-of-two
strides): a lane rotation below 128, a row rotation above.  So the kernel
needs no gather, no reshape and no concatenation -- the ops Mosaic lowers
on (8, 128) tiles.  Compiled arrays hold at least one full tile.

Event times are compared as exact two-word int32 keys
(repro.kernels.timekeys): the lexicographic (hi, lo) order *is* the
float64 total order, so admission matches the float64 tiers bit for bit --
ties included, broken by the same integer aux key as the float64 paths.
The keys and the event stream are built outside the kernel, and the
`pallas_call` is traced with 64-bit types off, so the kernel body and its
index maps are pure int32.

Oracle: repro.core.vectorized.dom_admit_watermark_np (itself property-
tested against the exact O(N^2) scan and the event-driven EarlyBuffer).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.timekeys import HI_INF, I32_MIN, LO_INF, lex_gt, time_sort_keys

LANES = 128
TILE = 8 * LANES           # one (8, 128) int32 tile


def padded_size(n: int, interpret: bool) -> int:
    """Power-of-two length >= n in whole rows of 128 lanes.

    Compiled, the array also fills at least one whole (8, 128) tile;
    interpreted (on the CPU), one row is enough, which keeps the unrolled
    network small for the small batches the tests use.
    """
    return max(1 << max(int(n) - 1, 0).bit_length(),
               LANES if interpret else TILE)


def to_tiles(x):
    """[..., m] -> [..., m // 128, 128] (m a multiple of 128)."""
    return x.reshape(*x.shape[:-1], x.shape[-1] // LANES, LANES)


def _flat_iota(shape):
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1]
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _xor_partner(stride, iota):
    """Returns x -> x at flat index ``i ^ stride`` (stride a power of two).

    Strides below the lane width rotate lanes; wider strides rotate whole
    rows.  The partner sits one rotation forward or back depending on bit
    `stride` of i; the rotated index decides which, so the result does not
    depend on the rotation's sign convention.
    """
    rows, lanes = iota.shape
    if stride < lanes:
        axis, shift, size = 1, stride, lanes
    else:
        axis, shift, size = 0, stride // lanes, rows
    fwd = pltpu.roll(iota, shift, axis) == (iota ^ stride)

    def partner(x):
        return jnp.where(fwd, pltpu.roll(x, shift, axis),
                         pltpu.roll(x, size - shift, axis))

    return partner


def _bitonic_sort_multi(keys, vals):
    """Ascending bitonic sort by lexicographic `keys`, permuting `vals`.

    Arrays are (rows, 128) with a power-of-two element count.  Keys must
    be unique as tuples (every caller ends them with a distinct index), so
    each compare-exchange is a clean swap between the two partners.
    """
    shape = keys[0].shape
    m = shape[0] * shape[1]
    iota = _flat_iota(shape)
    for k in range(1, m.bit_length()):
        up = ((iota >> k) & 1) == 0
        for j in range(k - 1, -1, -1):
            stride = 1 << j
            partner = _xor_partner(stride, iota)
            pkeys = tuple(partner(x) for x in keys)
            keep_min = ((iota & stride) == 0) == up
            # boolean algebra, not a select of masks: Mosaic cannot
            # truncate a selected i1 vector
            swap = ((keep_min & lex_gt(keys, pkeys))
                    | (~keep_min & lex_gt(pkeys, keys)))
            keys = tuple(jnp.where(swap, p, x) for x, p in zip(keys, pkeys))
            vals = tuple(jnp.where(swap, partner(v), v) for v in vals)
    return keys, vals


def _exclusive_prefix_max_pair(hi, lo):
    """Lexicographic max of the (hi, lo) pairs strictly before each index.

    Butterfly scan over aligned blocks: after the stride-s step every
    element holds the max of its aligned 2s-block (`tot`) and of the part
    of that block before it (`pre`).  Index 0 gets (I32_MIN, I32_MIN).
    """
    shape = hi.shape
    m = shape[0] * shape[1]
    iota = _flat_iota(shape)
    tot_hi, tot_lo = hi, lo
    pre_hi = jnp.full(shape, I32_MIN, jnp.int32)
    pre_lo = pre_hi
    s = 1
    while s < m:
        partner = _xor_partner(s, iota)
        p_hi, p_lo = partner(tot_hi), partner(tot_lo)
        # the partner's block precedes i's block iff bit s of i is set
        take = ((iota & s) != 0) & lex_gt((p_hi, p_lo), (pre_hi, pre_lo))
        pre_hi = jnp.where(take, p_hi, pre_hi)
        pre_lo = jnp.where(take, p_lo, pre_lo)
        up = lex_gt((p_hi, p_lo), (tot_hi, tot_lo))
        tot_hi = jnp.where(up, p_hi, tot_hi)
        tot_lo = jnp.where(up, p_lo, tot_lo)
        s *= 2
    return pre_hi, pre_lo


def _dom_admit_kernel(thi_ref, tlo_ref, aux_ref, chi_ref, clo_ref, vhi_ref,
                      vlo_ref, admitted_ref):
    # One receiver's 2n events, (rows, 128) each: time key (thi, tlo),
    # tie-break aux, watermark contribution (chi, clo) and the message's
    # deadline key (vhi, vlo).  Every comparison is lexicographic over
    # int32 pairs == exact float64 compare.
    thi, tlo, aux = thi_ref[0], tlo_ref[0], aux_ref[0]
    shape = thi.shape
    n = shape[0] * shape[1] // 2
    (thi_s, tlo_s, aux_s), (chi_s, clo_s, vhi_s, vlo_s) = _bitonic_sort_multi(
        (thi, tlo, aux), (chi_ref[0], clo_ref[0], vhi_ref[0], vlo_ref[0]))

    ehi, elo = _exclusive_prefix_max_pair(chi_s, clo_s)
    is_test = (aux_s & 1) == 0
    d_gt_excl = lex_gt((vhi_s, vlo_s), (ehi, elo))
    t_fin = (thi_s != HI_INF) | (tlo_s != LO_INF)
    adm = (is_test & d_gt_excl & t_fin).astype(jnp.int32)

    # unsort: tests back to flat [0, n) by message, updates parked at [n, 2n)
    half = aux_s >> 1
    msg = jnp.where(half >= n, half - n, half)
    key2 = jnp.where(is_test, msg, n + msg)
    _, (adm_by_msg,) = _bitonic_sort_multi((key2,), (adm,))
    admitted_ref[0] = adm_by_msg


def _admit_events(dhi, dlo, ahi, alo):
    """The 2n-event stream of every receiver, as int32 [R, 2n] arrays.

    aux = (class*n + msg)*2 + kind packs the (class, message, kind)
    tie-break into one int; see core.vectorized.
    """
    R, n = ahi.shape
    idx = jnp.arange(n, dtype=jnp.int32)
    d_gt_a = (dhi > ahi) | ((dhi == ahi) & (dlo > alo))     # [R, n]
    rhi = jnp.where(d_gt_a, dhi, ahi)
    rlo = jnp.where(d_gt_a, dlo, alo)
    cls = jnp.where(d_gt_a, 0, n)
    test_aux = jnp.broadcast_to((n + idx) * 2, (R, n))
    d_fin = (dhi != HI_INF) | (dlo != LO_INF)
    bot = jnp.full((R, n), I32_MIN, jnp.int32)

    def both(first, second):
        return jnp.concatenate([jnp.broadcast_to(first, (R, n)),
                                jnp.broadcast_to(second, (R, n))], axis=1)

    return (both(ahi, rhi), both(alo, rlo),
            both(test_aux, (cls + idx) * 2 + 1),
            both(bot, jnp.where(d_fin, dhi, I32_MIN)),
            both(bot, jnp.where(d_fin, dlo, I32_MIN)),
            both(dhi, dhi), both(dlo, dlo))


@functools.partial(jax.jit, static_argnames=("interpret",))
def dom_admit_pallas(deadlines, arrivals, *, interpret=False):
    """deadlines [n] float, arrivals [R, n] float (+inf = dropped).

    Returns admitted [R, n] bool.  Times are encoded as exact int32
    (hi, lo) key words at the caller's input precision -- float64 in,
    float64-exact admission out.  n is padded so the 2n events fill
    whole rows (`padded_size`; pad lanes carry the +inf key for deadline
    and arrival: never admitted, never a watermark).  The grid iterates
    receivers; each program runs one receiver's full event network in
    VMEM.
    """
    R, n = arrivals.shape
    dhi, dlo = time_sort_keys(deadlines, f32_pair=not interpret)
    ahi, alo = time_sort_keys(arrivals, f32_pair=not interpret)
    n_pad = padded_size(2 * n, interpret) // 2
    with jax.enable_x64(False):
        if n_pad != n:
            dhi = jnp.pad(dhi, (0, n_pad - n), constant_values=HI_INF)
            dlo = jnp.pad(dlo, (0, n_pad - n), constant_values=LO_INF)
            ahi = jnp.pad(ahi, ((0, 0), (0, n_pad - n)),
                          constant_values=HI_INF)
            alo = jnp.pad(alo, ((0, 0), (0, n_pad - n)),
                          constant_values=LO_INF)
        events = tuple(to_tiles(e) for e in _admit_events(dhi, dlo, ahi, alo))
        rows = events[0].shape[1]
        block = pl.BlockSpec((1, rows, LANES), lambda r: (r, 0, 0))
        admitted = pl.pallas_call(
            _dom_admit_kernel,
            grid=(R,),
            in_specs=[block] * len(events),
            out_specs=block,
            out_shape=jax.ShapeDtypeStruct((R, rows, LANES), jnp.int32),
            interpret=interpret,
        )(*events)
    return admitted.reshape(R, 2 * n_pad)[:, :n] != 0


__all__ = ["dom_admit_pallas"]
