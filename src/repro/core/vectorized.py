"""Vectorized JAX Monte-Carlo of DOM + Nezha protocol dynamics.

The event-driven implementation (repro.core.replica) is exact but Python-
slow; the large benchmark sweeps (Figs 1-3, 8, 10, 11) need millions of
requests. This module reformulates the *steady-state data plane* of the
protocol as pure array programs:

  given per-(request, replica) arrival times, clock offsets and deadlines,
  compute -- entirely with array ops --
    * early-buffer admission (event-ordered watermark scan, O(N log N)),
    * release times (max(deadline, arrival) under admission),
    * fast/slow commit classification and commit latencies,
    * reordering scores (LIS via O(n log n) patience counts is replaced by
      a rank-based pairwise estimator for differentiability-free speed).

Admission comes in two roles:

  oracle      `dom_release_schedule` -- the original O(N^2) lax.scan that
              replays the early-buffer semantics literally.  Kept ONLY as
              the property-test oracle and for tiny instances; every
              production path below is checked against it.
  production  the watermark formulation (`dom_admit_watermark_np`,
              `dom_admit_watermark_jnp`, and the fused Pallas kernel in
              repro.kernels.dom_admit).  Key fact: a message j is released
              by time t iff admitted(j) and max(d_j, a_j) <= t, so when
              messages are processed in candidate-release order max(d, a)
              the released-deadline watermark is a monotone scalar.  A
              rejected message's deadline never exceeds the watermark that
              rejected it, so the watermark is a plain prefix max over ALL
              deadlines in event order -- admission is one sort plus one
              O(N) pass (O(N log N) total, down from O(N^2) work and
              O(N^2) memory traffic in the scan).

Everything is jit-compatible; the same code paths serve (a) the paper-figure
benchmarks and (b) the deadline-ordered gradient-aggregation planner in
repro.parallel.collectives (it reuses `dom_release_schedule`).

The staged epoch pipeline (admission tiers, commit classification, epoch
closed loop, fault epochs) lives in `repro.core.engine`; this module keeps
the DOM release-schedule primitives the tiers dispatch to, the reordering
metrics, and the one-shot `nezha_commit_times` compatibility wrapper.

Correspondence with the exact simulator is asserted in
tests/test_properties.py on small instances.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class VecDomParams:
    percentile: float = 50.0
    beta: float = 3.0
    clamp_d: float = 200e-6
    window: int = 1000


# ---------------------------------------------------------------------------
# DOM release schedule
# ---------------------------------------------------------------------------
def _release_one_receiver(deadlines: jnp.ndarray, arrivals: jnp.ndarray) -> jnp.ndarray:
    """Exact early-buffer admission for ONE receiver via lax.scan.

    Processes messages in arrival order; message m is admitted iff
    d_m > max{ d_j : admitted(j), a_j < a_m, d_j <= a_m } -- i.e. larger than
    every deadline already *released* when m arrives. O(N^2) but fully
    vectorized per scan step.
    """
    N = deadlines.shape[0]
    order = jnp.argsort(arrivals, stable=True)
    d_by_arr = deadlines[order]
    a_by_arr = arrivals[order]

    def step(admitted_d, i):
        a_i = a_by_arr[i]
        d_i = d_by_arr[i]
        # deadlines of already-admitted messages that have been released by a_i
        released = jnp.where(jnp.isfinite(admitted_d) & (admitted_d <= a_i),
                             admitted_d, -jnp.inf)
        w = jnp.max(released)
        admit = (d_i > w) & jnp.isfinite(a_i)
        admitted_d = admitted_d.at[i].set(jnp.where(admit, d_i, jnp.inf))
        return admitted_d, admit

    init = jnp.full((N,), jnp.inf)
    _, admit_by_arr = jax.lax.scan(step, init, jnp.arange(N))
    # scatter back to original message order
    admitted = jnp.zeros((N,), dtype=bool).at[order].set(admit_by_arr)
    return admitted


@jax.jit
def _dom_release_schedule_impl(deadlines: jnp.ndarray,
                               arrivals: jnp.ndarray) -> tuple:
    d = deadlines[:, None]
    admitted = jax.vmap(_release_one_receiver, in_axes=(None, 1), out_axes=1)(
        deadlines, arrivals)
    release = jnp.where(admitted, jnp.maximum(d, arrivals), jnp.inf)
    return admitted, release


def dom_release_schedule(deadlines, arrivals) -> tuple:
    """Per-receiver DOM early-buffer semantics, vectorized (exact).

    Args:
      deadlines: [N] message deadlines (global synchronized time).
      arrivals:  [N, R] arrival time of each message at each receiver
                 (+inf = dropped).

    Returns:
      admitted:  [N, R] bool -- entered the early-buffer.
      release:   [N, R] release time (inf if not admitted/dropped).

    Semantics match repro.core.dom.EarlyBuffer exactly (asserted by the
    property tests): a message is admitted iff its deadline exceeds the
    largest deadline already *released* at its arrival; admitted messages
    release at max(deadline, arrival), in deadline order.

    Conversion happens under `enable_x64` so float64 inputs are traced in
    float64 (jit specializes per input dtype; float32 inputs stay float32).
    Without this, callers outside an x64 context -- the chunked fast path,
    the kernel reference oracle -- silently got float32 admission, which
    collapses sub-microsecond deadline separations.
    """
    with jax.enable_x64(True):
        return _dom_release_schedule_impl(jnp.asarray(deadlines),
                                          jnp.asarray(arrivals))


# ---------------------------------------------------------------------------
# Watermark admission (production path, O(N log N))
# ---------------------------------------------------------------------------
# Early-buffer admission replayed as a 2N-event stream per receiver:
#
#   test event    at a_i  -- decide admission of i against the watermark;
#   update event  at r_i = max(d_i, a_i) -- i's candidate release raises the
#                 watermark to max(W, d_i).
#
# Watermark updates are UNCONDITIONAL: an admitted message releases at r_i by
# definition, and a rejected message satisfies d_i <= W already, so folding
# its deadline into the running max changes nothing.  That removes the
# admitted-set carry entirely -- the watermark is a prefix max of deadlines
# in event order.
#
# Event order (ties matter; this mirrors the exact scan's stable arrival
# processing, in which a release at time t counts against an arrival at t):
#   key = (time, class, message, kind) with
#     class    0 for an in-flight release (d > a, fires at d), 1 for arrival
#              events (tests, and at-arrival releases where d <= a);
#     message  the original index -- for tied arrival times this equals the
#              stable arrival rank, interleaving each at-arrival release
#              right after its own admission test;
#     kind     test (0) before the same message's at-arrival update (1).
# The composite (class, message, kind) packs into one integer aux key, so
# the sort is a two-key lexsort.  Non-finite deadlines are admitted but
# masked out of the watermark (they never release), matching the oracle.
def _admit_events_aux(n: int, dtype=np.int64):
    """aux keys for [test events | update events] given per-update class."""
    idx = np.arange(n, dtype=dtype)
    test_aux = (n + idx) * 2
    return idx, test_aux


def dom_admit_watermark_np(deadlines: np.ndarray,
                           arrivals: np.ndarray) -> np.ndarray:
    """Event-ordered watermark admission (numpy). [N],[N,R] -> [N,R] bool."""
    d = np.asarray(deadlines, np.float64)
    a = np.asarray(arrivals, np.float64)
    N, R = a.shape
    admitted = np.zeros((N, R), dtype=bool)
    if N == 0:
        return admitted
    idx, test_aux = _admit_events_aux(N)
    contrib = np.where(np.isfinite(d), d, -np.inf)
    no_upd = np.full(N, -np.inf)
    for r in range(R):
        ar = a[:, r]
        times = np.concatenate([ar, np.maximum(d, ar)])
        cls = np.where(d > ar, 0, N)            # class * N, pre-scaled
        aux = np.concatenate([test_aux, (cls + idx) * 2 + 1])
        order = np.lexsort((aux, times))
        runmax = np.maximum.accumulate(
            np.concatenate([no_upd, contrib])[order])
        excl = np.concatenate([[-np.inf], runmax[:-1]])
        is_test = order < N
        m = order[is_test]
        admitted[m, r] = (d[m] > excl[is_test]) & np.isfinite(ar[m])
    return admitted


def dom_release_schedule_watermark(deadlines: np.ndarray,
                                   arrivals: np.ndarray
                                   ) -> tuple[np.ndarray, np.ndarray]:
    """O(N log N) admission + release times, numpy (the NumpyTier hot path).

    Exact w.r.t. `dom_release_schedule` (property-tested, including
    duplicate deadlines, late arrivals and dropped receivers) without the
    chunk+halo machinery the old chunked path needed.
    """
    d = np.asarray(deadlines, np.float64)
    a = np.asarray(arrivals, np.float64)
    admitted = dom_admit_watermark_np(d, a)
    release = np.where(admitted, np.maximum(d[:, None], a), np.inf)
    return admitted, release


def dom_admit_watermark_jnp(deadlines: jnp.ndarray,
                            arrivals: jnp.ndarray) -> jnp.ndarray:
    """Traceable watermark admission: [N],[N,R] -> [N,R] bool.

    Same event construction as `dom_admit_watermark_np`, with the sequential
    O(N^2) scan carry replaced by sort + prefix max (O(1) carried state).
    Runs at whatever precision the caller traces it at -- the engine's
    fused epoch step traces it under float64 for exact numpy-tier parity.

    Receivers are unrolled (R = 2f+1 is static and small), not vmapped:
    XLA:TPU compiles a batched float64 sort super-linearly in N (minutes at
    N=2048), while R separate 1-D sorts compile in seconds.  The prefix max
    is a log-step `associative_scan`; `lax.cummax` lowers to a float64
    reduce-window that alone takes seconds to compile on the TPU.  Max is
    exact, so both give the same bits.  The tie-break key and the carried
    index are int32 (aux < 4N): a sort over emulated int64 operands
    compiles about twice as slowly on the TPU.
    """
    d = deadlines
    N = d.shape[0]
    idx = jnp.arange(N, dtype=jnp.int32)
    contrib = jnp.where(jnp.isfinite(d), d, -jnp.inf)
    no_upd = jnp.full((N,), -jnp.inf, d.dtype)

    def one_receiver(ar):
        times = jnp.concatenate([ar, jnp.maximum(d, ar)])
        cls = jnp.where(d > ar, 0, N).astype(jnp.int32)
        aux = jnp.concatenate([(N + idx) * 2, (cls + idx) * 2 + 1])
        _, _, order = jax.lax.sort(
            (times, aux, jnp.arange(2 * N, dtype=jnp.int32)), num_keys=2)
        runmax = jax.lax.associative_scan(
            jnp.maximum, jnp.concatenate([no_upd, contrib])[order])
        excl = jnp.concatenate([jnp.full((1,), -jnp.inf, d.dtype),
                                runmax[:-1]])
        is_test = order < N
        m = jnp.where(is_test, order, N)        # N = out-of-bounds, dropped
        ok = is_test & (d[jnp.minimum(m, N - 1)] > excl) \
            & jnp.isfinite(ar[jnp.minimum(m, N - 1)])
        return jnp.zeros((N,), bool).at[m].set(ok, mode="drop")

    return jnp.stack([one_receiver(arrivals[:, r])
                      for r in range(arrivals.shape[1])], axis=1)


@jax.jit
def _watermark_schedule_jit(deadlines, arrivals):
    admitted = dom_admit_watermark_jnp(deadlines, arrivals)
    release = jnp.where(admitted, jnp.maximum(deadlines[:, None], arrivals),
                        jnp.inf)
    return admitted, release


def dom_release_schedule_chunked(deadlines: np.ndarray, arrivals: np.ndarray,
                                 chunk: int = 2048) -> tuple[np.ndarray, np.ndarray]:
    """Chunked (deadline-sorted) variant for large N.  LEGACY.

    Superseded by `dom_release_schedule_watermark` (O(N log N), no chunk
    tuning, no halo blow-up under heavy reordering); kept as the pre-PR
    baseline the `dom_scale` benchmark measures speedups against.

    Each chunk is processed exactly, extended by a *halo* of later-deadline
    messages whose deadlines fall within the maximum observed arrival
    lateness of the chunk's tail -- those are the only later messages that
    can be released before a chunk message arrives and reject it. Across
    chunks the released-deadline watermark carries forward. Agreement with
    the exact scan (`dom_release_schedule`) is property-tested.
    """
    order = np.argsort(deadlines, kind="stable")
    inv = np.argsort(order, kind="stable")
    d_sorted = deadlines[order]
    a_sorted = arrivals[order]
    N, R = arrivals.shape
    fin_a = np.where(np.isfinite(a_sorted), a_sorted, -np.inf)
    max_late = max(0.0, float(np.max(fin_a - d_sorted[:, None], initial=0.0)))
    admitted = np.zeros((N, R), dtype=bool)
    release = np.full((N, R), np.inf)
    watermark = np.full((R,), -np.inf)
    for lo in range(0, N, chunk):
        hi = min(lo + chunk, N)
        # halo: later-deadline messages that could reject a chunk member
        hi_ext = int(np.searchsorted(d_sorted, d_sorted[hi - 1] + max_late,
                                     side="right"))
        hi_ext = min(max(hi_ext, hi), N)
        # numpy float64 in: the oracle converts under enable_x64, so the
        # chunk is admitted at full deadline precision
        adm, rel = dom_release_schedule(d_sorted[lo:hi_ext],
                                        a_sorted[lo:hi_ext])
        adm = np.asarray(adm)[: hi - lo]  # lint: allow[HS003] per-chunk boundary pull of the oracle's device result
        # Apply the carried watermark: a message also needs deadline > the
        # largest deadline released in prior chunks *before its arrival*.
        bad = d_sorted[lo:hi, None] <= watermark[None, :]
        adm = adm & ~bad
        rel = np.where(adm, np.maximum(d_sorted[lo:hi, None], a_sorted[lo:hi]), np.inf)
        admitted[lo:hi] = adm
        release[lo:hi] = rel
        fin = np.isfinite(rel)
        if fin.any():
            watermark = np.maximum(watermark,
                                   np.max(np.where(fin, d_sorted[lo:hi, None], -np.inf), axis=0))
    return admitted[inv], release[inv]


# ---------------------------------------------------------------------------
# Nezha commit classification
# ---------------------------------------------------------------------------
def nezha_commit_times(
    deadlines: np.ndarray,          # [N] request deadlines (proxy-stamped)
    arrivals: np.ndarray,           # [N, R] request arrival at each replica
    reply_owd: np.ndarray,          # [N, R] replica->proxy reply delay
    leader: int,
    f: int,
    mod_owd: Optional[np.ndarray] = None,   # [N, R] leader->follower log-mod delay
    leader_batch_delay: float = 50e-6,
    key_ids: Optional[np.ndarray] = None,   # [N] commutativity class per request
) -> dict:
    """Classify each request's commit path and commit time at the proxy.

    Fast path: request admitted at leader + enough followers with *identical
    log prefixes*. In steady state, hash-consistency at request m's release
    equals "the set of admitted non-commutative requests with smaller
    deadline is identical" -- we approximate set-identity by requiring the
    follower to have admitted m AND every smaller-deadline request the leader
    admitted that m's reply hash covers.

    `key_ids` enables the paper's commutativity relaxation (S8.2) without
    per-class Python loops: requests only hash-conflict *within* their key
    class, so the prefix-disagreement count is segmented per class instead of
    global. Omit it for the no-commutativity model (every request conflicts
    with every other).

    Returns dict with commit_time[N], fast[N], committed[N].

    This is the one-shot compatibility form; the staged engine
    (`repro.core.engine`) computes admission/release through a compute tier
    and calls `classify_commits` directly.
    """
    from repro.core.engine import classify_commits

    admitted, release = dom_release_schedule_watermark(deadlines, arrivals)
    admitted = np.asarray(admitted)
    release = np.asarray(release)
    res = classify_commits(
        deadlines, arrivals, admitted, release, reply_owd, leader, f,
        mod_owd=mod_owd, leader_batch_delay=leader_batch_delay,
        key_ids=key_ids)
    res["admitted"] = admitted
    res["release"] = release
    return res


# ---------------------------------------------------------------------------
# Reordering score (vectorized LIS via patience counting in numpy)
# ---------------------------------------------------------------------------
def reordering_score_np(ref_ranks: np.ndarray) -> float:
    """1 - LIS/len over an array of reference ranks (see sim.network)."""
    import bisect

    tails: list = []
    for x in ref_ranks.tolist():
        i = bisect.bisect_left(tails, x)
        if i == len(tails):
            tails.append(x)
        else:
            tails[i] = x
    if ref_ranks.size == 0:
        return 0.0
    return (1.0 - len(tails) / ref_ranks.size) * 100.0


def multicast_reordering(owd: np.ndarray, send_times: np.ndarray) -> float:
    """Fig 1-2 metric: reordering of receiver 2 w.r.t. receiver 1.

    owd: [N, 2] one-way delays; send_times: [N].
    """
    t1 = send_times + owd[:, 0]
    t2 = send_times + owd[:, 1]
    order1 = np.argsort(t1, kind="stable")
    rank1 = np.empty_like(order1)
    rank1[order1] = np.arange(len(order1))
    order2 = np.argsort(t2, kind="stable")
    return reordering_score_np(rank1[order2])


def dom_reordering(owd: np.ndarray, send_times: np.ndarray, deadlines: np.ndarray) -> float:
    """Fig 3: reordering of the *released* sequences under DOM."""
    arrivals = send_times[:, None] + owd
    admitted, release = dom_release_schedule_watermark(deadlines, arrivals)
    both = admitted[:, 0] & admitted[:, 1]
    r1, r2 = release[both, 0], release[both, 1]
    order1 = np.argsort(r1, kind="stable")
    rank1 = np.empty_like(order1)
    rank1[order1] = np.arange(len(order1))
    order2 = np.argsort(r2, kind="stable")
    return reordering_score_np(rank1[order2])


__all__ = [
    "VecDomParams",
    "dom_release_schedule",
    "dom_release_schedule_chunked",
    "dom_release_schedule_watermark",
    "dom_admit_watermark_np",
    "dom_admit_watermark_jnp",
    "nezha_commit_times",
    "multicast_reordering",
    "dom_reordering",
    "reordering_score_np",
]
