"""Staged DOM engine: the vectorized Nezha data plane as composable stages.

The monolithic `_process_batch` of the original vectorized backend is split
into five explicit stages, run in order over an `EpochState`:

  SampleStage   bulk per-epoch network sampling (client->proxy, proxy->replica
                multicast, replica->proxy replies, proxy->client delivery --
                reply paths sampled per *actual* submitting client node);
  StampStage    proxy stamping + DOM deadline bounding (sliding-window OWD
                percentile pool carried across epochs + clock-error margin,
                clamped to D);
  DomStage      DOM early-buffer admission + release schedule;
  CommitStage   fast/slow commit classification (prefix hash-consistency vs
                the leader, per-key-class commutativity, quorum arithmetic);
  DeliverStage  commit delivery at the client and latency accounting;
  LogStage      cross-epoch replica-log bookkeeping (`ReplicaLogState`):
                committed entries enter the shared synced log in execution
                order, uncommitted-but-admitted entries become per-replica
                speculative tails -- the exact state the vectorized
                MERGE-LOG (repro.core.recovery) consults at a view change.

Stages that run array programs dispatch through a pluggable **compute tier**.
Admission in every tier is the O(N log N) event-ordered watermark scan
(`repro.core.vectorized`, one sort + one prefix-max pass per receiver --
the O(N^2) `dom_release_schedule` lax.scan survives only as the
property-test oracle):

  numpy    `dom_release_schedule_watermark` -- lexsort + maximum.accumulate
           in float64 numpy (the CPU default);
  jit      the same watermark admission as one jitted float64 program, and
           the whole stamp->dom->commit epoch fused into a single device
           dispatch (see below);
  pallas   fused epochs like jit, but admission runs in the
           `repro.kernels.dom_admit` bitonic-event-sort + prefix-max kernel
           and release ordering in the `repro.kernels.ops.dom_release`
           bitonic kernel (interpreted on the CPU). Event times are compared
           as exact two-word int32 keys (repro.kernels.timekeys), so kernel
           sort order equals the float64 tiers' order unconditionally --
           ties included; there is no precision caveat.

**Fused single-dispatch epochs**: tiers with ``fused = True`` (jit, pallas)
replace the Stamp/Dom/Commit stages with one `FusedEpochStage` whose body is
a single jitted program -- ring-pool OWD fold + sliding-percentile deadline
bounding, the mean-reply fetch estimate, watermark admission, release
times, and the quorum arithmetic of `classify_commits` as jnp ops over the
pow2-padded batch, traced under float64 (`jax.enable_x64`) so
the release/commit boundary no longer needs the host-side float64 recompute
the old per-stage jit path did. The two formerly host-owned per-epoch
scalars -- the sliding-pool percentile ``bound`` and the mean-reply
``fetch`` -- are computed ON DEVICE from carried ring-buffer pool state,
bit-identical to the host estimators (`_partition_percentile` /
`_fetch_estimate`); the host keeps a cheap numpy mirror of the pool for
bookkeeping and fault-path epochs. The numpy tier keeps the five-stage
pipeline as the readable staged reference; `FusedEpochStage` is
regression-tested bit-for-bit against it.

**K-epochs-per-dispatch scan**: `DomEngine.run_epoch_window` wraps the same
epoch body in a `jax.lax.scan` over K epochs (K in `SCAN_K_BUCKETS`), with
the (pool, ptr, cnt) ring carry threaded through the scan and donated to
XLA off-CPU -- the data plane compiles to ONE program and performs ONE
device->host pull per K epoch generations instead of one per generation.
Fault and recovery boundaries (crash, relaunch, StartView, `release_floor`
changes, clock faults) segment the scan: the cluster's fast path
(`repro.core.vectorized_cluster`) only dispatches windows that are provably
fault-free and retry-closed, so K=1 sequential epochs remain bit-for-bit
identical to the staged numpy tier AND K>1 windows are bit-for-bit
identical to the same epochs run sequentially.

Epoch batches are padded to power-of-two buckets before tier dispatch so jit
recompilation is bounded by O(log N) distinct shapes per run instead of one
per epoch size; scan windows additionally share one bucket across their K
epochs (pad lanes are invisible to real rows by construction).

`classify_commits` is the tier-independent commit classifier (quorum order
statistics via O(R) `np.partition`, not full sorts); the legacy
`repro.core.vectorized.nezha_commit_times` wraps it for callers that want the
one-shot (admission + classification) form.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional, Union

import numpy as np

from repro import obs
from repro.core.clocksync import estimate_offsets
from repro.core.quorum import fast_quorum_size, slow_quorum_size
from repro.core.recovery import (
    merge_logs_vectorized,
    pack_uids,
    qualified_replicas,
)
from repro.core.vectorized import (
    dom_admit_watermark_jnp,
    dom_release_schedule_watermark,
)

# ---------------------------------------------------------------------------
# Pending-submission buffer (structured, amortized growth)
# ---------------------------------------------------------------------------
PENDING_DTYPE = np.dtype([
    ("t", np.float64),       # next attempt time (sim s)
    ("t0", np.float64),      # original submission time (latency baseline)
    ("cid", np.int64),       # submitting client id
    ("rid", np.int64),       # per-client request id
    ("kcls", np.int64),      # interned commutativity class (-1 = global)
    ("tries", np.int64),     # completed attempts (retry model)
    ("dl", np.float64),      # pre-stamped deadline (0.0 = stamp normally;
    #   > 0 = the sharded multi-op layer fixed this entry's global deadline
    #   before routing, so every group orders it at the same slot)
])


class PendingBuffer:
    """Growable structured array of pending submissions.

    Replaces the Python list-of-tuples buffer: appends are O(1) amortized and
    `pop_due` is a vectorized mask + stable time-sort instead of two list
    comprehensions over every pending request.
    """

    def __init__(self, capacity: int = 1024):
        self._buf = np.empty(max(capacity, 1), dtype=PENDING_DTYPE)
        self._n = 0

    def __len__(self) -> int:
        return self._n

    def _reserve(self, need: int) -> None:
        if self._buf.size < need:
            cap = self._buf.size
            while cap < need:
                cap *= 2
            grown = np.empty(cap, dtype=PENDING_DTYPE)
            grown[: self._n] = self._buf[: self._n]
            self._buf = grown

    def append(self, t: float, cid: int, rid: int, kcls: int,
               t0: Optional[float] = None, tries: int = 0,
               dl: float = 0.0) -> None:
        self._reserve(self._n + 1)
        self._buf[self._n] = (t, t if t0 is None else t0, cid, rid, kcls,
                              tries, dl)
        self._n += 1

    def extend(self, rows: np.ndarray) -> None:
        """Bulk re-enqueue of PENDING_DTYPE rows (the retry path)."""
        self._reserve(self._n + rows.size)
        self._buf[self._n: self._n + rows.size] = rows
        self._n += rows.size

    def min_time(self) -> float:
        if self._n == 0:
            return np.inf
        return float(self._buf["t"][: self._n].min())

    def has_prestamped(self) -> bool:
        """Any pending entry carrying a pre-stamped deadline (dl > 0)?
        Such epochs need the per-epoch step program (it takes the extra
        pre_dl operand); the scan fast path excludes them."""
        if self._n == 0:
            return False
        return bool((self._buf["dl"][: self._n] > 0).any())

    def pop_due(self, horizon: float) -> np.ndarray:
        """Remove and return all entries with t <= horizon, time-sorted."""
        view = self._buf[: self._n]
        due_mask = view["t"] <= horizon
        if not due_mask.any():
            return np.empty(0, dtype=PENDING_DTYPE)
        due = np.sort(view[due_mask], order="t", kind="stable")
        self._keep(~due_mask)
        return due

    def _keep(self, keep_mask: np.ndarray) -> None:
        rest = self._buf[: self._n][keep_mask].copy()
        self._n = rest.size
        if self._buf.size < rest.size:       # pragma: no cover - cannot shrink
            self._buf = np.empty(rest.size, dtype=PENDING_DTYPE)
        self._buf[: self._n] = rest

    def _uid_mask(self, cid: np.ndarray, rid: np.ndarray) -> np.ndarray:
        view = self._buf[: self._n]
        return np.isin(pack_uids(view["cid"], view["rid"]),
                       pack_uids(cid, rid))

    def uids(self) -> np.ndarray:
        """Packed uids of every pending attempt (sharded abandonment
        accounting: a request neither committed nor pending anywhere was
        given up on)."""
        view = self._buf[: self._n]
        return pack_uids(view["cid"], view["rid"])

    def pop_uids(self, cid: np.ndarray, rid: np.ndarray) -> np.ndarray:
        """Remove and return the pending attempts of the given requests
        (the recovery path: a merged speculative entry commits through the
        view change, so its client stops retrying)."""
        if self._n == 0:
            return np.empty(0, dtype=PENDING_DTYPE)
        mask = self._uid_mask(cid, rid)
        taken = self._buf[: self._n][mask].copy()
        if taken.size:
            self._keep(~mask)
        return taken

    def reschedule_uids(self, cid: np.ndarray, rid: np.ndarray,
                        t: float) -> None:
        """Pull the given requests' next attempt up to ``t`` at the latest
        (proxy retransmission of un-merged entries at StartView)."""
        if self._n == 0:
            return
        mask = self._uid_mask(cid, rid)
        view = self._buf[: self._n]
        view["t"][mask] = np.minimum(view["t"][mask], t)


# ---------------------------------------------------------------------------
# Compute tiers
# ---------------------------------------------------------------------------
def _pow2_bucket(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length()) if n > 1 else 1


class ComputeTier:
    """Backend for the DOM hot loops; see module docstring for the tiers.

    The fused epoch programs (`epoch_step`, `epoch_scan`) are shared by
    every instance of a tier class in the process (`_EPOCH_PROGRAMS`), so
    `admit_traced` and `order_traced` must not read instance state: a
    program traced through one instance runs for all of them.  A tier whose
    `shares_programs()` is false keeps its programs per instance.
    """

    name = "abstract"
    # Pad epoch batches to pow2 buckets before release_schedule? True for
    # jit-compiled tiers (bounds recompilation to O(log N) shapes per run);
    # pointless extra work for the numpy tier.
    pad_batches = False
    # Fused tiers run stamp->dom->commit as ONE jitted device dispatch per
    # epoch generation (FusedEpochStage) instead of the staged numpy path,
    # and support the K-epochs-per-dispatch lax.scan window (`epoch_scan`).
    fused = False

    def release_schedule(self, deadlines: np.ndarray,
                         arrivals: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Early-buffer admission + release times: ([N,R] bool, [N,R] f64)."""
        raise NotImplementedError

    def deadline_order(self, deadlines: np.ndarray) -> np.ndarray:
        """Message indices sorted by deadline (the release/ordering sort)."""
        return np.argsort(deadlines, kind="stable")

    # -- traceable hooks consumed by the fused epoch program -----------------
    def admit_traced(self, deadlines, arrivals):
        """jnp admission [N],[N,R] -> [N,R] bool inside the fused program."""
        raise NotImplementedError

    def order_traced(self, deadlines):
        """jnp deadline order [N] -> [N] inside the fused program."""
        raise NotImplementedError

    def epoch_step(self, f: int, use_kcls: bool, use_cap: bool = False):
        """The fused stamp->dom->commit program (jitted, cached per shape)."""
        return self._epoch_program("step", f, use_kcls, use_cap)

    def epoch_scan(self, f: int, use_kcls: bool, use_cap: bool = False):
        """The K-epochs-per-dispatch `lax.scan` program (fault-free path)."""
        return self._epoch_program("scan", f, use_kcls, use_cap)

    def _epoch_program(self, kind: str, f: int, use_kcls: bool,
                       use_cap: bool):
        # The instance memo keeps the per-epoch lookup to one dict get and
        # counts each key once per instance (one tier per cluster).
        memo = self.__dict__.setdefault("_programs", {})
        key = (kind, type(self), f, use_kcls, use_cap)
        program = memo.get(key)
        if program is None:
            shared = _EPOCH_PROGRAMS if self.shares_programs() else {}
            program = shared.get(key)
            if program is None:
                obs.count("programs_shared.miss")
                build = _build_fused_step if kind == "step" \
                    else _build_fused_scan
                program = shared[key] = build(self, f, use_kcls, use_cap)
            else:
                obs.count("programs_shared.hit")
            memo[key] = program
        return program

    def shares_programs(self) -> bool:
        """Whether this tier's epoch programs go in the process-wide
        registry (and so outlive the tier with every executable they
        compiled)."""
        return True


class NumpyTier(ComputeTier):
    """Float64 numpy watermark admission (lexsort + maximum.accumulate)."""

    name = "numpy"

    def __init__(self, chunk: int = 2048):
        # `chunk` kept for construction compatibility; the watermark path
        # needs no chunk/halo tuning.
        self.chunk = chunk

    def release_schedule(self, deadlines, arrivals):
        return dom_release_schedule_watermark(deadlines, arrivals)


class JitTier(ComputeTier):
    """Watermark admission as one jitted float64 program; fused epochs."""

    name = "jit"
    pad_batches = True
    fused = True

    def release_schedule(self, deadlines, arrivals):
        import jax
        import jax.numpy as jnp

        from repro.core.vectorized import _watermark_schedule_jit

        # Traced under x64 so admission AND release are float64 end to end;
        # no host-side boundary recompute needed.
        with jax.enable_x64(True):
            adm, rel = _watermark_schedule_jit(
                jnp.asarray(np.asarray(deadlines, np.float64)),
                jnp.asarray(np.asarray(arrivals, np.float64)))
            # lint: allow[HS003] the documented per-epoch device->host pull at the staged-tier boundary
            return np.asarray(adm), np.asarray(rel)

    def admit_traced(self, deadlines, arrivals):
        return dom_admit_watermark_jnp(deadlines, arrivals)

    def order_traced(self, deadlines):
        import jax.numpy as jnp

        return jnp.argsort(deadlines, stable=True)


class PallasTier(JitTier):
    """Fused epochs with admission + ordering on-device via Pallas kernels.

    Admission runs in `repro.kernels.dom_admit` (bitonic event sort fused
    with the watermark prefix-max, one grid program per receiver); the
    release/deadline ordering runs in the `repro.kernels.ops.dom_release`
    bitonic kernel; on the CPU they run interpreted. Both compare times as
    exact two-word int32 keys (repro.kernels.timekeys), so the kernel order
    equals the float64 tiers' order unconditionally -- ties included.
    """

    name = "pallas"

    def release_schedule(self, deadlines, arrivals):
        from repro.kernels.ops import dom_admit

        d = np.asarray(deadlines, np.float64)
        a = np.asarray(arrivals, np.float64)
        adm = dom_admit(d, a, use_pallas=True)
        rel = np.where(adm, np.maximum(d[:, None], a), np.inf)
        return adm, rel

    def shares_programs(self) -> bool:
        # Interpreted on the CPU, each compiled shape of a kernel program
        # holds thousands of memory mappings; kept for the life of the
        # process across a catalog of scenarios they exceed the kernel's
        # mapping limit and XLA's compiler aborts.  Compiled for the TPU
        # they are small, and shared like the jit tier's.
        from repro.kernels.ops import interpret_mode

        return not interpret_mode()

    def deadline_order(self, deadlines):
        from repro.kernels.ops import dom_deadline_order

        return dom_deadline_order(deadlines, use_pallas=True)

    def admit_traced(self, deadlines, arrivals):
        from repro.kernels.ops import dom_admit_traced

        return dom_admit_traced(deadlines, arrivals, use_pallas=True)

    def order_traced(self, deadlines):
        from repro.kernels.ops import dom_deadline_order_traced

        return dom_deadline_order_traced(deadlines, use_pallas=True)


TIERS: dict[str, type] = {"numpy": NumpyTier, "jit": JitTier, "pallas": PallasTier}


def make_tier(tier: Union[str, ComputeTier]) -> ComputeTier:
    if isinstance(tier, ComputeTier):
        return tier
    try:
        return TIERS[tier]()
    except KeyError:
        raise KeyError(f"unknown compute tier {tier!r}; available: {', '.join(TIERS)}")


# ---------------------------------------------------------------------------
# Commit classification (tier-independent)
# ---------------------------------------------------------------------------
def _tree_sum(x: np.ndarray) -> float:
    """Fold-halves binary-tree sum of a 1-D float64 array.

    Deterministic and pow2-padding-invariant: zero-padding to ANY larger
    power of two folds away exactly (v + 0.0 == v bitwise), so the fused
    device programs -- which reduce the same values at pow2-padded batch
    shape with masked lanes contributing 0.0 -- produce the bit-identical
    total.  This is the ONE summation order shared by the numpy tier, the
    fused step, and the K-epoch scan for the mean-reply fetch estimate.
    """
    m = x.size
    if m == 0:
        return 0.0
    p = _pow2_bucket(m)
    if p != m:
        x = np.concatenate([x, np.zeros(p - m)])
    while x.size > 1:
        h = x.size // 2
        x = x[:h] + x[h:]
    return float(x[0])


def _fetch_estimate(reply_owd: np.ndarray) -> float:
    """3x the mean finite reply delay: the slow-path fetch detour estimate.

    Reduced in the canonical `_tree_sum` order so the device-resident
    mirror inside the fused programs matches bit for bit.
    """
    fin = np.isfinite(reply_owd)
    cnt = int(fin.sum())
    if cnt == 0:
        return float(np.inf)
    return 3.0 * (_tree_sum(np.where(fin, reply_owd, 0.0).ravel()) / cnt)



def classify_commits(
    deadlines: np.ndarray,          # [N] request deadlines (proxy-stamped)
    arrivals: np.ndarray,           # [N, R] request arrival at each replica
    admitted: np.ndarray,           # [N, R] early-buffer admission
    release: np.ndarray,            # [N, R] release times (inf if not admitted)
    reply_owd: np.ndarray,          # [N, R] replica->proxy reply delay
    leader: int,
    f: int,
    mod_owd: Optional[np.ndarray] = None,   # [N, R] leader->follower log-mod delay
    leader_batch_delay: float = 50e-6,
    key_ids: Optional[np.ndarray] = None,   # [N] commutativity class per request
    order: Optional[np.ndarray] = None,     # [N] deadline-sorted indices (tier)
    force_slow: Optional[np.ndarray] = None,  # [N] fast path disallowed (cap)
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

    `order`, when given, is the deadline sort produced by a compute tier (the
    Pallas tier emits it from the bitonic kernel); requests that no replica
    admitted never influence prefix disagreement, so their position in a
    tier's order is immaterial.

    Returns dict with commit_time[N], fast[N], committed[N].
    """
    N, R = arrivals.shape
    admitted = np.asarray(admitted)
    release = np.asarray(release)

    # --- hash consistency: prefix-set equality per replica vs leader -------
    if order is None:
        order = np.argsort(deadlines, kind="stable")
    else:
        order = np.asarray(order, np.int64)
    if key_ids is not None and N > 0:
        # Per key class (S8.2): regroup the deadline order by class (stable),
        # giving the (class, deadline) lexicographic order. A request's reply
        # hash covers only the smaller-deadline requests in ITS class, so
        # disagreements in other classes cannot break its fast path.
        ks_all = np.asarray(key_ids)
        order = order[np.argsort(ks_all[order], kind="stable")]
    adm_sorted = admitted[order]                       # [N, R] in (class,) deadline order
    lead_adm = adm_sorted[:, leader]
    # A replica's prefix (strictly before position i) matches the leader's iff
    # the cumulative count of disagreements with the leader is 0.
    disagree = adm_sorted != lead_adm[:, None]
    cum_disagree = np.cumsum(disagree, axis=0) - disagree  # exclusive prefix
    if key_ids is not None and N > 0:
        # Segmented cumsum: subtract each class's running total at its start.
        ks = np.asarray(key_ids)[order]
        starts = np.r_[0, np.flatnonzero(ks[1:] != ks[:-1]) + 1]
        seg_of = np.cumsum(np.r_[0, (ks[1:] != ks[:-1]).astype(np.int64)])
        cum_disagree = cum_disagree - cum_disagree[starts][seg_of]
    prefix_match = cum_disagree == 0                       # [N, R]
    # Back to original order.
    inv = np.argsort(order, kind="stable")
    prefix_match = prefix_match[inv]

    # --- replies ------------------------------------------------------------
    fast_reply_t = np.where(admitted, release + reply_owd, np.inf)   # [N, R]
    fast_hash_ok = admitted & prefix_match & admitted[:, [leader]]

    # Fast quorum: leader + (fq-1) matching followers, by reply arrival time.
    # Only the (fq-1)-th order statistic is consumed, so an O(R) partition
    # replaces the full row sort.
    fq = fast_quorum_size(f)
    ok_t = np.where(fast_hash_ok, fast_reply_t, np.inf)
    ok_kth = (np.partition(ok_t, fq - 1, axis=1)[:, fq - 1]
              if fq - 1 < R else np.full(N, np.inf))
    fast_commit_t = np.where(np.isfinite(ok_t[:, leader]), ok_kth, np.inf)
    fast_commit_t = np.maximum(fast_commit_t, ok_t[:, leader])
    if force_slow is not None:
        # Deadline-capped requests (SD.2.4): re-deadlined at the leader, so
        # their hash never matches a fast quorum -- slow path only.
        fast_commit_t = np.where(force_slow, np.inf, fast_commit_t)

    # --- slow path ------------------------------------------------------------
    # Leader appends everything eventually: late requests get re-deadlined and
    # released ~immediately at the leader.
    leader_t = np.where(admitted[:, leader], release[:, leader], arrivals[:, leader])
    leader_t = np.where(np.isfinite(arrivals[:, leader]), leader_t, np.inf)
    if mod_owd is None:
        mod_owd = reply_owd  # symmetric paths by default
    # log-modification reaches follower; follower syncs; sends slow-reply.
    sync_t = leader_t[:, None] + leader_batch_delay + mod_owd          # [N, R]
    # Follower can only sync m after receiving it (or fetching: +2 hops).
    # Crashed replicas are modeled by inf reply_owd; exclude them from the
    # fetch-delay estimate so live replicas keep a finite fetch path.
    fetch = _fetch_estimate(reply_owd)
    have_t = np.where(np.isfinite(arrivals), arrivals, leader_t[:, None] + fetch)
    slow_ready = np.maximum(sync_t, have_t)
    slow_reply_t = slow_ready + reply_owd
    slow_reply_t[:, leader] = leader_t + reply_owd[:, leader]          # leader fast-reply
    sq = slow_quorum_size(f)
    slow_kth = np.partition(slow_reply_t, sq - 1, axis=1)[:, sq - 1]
    slow_commit_t = np.maximum(slow_kth, slow_reply_t[:, leader])

    commit_t = np.minimum(fast_commit_t, slow_commit_t)
    fast = fast_commit_t <= slow_commit_t
    committed = np.isfinite(commit_t)
    return {
        "commit_time": commit_t,
        "fast": fast & committed,
        "committed": committed,
    }


# ---------------------------------------------------------------------------
# Fused epoch program (single device dispatch per epoch generation)
# ---------------------------------------------------------------------------
# Allowed K-epochs-per-dispatch scan lengths for the fault-free fast path.
# A fixed menu (like the pow2 batch buckets) bounds the compile-count model
# (lint TS003): windows are padded with empty epochs up to a bucket size.
SCAN_K_BUCKETS = (4, 16, 64)


def _build_epoch_body(tier: ComputeTier, f: int, use_kcls: bool,
                      use_cap: bool = False):
    """The shared jnp epoch body behind the K=1 step and the K-epoch scan.

    A mirror of StampStage + DomStage + `classify_commits`, traced under
    float64 (the caller enters `enable_x64`), eliminating the per-stage
    host<->device ping-pong.  The two formerly host-owned per-epoch scalars
    are computed IN-PROGRAM from carried state:

      bound  -- this epoch's observed OWDs fold into a fixed-size ring
                pool (the device twin of `DomEngine.owd_pool`), then the
                sliding percentile + clock margin is selected on device,
                bit-identical to `update_bound`/`_partition_percentile`;
      fetch  -- the mean-reply estimate via the canonical `_tree_sum`
                fold, bit-identical to `_fetch_estimate`.

    Signature: body(pool, ptr, cnt, <epoch operands>) ->
    ((pool, ptr, cnt), (stamp, deadlines, arrivals, admitted, release,
    commit_t, fast, committed, bound)).  The carry is the ring pool; all
    epoch outputs are bit-for-bit equal to the staged numpy tier
    (tests/test_engine.py).
    """
    import jax
    import jax.numpy as jnp

    fq = fast_quorum_size(f)
    sq = slow_quorum_size(f)

    @jax.named_scope("pool_fold")
    def pool_fold(pool, ptr, cnt, samples, n_valid):
        # Ring-buffer fold of this epoch's observed OWD samples, row-major
        # over the valid rows -- the device twin of update_bound's
        # `concat(pool, obs)[-W:]`: when more than W samples would land,
        # the oldest overflow is skipped before writing.  Write targets are
        # distinct (mode="drop" discards masked lanes at index W), so the
        # scatter is deterministic.
        W = pool.shape[0]
        n_pad, R = samples.shape
        m = n_valid * R
        m_kept = jnp.minimum(m, W)
        skip = m - m_kept
        k = jnp.arange(n_pad * R)
        write = (k >= skip) & (k < m)
        tgt = jnp.where(write, (ptr + k - skip) % W, W)
        pool = pool.at[tgt].set(samples.ravel(), mode="drop")
        return pool, (ptr + m_kept) % W, jnp.minimum(cnt + m, W)

    @jax.named_scope("bound")
    def pool_percentile(pool, cnt, pq01, margin, clamp_d):
        # Device mirror of update_bound: sort-select the two order
        # statistics (+inf fills the unfilled tail) and interpolate exactly
        # like `_partition_percentile` (numpy _lerp branch structure).
        # pq01 is percentile/100 PRE-divided on the host: XLA strength-
        # reduces an in-program `pq / 100.0` into a reciprocal multiply
        # (pq * 0.01), which is 1 ulp off the host's true division and
        # breaks bit-parity with the numpy oracle.
        W = pool.shape[0]
        srt = jnp.sort(pool)
        pos = pq01 * (cnt - 1).astype(pool.dtype)
        lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, W - 1)
        hi = jnp.clip(jnp.ceil(pos).astype(jnp.int32), 0, W - 1)
        lo_v = srt[lo]
        hi_v = srt[hi]
        t = pos - jnp.floor(pos)
        r = jnp.where(t < 0.5, lo_v + t * (hi_v - lo_v),
                      hi_v - (hi_v - lo_v) * (1.0 - t))
        pct = jnp.where((t == 0.0) | (lo_v == hi_v), lo_v, r)
        bound = pct + margin
        bound = jnp.where((bound > 0.0) & (bound < clamp_d), bound, clamp_d)
        return jnp.where(cnt > 0, bound, clamp_d)

    @jax.named_scope("fetch")
    def tree_mean_fetch(reply):
        # `_fetch_estimate` on device: the fold-halves tree sum is
        # pow2-padding-invariant, so the padded batch reduces to the exact
        # numpy-tier value (pad lanes are +inf -> masked to 0.0).
        fin = jnp.isfinite(reply)
        cnt = jnp.sum(fin)
        x = jnp.where(fin, reply, 0.0).ravel()
        p = _pow2_bucket(x.shape[0])
        if p != x.shape[0]:
            x = jnp.concatenate([x, jnp.zeros((p - x.shape[0],), x.dtype)])
        while x.shape[0] > 1:
            h = x.shape[0] // 2
            x = x[:h] + x[h:]
        return jnp.where(cnt > 0, 3.0 * (x[0] / jnp.maximum(cnt, 1)),
                         jnp.inf)

    def body(pool, ptr, cnt, t, c2p, owd_pr, drop_pr, reply_owd, alive,
             kcls, leader, n_valid, pq01, margin, clamp_d, batch_delay, cap,
             floor, dies_at=None, stamp_off=None, arr_off=None,
             pair_drop=None, pair_delay=None, pre_dl=None,
             sync_theta=None, sync_rtt=None, sync_safety=None,
             sync_floor=None):
        N, R = owd_pr.shape
        # Per-pair network-fault operands (Partition / GrayLink): extra
        # delay joins the effective OWD BEFORE anything observes it -- the
        # proxies' estimator pool sees the gray-degraded path exactly like
        # the event backend's sliding window does -- and per-pair drops
        # extend the fabric's own drop mask. Optional operands like dies_at:
        # fault-free epochs carry neither, and faulted stretches fall off
        # the K-scan fast path (the scan variant never carries them).
        owd_eff = owd_pr if pair_delay is None else owd_pr + pair_delay
        drop_eff = drop_pr if pair_drop is None else drop_pr | pair_drop
        # --- bound: device-resident sliding-percentile deadline bound ------
        # Fold BEFORE selecting, mirroring StampStage's update_bound call
        # (this epoch's samples are part of its own bound).
        samples = owd_eff
        if stamp_off is not None:
            samples = owd_eff + arr_off - stamp_off[:, None]
        pool, ptr, cnt = pool_fold(pool, ptr, cnt, samples, n_valid)
        bound = pool_percentile(pool, cnt, pq01, margin, clamp_d)
        # --- fetch: device-resident mean-reply estimate --------------------
        reply = jnp.where(alive[None, :], reply_owd, jnp.inf)
        fetch = tree_mean_fetch(reply)
        with jax.named_scope("stamp"):
            # --- stamp: proxy stamping + deadline bounding -------------------
            # stamp_off: proxy clock-read error folded into the deadline
            # value; arr_off: replica clock-read error shifting each
            # receiver's local frame (admission compares + release
            # instants). Clock-fault-free epochs omit both (None): the
            # synced-clock program carries no offset operands at all,
            # keeping the PR-3 hot path untaxed.
            stamp = t + c2p
            deadlines = stamp + bound
            if stamp_off is not None:
                deadlines = deadlines + stamp_off
            if pre_dl is not None:
                # Sharded multi-op entries carry a pre-stamped global
                # deadline (dl > 0): the proxy forwards it untouched so
                # every involved group orders the op at the identical
                # synchronized-time slot. The override is LAST -- the
                # deadline was fixed client-side, so proxy-clock error does
                # not re-bias it. 0.0 = stamp normally.
                deadlines = jnp.where(pre_dl > 0, pre_dl, deadlines)
            arrivals = jnp.where(drop_eff | ~alive[None, :], jnp.inf,
                                 stamp[:, None] + owd_eff)
            # recovery stall: nothing releases before `floor` (StartView);
            # a zero floor is the identity, mirroring StampStage's op order
            # exactly
            arrivals = jnp.maximum(arrivals, floor)
            if dies_at is not None:
                # crash-epoch fidelity: in-flight messages to a replica
                # dying at the epoch's end are never received (optional
                # operand, like the clock offsets -- crash-free epochs carry
                # none of this)
                arrivals = jnp.where(arrivals > dies_at[None, :], jnp.inf,
                                     arrivals)
        with jax.named_scope("admit"):
            # --- dom: watermark admission + release (receiver-local frames)
            a_loc = arrivals if arr_off is None else arrivals + arr_off
            admitted = tier.admit_traced(deadlines, a_loc)
            release = jnp.where(admitted,
                                jnp.maximum(deadlines[:, None], a_loc),
                                jnp.inf)
            if arr_off is not None:
                release = release - arr_off
            # --- deadline cap (SD.2.4): leader releases far-future deadlines
            # at arrival; those requests are barred from the fast path. The
            # program is specialized on use_cap (like use_kcls), so cap-free
            # runs carry none of this masking work.
            lead_col = jnp.arange(R)[None, :] == leader
            if use_cap:
                capped = jnp.isfinite(a_loc[:, leader]) \
                    & (deadlines > a_loc[:, leader] + cap)
                admitted = admitted | (lead_col & capped[:, None])
                release = jnp.where(lead_col & capped[:, None], arrivals,
                                    release)
        # --- commit: prefix hash-consistency vs the leader ------------------
        with jax.named_scope("order"):
            order = tier.order_traced(deadlines)
            if use_kcls:
                order = order[jnp.argsort(kcls[order], stable=True)]
        with jax.named_scope("commit"):
            adm_sorted = admitted[order]
            lead_adm_sorted = adm_sorted[:, leader]
            disagree = adm_sorted != lead_adm_sorted[:, None]
            cum_disagree = jnp.cumsum(disagree, axis=0) - disagree
            if use_kcls:
                ks = kcls[order]
                is_start = jnp.concatenate(
                    [jnp.ones((1,), bool), ks[1:] != ks[:-1]])
                start_pos = jax.lax.cummax(
                    jnp.where(is_start, jnp.arange(N), 0))
                cum_disagree = cum_disagree - cum_disagree[start_pos]
            prefix_match = cum_disagree == 0
            inv = jnp.zeros((N,), order.dtype).at[order].set(
                jnp.arange(N, dtype=order.dtype))
            prefix_match = prefix_match[inv]
            # --- fast quorum -------------------------------------------------
            lead_admitted = admitted[:, leader]
            fast_reply_t = jnp.where(admitted, release + reply, jnp.inf)
            fast_hash_ok = admitted & prefix_match & lead_admitted[:, None]
            ok_t = jnp.where(fast_hash_ok, fast_reply_t, jnp.inf)
            ok_lead = ok_t[:, leader]
            ok_kth = (jnp.sort(ok_t, axis=1)[:, fq - 1] if fq - 1 < R
                      else jnp.full((N,), jnp.inf))
            fast_commit_t = jnp.where(jnp.isfinite(ok_lead), ok_kth, jnp.inf)
            fast_commit_t = jnp.maximum(fast_commit_t, ok_lead)
            if use_cap:
                fast_commit_t = jnp.where(capped, jnp.inf, fast_commit_t)
            # --- slow path ---------------------------------------------------
            arr_lead = arrivals[:, leader]
            leader_t = jnp.where(lead_admitted, release[:, leader], arr_lead)
            leader_t = jnp.where(jnp.isfinite(arr_lead), leader_t, jnp.inf)
            sync_t = leader_t[:, None] + batch_delay + reply
            have_t = jnp.where(jnp.isfinite(arrivals), arrivals,
                               leader_t[:, None] + fetch)
            slow_reply_t = jnp.maximum(sync_t, have_t) + reply
            slow_reply_t = jnp.where(lead_col, leader_t[:, None] + reply,
                                     slow_reply_t)
            slow_kth = jnp.sort(slow_reply_t, axis=1)[:, sq - 1]
            slow_commit_t = jnp.maximum(slow_kth, leader_t + reply[:, leader])
            # --- verdicts ----------------------------------------------------
            commit_t = jnp.minimum(fast_commit_t, slow_commit_t)
            fast = fast_commit_t <= slow_commit_t
            committed = jnp.isfinite(commit_t)
        outs = (stamp, deadlines, arrivals, admitted, release,
                commit_t, fast & committed, committed, bound)
        if sync_theta is not None:
            # Modeled sync round (PR 10): the estimator's per-node
            # reductions run INSIDE the dispatch over the [M, M] probe
            # arrays this epoch carries (the sync analogue of the clock
            # operands -- round-free epochs carry none of this), emitting
            # the per-node offset estimates and honest error bounds the
            # daemon folds into corrections at the epoch boundary.
            with jax.named_scope("sync_estimate"):
                sync_est, sync_sigma = estimate_offsets(
                    sync_theta, sync_rtt, jnp, sync_safety, sync_floor)
            outs = outs + (sync_est, sync_sigma)
        return ((pool, ptr, cnt), outs)

    return body


def _count_trace(program: str, **shape) -> None:
    """Called from an epoch program's Python body, which runs only while
    JAX traces it: once per (program object, operand signature)."""
    obs.count("programs_traced." + program)
    obs.event("programs_traced." + program, **shape)


def _build_fused_step(tier: ComputeTier, f: int, use_kcls: bool,
                      use_cap: bool = False):
    """Jit the K=1 epoch body: one device dispatch per epoch generation.

    Returns the 9 epoch outputs followed by the updated (pool, ptr, cnt)
    ring carry.  The optional fault operands (dies_at / clock offsets)
    dispatch at trace time, so fault-free epochs carry none of that work;
    a modeled sync round additionally carries theta/rtt probe operands and
    appends the per-node (est, sigma) estimator outputs before the carry.
    """
    import jax

    body = _build_epoch_body(tier, f, use_kcls, use_cap)

    @jax.jit
    def step(pool, ptr, cnt, t, c2p, owd_pr, drop_pr, reply_owd, alive,
             kcls, leader, n_valid, pq01, margin, clamp_d, batch_delay, cap,
             floor, dies_at=None, stamp_off=None, arr_off=None,
             pair_drop=None, pair_delay=None, pre_dl=None,
             sync_theta=None, sync_rtt=None, sync_safety=None,
             sync_floor=None):
        _count_trace("step", n_pad=t.shape[0])
        carry, outs = body(pool, ptr, cnt, t, c2p, owd_pr, drop_pr,
                           reply_owd, alive, kcls, leader, n_valid, pq01,
                           margin, clamp_d, batch_delay, cap, floor,
                           dies_at=dies_at, stamp_off=stamp_off,
                           arr_off=arr_off, pair_drop=pair_drop,
                           pair_delay=pair_delay, pre_dl=pre_dl,
                           sync_theta=sync_theta, sync_rtt=sync_rtt,
                           sync_safety=sync_safety, sync_floor=sync_floor)
        return outs + carry

    return step


def _build_fused_scan(tier: ComputeTier, f: int, use_kcls: bool,
                      use_cap: bool = False):
    """K-epochs-per-dispatch: the epoch body under a `jax.lax.scan`.

    The stacked per-epoch operands (leading K axis) scan over the shared
    body with the (pool, ptr, cnt) ring carry threaded through -- one
    compiled program and ONE device->host pull per K epoch generations.
    Fault-free segments only: the scan variant carries no dies_at /
    clock-offset operands; the cluster's fast-path guards ensure crashes,
    relaunches, StartView stalls and `release_floor` changes land on
    dispatch boundaries.  Off-CPU the carry buffers are donated so XLA
    updates the ring pool in place.
    """
    import jax

    body = _build_epoch_body(tier, f, use_kcls, use_cap)

    def scan_fn(pool, ptr, cnt, t, c2p, owd_pr, drop_pr, reply_owd, kcls,
                n_valid, alive, leader, pq01, margin, clamp_d, batch_delay,
                cap, floor):
        _count_trace("scan", K=t.shape[0], n_pad=t.shape[1])

        def one_epoch(carry, xs):
            pool, ptr, cnt = carry
            tk, c2pk, owdk, dropk, replyk, kclsk, nvk = xs
            return body(pool, ptr, cnt, tk, c2pk, owdk, dropk, replyk,
                        alive, kclsk, leader, nvk, pq01, margin, clamp_d,
                        batch_delay, cap, floor)

        carry, ys = jax.lax.scan(
            one_epoch, (pool, ptr, cnt),
            (t, c2p, owd_pr, drop_pr, reply_owd, kcls, n_valid))
        return ys + carry

    donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
    return jax.jit(scan_fn, donate_argnums=donate)


# The process's fused epoch programs, keyed by (kind, tier class, f,
# use_kcls, use_cap): every cluster of a process dispatches the same jitted
# objects, so JAX's in-memory caches (traces, executables) outlive a
# cluster and a scenario run after the first traces nothing it ran before.
# Operand shapes key each program's own cache; donation is per call.
_EPOCH_PROGRAMS: dict[tuple, object] = {}


def clear_epoch_programs() -> None:
    """Forget the shared epoch programs, so the next tier builds and traces
    afresh (for tests that observe a first trace)."""
    _EPOCH_PROGRAMS.clear()


# ---------------------------------------------------------------------------
# Epoch pipeline
# ---------------------------------------------------------------------------
@dataclass
class EpochState:
    """Mutable per-epoch blackboard the stages fill in, in order."""

    # inputs
    t: np.ndarray                       # [N] attempt times (this submission)
    t0: np.ndarray                      # [N] original submit times (latency)
    cid: np.ndarray                     # [N] client ids
    rid: np.ndarray                     # [N] per-client request ids
    kcls: Optional[np.ndarray]          # [N] commutativity classes (or None)
    alive: np.ndarray                   # [R] replica liveness this epoch
    leader: int                         # leader this epoch
    release_floor: float = 0.0          # replicas release nothing before this
    #   instant (the StartView time of a just-completed view change: messages
    #   arriving during the recovery stall sit in early buffers until it)
    dies_at: Optional[np.ndarray] = None    # [R] death instants inside this
    #   epoch (inf = survives): an epoch cut short by a crash event still has
    #   messages in flight to the dying replica -- those arriving after its
    #   death are never received, which is what leaves speculative entries
    #   on the survivors for MERGE-LOG to recover
    # SampleStage
    proxy_nodes: Optional[np.ndarray] = None
    c2p: Optional[np.ndarray] = None    # [N] client->proxy OWD (inf = dropped)
    p2c: Optional[np.ndarray] = None    # [N] proxy->client reply OWD
    owd_pr: Optional[np.ndarray] = None     # [N, R] proxy->replica OWD
    drop_pr: Optional[np.ndarray] = None    # [N, R] multicast drops
    reply_owd: Optional[np.ndarray] = None  # [N, R] replica->proxy reply OWD
    # Clock-fault offsets (scenario `ClockFault` events; None = synced).
    # A faulty proxy clock shifts the deadline VALUE each of its messages
    # carries; a faulty replica clock shifts when that replica *observes*
    # arrivals/deadlines (its whole local frame), i.e. every admission
    # comparison and release instant at that receiver.
    clock_stamp_off: Optional[np.ndarray] = None  # [N] proxy-clock read error
    clock_arr_off: Optional[np.ndarray] = None    # [N, R] replica-clock read error
    # Per-pair network-fault operands (Partition / GrayLink events; None =
    # clean): extra proxy->replica drops and path delay for this epoch's
    # (message, replica) pairs, gathered from the engine's per-(proxy,
    # replica) fault state by SampleStage. The reverse (replica->proxy)
    # effects are folded into reply_owd directly -- pure data, no operand.
    pair_drop: Optional[np.ndarray] = None    # [N, R] extra drops (bool)
    pair_delay: Optional[np.ndarray] = None   # [N, R] extra path delay (s)
    # Pre-stamped deadlines (sharded MultiOp entries; None = all stamped
    # normally). Where > 0, the value REPLACES the proxy-computed deadline
    # after all stamping/offset math -- the cross-group global slot.
    pre_deadline: Optional[np.ndarray] = None  # [N] fixed deadlines (0=none)
    # Modeled sync round (PR 10): a probe round due at this epoch's boundary
    # rides the dispatch as [M, M] operands (M = replicas + proxies); the
    # in-program estimator returns per-node (est, sigma), which the daemon
    # folds into corrections/bounds. None on round-free epochs.
    sync_theta: Optional[np.ndarray] = None   # [M, M] NTP offset samples
    sync_rtt: Optional[np.ndarray] = None     # [M, M] selected-probe RTTs
    sync_est: Optional[np.ndarray] = None     # [M] estimator output
    sync_sigma: Optional[np.ndarray] = None   # [M] measured error bounds
    # StampStage
    bound: float = 0.0                  # DOM latency bound this epoch
    stamp: Optional[np.ndarray] = None  # [N] proxy stamp times
    deadlines: Optional[np.ndarray] = None  # [N]
    arrivals: Optional[np.ndarray] = None   # [N, R]
    # DomStage
    admitted: Optional[np.ndarray] = None   # [N, R]
    release: Optional[np.ndarray] = None    # [N, R]
    # CommitStage
    commit_time: Optional[np.ndarray] = None  # [N] commit at proxy
    fast: Optional[np.ndarray] = None
    committed: Optional[np.ndarray] = None    # [N] protocol-level commit
    exec_order: Optional[np.ndarray] = None   # [N] tier's deadline order (the
    #   execution/log order; LogStage appends committed entries along it)
    # DeliverStage
    commit_at_client: Optional[np.ndarray] = None  # [N]
    latency: Optional[np.ndarray] = None           # [N] (inf = undelivered)
    delivered: Optional[np.ndarray] = None    # [N] committed AND the reply
    #   reached the client (drives client-side retry + latency accounting)


class Stage:
    name = "stage"

    def run(self, s: EpochState, eng: "DomEngine") -> None:
        raise NotImplementedError


class SampleStage(Stage):
    """Bulk network sampling for the epoch batch, one rng stream."""

    name = "sample"

    def run(self, s, eng):
        cfg = eng.cfg
        n = eng.n
        N = s.t.size
        s.proxy_nodes = eng.proxy_nodes(s.cid % cfg.n_proxies)
        if cfg.co_locate_proxies:       # Nezha-Non-Proxy: no client<->proxy hops
            s.c2p = np.zeros(N)
            s.p2c = np.zeros(N)
        elif getattr(cfg, "client_proxy_lan", 0.0) > 0.0:
            # WAN mode (S9.8): proxies live in the client's zone -- both
            # client legs take the fixed LAN delay, not the WAN fabric.
            s.c2p = np.full(N, cfg.client_proxy_lan)
            s.p2c = np.full(N, cfg.client_proxy_lan)
        else:
            cnodes = eng.client_nodes(s.cid)
            c2p, drop_cp = eng.net.sample_owd_pairs(cnodes, s.proxy_nodes)
            # A lost message on either client leg leaves the attempt
            # uncommitted at the client (inf latency); the cluster's retry
            # model then re-issues it after client_timeout.
            c2p[drop_cp] = np.inf
            s.c2p = c2p
            # Reply path sampled per actual submitting client node.
            p2c, drop_pc = eng.net.sample_owd_pairs(s.proxy_nodes, cnodes)
            p2c[drop_pc] = np.inf
            s.p2c = p2c
        replicas = list(range(n))
        s.owd_pr, s.drop_pr = eng.net.sample_owd_matrix(s.proxy_nodes, N, replicas)
        # replica -> proxy replies (symmetric path statistics)
        s.reply_owd, _ = eng.net.sample_owd_matrix(s.proxy_nodes, N, replicas)
        # Clock-fault read errors (Appendix D): one N(mu, sigma) sample per
        # proxy stamp and per (message, replica) observation, from a separate
        # rng stream so fault-free runs stay bit-identical to before. Sampled
        # here (not in StampStage) because the fused tiers skip StampStage.
        if eng.clocks_faulty:
            pids = np.asarray(s.cid) % cfg.n_proxies
            s.clock_stamp_off = eng.rng.normal(eng.proxy_clock[pids, 0],
                                               eng.proxy_clock[pids, 1])
            s.clock_arr_off = eng.rng.normal(
                eng.replica_clock[None, :, 0], eng.replica_clock[None, :, 1],
                size=(N, n))
        if eng.pairs_faulty:
            # Per-pair faults (Partition / GrayLink): gather this epoch's
            # (message, replica) fault rows from the per-(proxy, replica)
            # state. Gray draws come from the engine's fault rng stream
            # (like clock faults) in ONE fixed order -- forward drop,
            # forward delay, reverse drop, reverse delay -- so every tier
            # consumes identical variates and fault-free runs draw nothing.
            pids = np.asarray(s.cid) % cfg.n_proxies
            blk = eng._pair_block[pids]                 # [N, R]
            gdp = eng._pair_gray_drop[pids]
            gmu = eng._pair_mu[pids]
            gsg = eng._pair_sigma[pids]
            delayed = (gmu > 0.0) | (gsg > 0.0)
            pair_drop = blk.copy()
            if gdp.any():
                pair_drop |= eng.rng.random((N, n)) < gdp
            s.pair_drop = pair_drop
            delay = np.zeros((N, n))
            if delayed.any():
                delay = np.where(
                    delayed, np.maximum(0.0, eng.rng.normal(gmu, gsg)), 0.0)
            s.pair_delay = delay
            # Reverse leg (replica->proxy replies): fold the same per-pair
            # faults into reply_owd before it becomes a fused operand --
            # blocked/dropped replies never arrive, gray delay adds on.
            reply = s.reply_owd.copy()
            if delayed.any():
                reply = reply + np.where(
                    delayed, np.maximum(0.0, eng.rng.normal(gmu, gsg)), 0.0)
            rdrop = blk.copy()
            if gdp.any():
                rdrop |= eng.rng.random((N, n)) < gdp
            reply[rdrop] = np.inf
            s.reply_owd = reply
        if eng.stampers_biased:
            # SkewedStamper: a deterministic stamp bias is exactly a proxy
            # clock-read offset -- the carried deadline VALUE shifts while
            # true send/arrival instants do not, and the receiver-measured
            # OWD observations absorb -bias. Reuses the clock stamp_off /
            # arr_off operand variant (no new fused specialization).
            pids = np.asarray(s.cid) % cfg.n_proxies
            bias = eng.proxy_stamp_bias[pids]
            if s.clock_stamp_off is None:
                s.clock_stamp_off = bias
                s.clock_arr_off = np.zeros((N, n))
            else:
                s.clock_stamp_off = s.clock_stamp_off + bias
        if eng.sync_active:
            # Modeled sync (PR 10): the daemon's effective residual offsets
            # (truth minus applied corrections, advanced to this epoch's
            # boundary) ARE the clock read errors -- a proxy's residual
            # shifts the deadline values it stamps, a replica's shifts its
            # whole local frame. Folded additively like SkewedStamper so
            # injected clock faults still compose on top.
            ds = eng.clocksync
            pids = np.asarray(s.cid) % cfg.n_proxies
            soff = ds.stamp_err(pids)
            aoff = np.tile(ds.arr_err(), (N, 1))
            if s.clock_stamp_off is None:
                s.clock_stamp_off = soff
                s.clock_arr_off = aoff
            else:
                s.clock_stamp_off = s.clock_stamp_off + soff
                s.clock_arr_off = s.clock_arr_off + aoff
            if eng.tier.fused and ds.pending is not None:
                # a due probe round rides this epoch's dispatch; the staged
                # tier instead applies the numpy twin in run_epoch's
                # epilogue (bit-identical by construction)
                _, theta, rtt = ds.pending
                s.sync_theta = theta
                s.sync_rtt = rtt


class StampStage(Stage):
    """Proxy stamping + DOM deadline bounding.

    The bound is the percentile of a sliding pool of observed proxy->replica
    OWDs carried across epochs (the sliding-window estimator's steady state)
    plus the clock-error margin, clamped to [0, D]; `DomEngine.update_bound`
    owns the pool and computes the percentile via an O(pool) partition,
    skipping the recompute entirely when the pool is unchanged.
    """

    name = "stamp"

    def run(self, s, eng):
        s.stamp = s.t + s.c2p
        bound = eng.update_bound(eng.observed_owd_samples(s))
        s.bound = bound
        s.deadlines = s.stamp + bound
        if s.clock_stamp_off is not None:
            # The proxy stamps with its LOCAL clock: the deadline value each
            # message carries absorbs the proxy's read error.
            s.deadlines = s.deadlines + s.clock_stamp_off
        if s.pre_deadline is not None:
            # Sharded MultiOp entries: the client-side layer fixed these
            # deadlines before routing; the proxy forwards them untouched
            # (override LAST -- mirrors the fused body's pre_dl branch).
            s.deadlines = np.where(s.pre_deadline > 0, s.pre_deadline,
                                   s.deadlines)
        # owd_eff mirrors the fused body: pair_delay (GrayLink) joins the
        # path BEFORE the stamp adds on, keeping the summation order -- and
        # hence the bits -- identical to `stamp[:, None] + owd_eff` there.
        owd_eff = (s.owd_pr if s.pair_delay is None
                   else s.owd_pr + s.pair_delay)
        arrivals = s.stamp[:, None] + owd_eff
        arrivals[s.drop_pr] = np.inf
        if s.pair_drop is not None:         # Partition / GrayLink drops
            arrivals[s.pair_drop] = np.inf
        arrivals[:, ~s.alive] = np.inf      # crashed replicas never receive
        # Recovery stall (view change): messages arriving while replicas are
        # in VIEWCHANGE wait in the early buffers and release together -- in
        # deadline order -- at StartView. Floored arrivals reproduce that
        # exactly; a zero floor is the identity on (positive) arrival times.
        arrivals = np.maximum(arrivals, s.release_floor)
        if s.dies_at is not None:
            # a replica crashing at the epoch's end never receives what is
            # still in flight to it (releases/replies already sent survive)
            arrivals[arrivals > s.dies_at[None, :]] = np.inf
        s.arrivals = arrivals
        s.reply_owd = s.reply_owd.copy()
        s.reply_owd[:, ~s.alive] = np.inf   # ... and never reply


class DomStage(Stage):
    """DOM admission + release through the compute tier (pow2-padded).

    Admission at receiver r happens in r's LOCAL clock frame: the early
    buffer compares the carried deadline value against local reads. The
    per-receiver watermark scan is frame-local, so shifting r's arrival
    column by its clock-read error reproduces a skewed replica exactly;
    release instants come back to true time by undoing the shift.
    """

    name = "dom"

    def run(self, s, eng):
        N = s.deadlines.size
        R = eng.n
        a_in = (s.arrivals if s.clock_arr_off is None
                else s.arrivals + s.clock_arr_off)
        n_pad = _pow2_bucket(N) if eng.tier.pad_batches else N
        if n_pad != N:
            # Pad lanes carry +inf deadline AND +inf arrival: never admitted,
            # never a watermark -- invisible to the real rows.
            d = np.full(n_pad, np.inf)
            d[:N] = s.deadlines
            a = np.full((n_pad, R), np.inf)
            a[:N] = a_in
        else:
            d, a = s.deadlines, a_in
        adm, rel = eng.tier.release_schedule(d, a)
        s.admitted = np.asarray(adm)[:N]
        rel = np.asarray(rel)[:N]
        if s.clock_arr_off is not None:
            rel = rel - s.clock_arr_off      # local release -> true time
        s.release = rel


class FusedEpochStage(Stage):
    """Stamp->dom->commit as ONE jitted device dispatch (fused tiers).

    Replaces StampStage+DomStage+CommitStage when ``tier.fused``: the whole
    data plane between network sampling and client delivery runs as a
    single float64-traced program over the pow2-padded batch (see
    `_build_epoch_body`). The formerly host-owned per-epoch scalars -- the
    sliding-pool percentile ``bound`` and the mean-reply ``fetch`` -- are
    computed in-program from the uploaded ring-pool state; the host only
    advances its cheap numpy pool mirror (`update_bound`), whose value is
    bit-identical to the device fold by construction.
    """

    name = "fused"

    def run(self, s, eng):
        import jax

        with obs.span("fused.pack"):
            cap = float(getattr(eng.cfg, "deadline_cap", 0.0) or 0.0)
            step = eng.tier.epoch_step(eng.cfg.f, use_kcls=s.kcls is not None,
                                       use_cap=cap > 0.0)
            args, kw = self._pack(s, eng, cap)
        with jax.enable_x64(True):
            with obs.span("fused.dispatch"):
                out = step(*args, **kw)
            with obs.span("fused.pull"):
                pulled = (out[:8] if s.sync_theta is None
                          else out[:8] + out[9:11])
                # lint: allow[HS003] THE one epoch-end device->host pull of the fused program's outputs
                pulled = [np.asarray(o) for o in pulled]
        with obs.span("fused.unpack"):
            N = s.t.size
            (s.stamp, s.deadlines, s.arrivals, s.admitted, s.release,
             s.commit_time, s.fast, s.committed) = [o[:N] for o in pulled[:8]]
            if s.sync_theta is not None:
                # the round's estimator outputs land at the epoch boundary:
                # corrections/bounds fold exactly where the staged tier's
                # numpy twin folds them (run_epoch's epilogue)
                s.sync_est, s.sync_sigma = pulled[8], pulled[9]
                eng.clocksync.consume_round(s.sync_est, s.sync_sigma)

    @staticmethod
    def _pack(s, eng, cap: float) -> tuple:
        """The epoch program's padded operands: (args, kwargs)."""
        cfg = eng.cfg
        N = s.t.size
        R = eng.n
        # Upload the PRE-fold ring-pool snapshot; the program folds this
        # epoch's samples itself.  The host mirror advances in lockstep so
        # fault-path (staged) epochs and bookkeeping see the same pool.
        pool, ptr, cnt = eng.device_pool_state()
        s.bound = eng.update_bound(eng.observed_owd_samples(s))
        rep = s.reply_owd.copy()
        rep[:, ~s.alive] = np.inf
        n_pad = _pow2_bucket(N) if eng.tier.pad_batches else N
        # Pad lanes: +inf attempt time -> +inf stamp/deadline/arrival, never
        # admitted, never committed -- invisible to the real rows.
        t = np.full(n_pad, np.inf)
        t[:N] = s.t
        c2p = np.zeros(n_pad)
        c2p[:N] = s.c2p
        owd = np.zeros((n_pad, R))
        owd[:N] = s.owd_pr
        drop = np.ones((n_pad, R), dtype=bool)
        drop[:N] = s.drop_pr
        # +inf reply pads: row-local quorum arithmetic never sees them AND
        # the in-program fetch mean excludes them (pads must not count)
        reply = np.full((n_pad, R), np.inf)
        reply[:N] = s.reply_owd
        kcls = np.full(n_pad, -1, np.int64)
        if s.kcls is not None:
            kcls[:N] = s.kcls
        # clock-fault read errors: only faulty epochs carry the (dense)
        # offset operands -- pad lanes stay zero; their inf attempt times
        # keep them invisible either way
        fault_kw = {}
        if s.dies_at is not None:
            fault_kw["dies_at"] = np.asarray(s.dies_at, np.float64)
        if s.clock_stamp_off is not None:
            stamp_off = np.zeros(n_pad)
            stamp_off[:N] = s.clock_stamp_off
            arr_off = np.zeros((n_pad, R))
            arr_off[:N] = s.clock_arr_off
            fault_kw["stamp_off"] = stamp_off
            fault_kw["arr_off"] = arr_off
        if s.pair_drop is not None:
            # pair-fault operands (Partition / GrayLink): pad lanes stay
            # clean -- their +inf attempt times hide them regardless
            pair_drop = np.zeros((n_pad, R), dtype=bool)
            pair_drop[:N] = s.pair_drop
            pair_delay = np.zeros((n_pad, R))
            pair_delay[:N] = s.pair_delay
            fault_kw["pair_drop"] = pair_drop
            fault_kw["pair_delay"] = pair_delay
        if s.pre_deadline is not None:
            # pre-stamped multi-op deadlines: pad lanes carry the 0.0
            # sentinel (= stamp normally), staying invisible
            pre_dl = np.zeros(n_pad)
            pre_dl[:N] = s.pre_deadline
            fault_kw["pre_dl"] = pre_dl
        if s.sync_theta is not None:
            # modeled sync round: the probe arrays are [M, M] over the
            # synchronized fleet, independent of the batch -- no padding
            fault_kw["sync_theta"] = s.sync_theta
            fault_kw["sync_rtt"] = s.sync_rtt
            fault_kw["sync_safety"] = np.float64(cfg.clock.sigma_safety)
            fault_kw["sync_floor"] = np.float64(cfg.clock.sigma_floor)
        args = (pool, ptr, cnt, t, c2p, owd, drop, reply,
                np.asarray(s.alive, bool), kcls, s.leader, N,
                float(cfg.dom.percentile) / 100.0, eng.bound_margin(),
                float(cfg.dom.clamp_d), float(cfg.leader_batch_delay),
                cap, float(s.release_floor))
        s.reply_owd = rep
        return args, fault_kw


class CommitStage(Stage):
    """Fast/slow classification; the deadline sort comes from the tier."""

    name = "commit"

    def run(self, s, eng):
        cfg = eng.cfg
        force_slow = _apply_deadline_cap(s, eng)
        s.exec_order = eng.tier.deadline_order(s.deadlines)
        res = classify_commits(
            s.deadlines, s.arrivals, s.admitted, s.release, s.reply_owd,
            s.leader, cfg.f, leader_batch_delay=cfg.leader_batch_delay,
            key_ids=s.kcls, order=s.exec_order,
            force_slow=force_slow)
        s.commit_time = res["commit_time"]
        s.fast = res["fast"]
        s.committed = res["committed"]


def _apply_deadline_cap(s: EpochState, eng: "DomEngine") -> Optional[np.ndarray]:
    """SD.2.4 deadline cap in the epoch approximation.

    The event backend's leader pulls a deadline more than ``deadline_cap``
    past its local arrival time back to ~the arrival instant; the request
    then commits via the slow path (its re-deadlined position breaks hash
    consistency with the followers). Here: release-at-arrival in the leader
    column + a force-slow mask into `classify_commits`. Second-order effects
    of the re-deadlining on OTHER requests' prefixes are not modeled.
    Returns the capped mask (or None when the cap is off/never binds).
    """
    cap = float(getattr(eng.cfg, "deadline_cap", 0.0) or 0.0)
    if cap <= 0.0:
        return None
    off_l = (s.clock_arr_off[:, s.leader]
             if s.clock_arr_off is not None else 0.0)
    a_loc_lead = s.arrivals[:, s.leader] + off_l
    capped = np.isfinite(a_loc_lead) & (s.deadlines > a_loc_lead + cap)
    if not capped.any():
        return None
    s.admitted = s.admitted.copy()
    s.release = s.release.copy()
    s.admitted[capped, s.leader] = True
    s.release[capped, s.leader] = s.arrivals[capped, s.leader]
    return capped


class DeliverStage(Stage):
    """Reply delivery at the client + latency accounting.

    ``committed`` stays the protocol-level verdict (the entry is in the
    replicated log); ``delivered`` additionally requires the reply to reach
    the client -- a committed-but-undelivered request is retried by the
    client and answered from the at-most-once replay cache (LogStage skips
    re-appending it)."""

    name = "deliver"

    def run(self, s, eng):
        s.commit_at_client = s.commit_time + s.p2c
        # Latency is measured from the ORIGINAL submission (t0): a retried
        # request's earlier timed-out attempts are part of its latency.
        lat = s.commit_at_client - s.t0
        lat[~s.committed] = np.inf
        s.latency = lat
        s.delivered = s.committed & np.isfinite(lat)


class LogStage(Stage):
    """Cross-epoch replica-log bookkeeping (the recovery pipeline's input).

    Appends the epoch's committed entries -- in the tier's claimed execution
    order -- to the shared synced log, advances every live replica's
    sync-point (the steady-state log-modification flow: by epoch end each
    live replica has synced the leader's log), and files uncommitted-but-
    admitted entries as per-replica speculative tails, which is exactly the
    state MERGE-LOG consults at the next view change."""

    name = "log"

    def run(self, s, eng):
        if not eng.track_logs:
            return
        if s.exec_order is None:        # fused tiers: order stays on-device
            s.exec_order = eng.tier.deadline_order(s.deadlines)
        eng.logs.observe_epoch(
            s, reachable=(~eng.unreachable if eng.unreachable.any()
                          else None))


DEFAULT_STAGES = (SampleStage, StampStage, DomStage, CommitStage, DeliverStage,
                  LogStage)
FUSED_STAGES = (SampleStage, FusedEpochStage, DeliverStage, LogStage)


def _partition_percentile(a: np.ndarray, q: float) -> float:
    """np.percentile(a, q) (linear interpolation) via O(n) np.partition.

    Only two order statistics are consumed, so selecting them beats the
    full sort np.percentile does; the interpolation mirrors numpy's _lerp
    (including the monotonicity-preserving form switch at t >= 0.5) so the
    value is bit-identical.
    """
    pos = q / 100.0 * (a.size - 1)
    lo = int(np.floor(pos))
    hi = int(np.ceil(pos))
    part = np.partition(a, [lo, hi])
    lo_v, hi_v = float(part[lo]), float(part[hi])
    t = pos - lo
    if t == 0.0 or lo_v == hi_v:
        return lo_v
    if t < 0.5:
        return lo_v + t * (hi_v - lo_v)
    return hi_v - (hi_v - lo_v) * (1.0 - t)


class ReplicaLogState:
    """Array-structured per-replica logs for the recovery pipeline (SA).

    The epoch approximation keeps ONE shared synced log -- the committed
    entries, in execution order, each stamped with the view and batch that
    committed it -- plus per-replica scalars (`sync_point`,
    `last_normal_view`) and per-replica speculative tails: uncommitted
    entries encoded as columns + an admitted-mask over replicas. That is
    exactly the state Alg 4's MERGE-LOG consults, so a view change is one
    call into `repro.core.recovery.merge_logs_vectorized` (last-normal-view
    filter -> sync-point prefix copy -> ceil(f/2)+1 majority beyond it ->
    key3 re-sort) instead of per-replica Python loops.

    Modeling notes: within an epoch every live replica syncs the leader's
    log by epoch end (the steady-state log-modification flow), so live
    sync-points advance together; a crashed replica loses its in-memory
    state (speculative column cleared, sync-point zeroed, last-normal-view
    -1 = RECOVERING) and a relaunched one completes state transfer during
    its first live epoch (sync-point/last-normal-view catch up then).
    """

    LOG_COLS = ("deadline", "cid", "rid", "kcls", "view", "batch", "recovered")

    def __init__(self, n_replicas: int, f: int):
        self.n = n_replicas
        self.f = f
        self.view = 0
        self.sync_point = np.zeros(n_replicas, np.int64)
        self.last_normal_view = np.zeros(n_replicas, np.int64)
        self.synced_len = 0
        self.tail_deadline = -np.inf        # deadline of the last synced entry
        self._chunks: dict[str, list[np.ndarray]] = {c: [] for c in self.LOG_COLS}
        # speculative tails: entries admitted somewhere but not committed
        self.spec_deadline = np.empty(0)
        self.spec_cid = np.empty(0, np.int64)
        self.spec_rid = np.empty(0, np.int64)
        self.spec_kcls = np.empty(0, np.int64)
        self.spec_admitted = np.empty((0, n_replicas), bool)
        # committed-but-undelivered uids: the client retries these and the
        # replicas answer from the at-most-once replay cache -- the replay
        # commit must not re-enter the log
        self._replay_uids = np.empty(0, np.int64)
        self._batch = 0
        # LossyAcker (Byzantine-leaning) durability model: a lossy replica
        # keeps ACKING normally -- its sync_point advances and quorums count
        # it -- but its durable persistence freezes at `persist_point`. A
        # crash exposes the gap: the acked-but-unpersisted suffix becomes a
        # durability event and a hole in that replica's durable-log view.
        self.lossy = np.zeros(n_replicas, bool)
        self.persist_point = np.zeros(n_replicas, np.int64)
        self.durability_events: list[dict] = []
        self._holes: dict[int, list[tuple[int, int]]] = {}

    # -- log append (per epoch batch) ---------------------------------------
    def observe_epoch(self, s: "EpochState",
                      reachable: Optional[np.ndarray] = None) -> None:
        batch = self._batch
        self._batch += 1
        committed = np.asarray(s.committed, bool)
        order = np.asarray(s.exec_order, np.int64)
        row_uids = pack_uids(s.cid, s.rid)
        exec_idx = order[committed[order]]          # committed, in exec order
        uids = row_uids[exec_idx]
        if self._replay_uids.size:
            replay = np.isin(uids, self._replay_uids)
            if replay.any():
                # replays that finally reached their client stop retrying
                done = uids[replay][np.asarray(s.delivered, bool)[exec_idx[replay]]]
                self._replay_uids = self._replay_uids[
                    ~np.isin(self._replay_uids, done)]
                exec_idx = exec_idx[~replay]
                uids = uids[~replay]
        if exec_idx.size:
            kcls = (s.kcls[exec_idx] if s.kcls is not None
                    else np.full(exec_idx.size, -1, np.int64))
            self._append(s.deadlines[exec_idx], s.cid[exec_idx],
                         s.rid[exec_idx], kcls, batch=batch)
            undelivered = ~np.asarray(s.delivered, bool)[exec_idx]
            if undelivered.any():
                self._replay_uids = np.concatenate(
                    [self._replay_uids, uids[undelivered]])
        # Partitioned-away (unreachable) replicas receive no log
        # modifications: their sync/persist points freeze for the window,
        # which is exactly the asymmetry check_partition_liveness measures.
        sync = (np.asarray(s.alive, bool) if reachable is None
                else np.asarray(s.alive, bool) & reachable)
        self.sync_point[sync] = self.synced_len
        self.last_normal_view[sync] = self.view
        self.persist_point[sync & ~self.lossy] = self.synced_len
        # speculative tails: uncommitted entries some live replica admitted.
        # A failed RETRY of an already-durable uid (committed earlier, reply
        # lost) must NOT re-enter them -- the entry is in the synced log and
        # the oracle's synced-uid membership check would skip it; without
        # this exclusion a view change could append the uid a second time.
        spec = ~committed & np.asarray(s.admitted, bool).any(axis=1)
        if spec.any() and self._replay_uids.size:
            spec &= ~np.isin(row_uids, self._replay_uids)
        if self.spec_deadline.size:
            # an entry leaves the speculative tails when a newer attempt
            # lands (replace) or when it commits (now durable)
            gone = row_uids[spec | committed]
            self._drop_spec(np.isin(
                pack_uids(self.spec_cid, self.spec_rid), gone))
        if spec.any():
            self.spec_deadline = np.concatenate(
                [self.spec_deadline, s.deadlines[spec]])
            self.spec_cid = np.concatenate([self.spec_cid, s.cid[spec]])
            self.spec_rid = np.concatenate([self.spec_rid, s.rid[spec]])
            kcls = (s.kcls[spec] if s.kcls is not None
                    else np.full(int(spec.sum()), -1, np.int64))
            self.spec_kcls = np.concatenate([self.spec_kcls, kcls])
            self.spec_admitted = np.concatenate(
                [self.spec_admitted, np.asarray(s.admitted, bool)[spec]])

    def _append(self, deadline, cid, rid, kcls, batch: int,
                view: Optional[int] = None, recovered: bool = False) -> None:
        k = len(deadline)
        self._chunks["deadline"].append(np.asarray(deadline, np.float64))
        self._chunks["cid"].append(np.asarray(cid, np.int64))
        self._chunks["rid"].append(np.asarray(rid, np.int64))
        self._chunks["kcls"].append(np.asarray(kcls, np.int64))
        self._chunks["view"].append(
            np.full(k, self.view if view is None else view, np.int64))
        self._chunks["batch"].append(np.full(k, batch, np.int64))
        self._chunks["recovered"].append(np.full(k, recovered, bool))
        self.synced_len += k
        if k:
            self.tail_deadline = float(np.asarray(deadline)[-1])

    def _drop_spec(self, mask: np.ndarray) -> None:
        if mask.any():
            keep = ~mask
            self.spec_deadline = self.spec_deadline[keep]
            self.spec_cid = self.spec_cid[keep]
            self.spec_rid = self.spec_rid[keep]
            self.spec_kcls = self.spec_kcls[keep]
            self.spec_admitted = self.spec_admitted[keep]

    def drop_uids(self, cid: np.ndarray, rid: np.ndarray) -> None:
        """Forget speculative entries of abandoned requests (retry cap)."""
        if self.spec_deadline.size:
            gone = pack_uids(cid, rid)
            self._drop_spec(np.isin(
                pack_uids(self.spec_cid, self.spec_rid), gone))

    # -- fault hooks ---------------------------------------------------------
    def set_lossy(self, rid: int) -> None:
        """LossyAcker: from now on replica ``rid`` acks without persisting
        -- its persist point freezes where it stands."""
        self.lossy[rid] = True
        self.persist_point[rid] = self.sync_point[rid]

    def on_crash(self, rid: int) -> None:
        """Diskless crash: the replica's in-memory log state is gone."""
        if self.lossy[rid]:
            # The crash exposes the LossyAcker lie: everything it acked
            # past its frozen persist point was never durable. Record the
            # event (check_durability's evidence) and the hole range its
            # durable-log view excises (check_split_brain's evidence).
            acked = int(self.sync_point[rid])
            persisted = int(self.persist_point[rid])
            if acked > persisted:
                cols = self.log_columns()
                uids = pack_uids(cols["cid"][persisted:acked],
                                 cols["rid"][persisted:acked])
                self.durability_events.append({
                    "replica": rid, "acked": acked, "persisted": persisted,
                    "missing": acked - persisted, "uids": uids})
                self._holes.setdefault(rid, []).append((persisted, acked))
        if self.spec_admitted.size:
            self.spec_admitted[:, rid] = False
        self.sync_point[rid] = 0
        self.persist_point[rid] = 0
        self.last_normal_view[rid] = -1     # RECOVERING until a live epoch

    # -- the view change itself ----------------------------------------------
    def view_change(self, new_view: int, alive: np.ndarray) -> dict:
        """Run the vectorized MERGE-LOG; enter ``new_view``.

        Returns the recovery outcome: ``recovered`` -- column dict of the
        speculative entries the merge kept (appended to the synced log in
        key3 order, stamped recovered); ``dropped`` -- column dict of the
        rest (sub-majority or behind the authoritative prefix; the proxies
        re-admit them into the next epoch's DOM stage).
        """
        alive = np.asarray(alive, bool)
        qualified = qualified_replicas(self.last_normal_view, alive)
        merge_order, keep = merge_logs_vectorized(
            self.spec_deadline, self.spec_cid, self.spec_rid,
            self.spec_admitted, qualified, self.f,
            synced_tail_deadline=self.tail_deadline)
        out = {
            "recovered": {
                "deadline": self.spec_deadline[merge_order],
                "cid": self.spec_cid[merge_order],
                "rid": self.spec_rid[merge_order],
                "kcls": self.spec_kcls[merge_order],
            },
            "dropped": {
                "deadline": self.spec_deadline[~keep],
                "cid": self.spec_cid[~keep],
                "rid": self.spec_rid[~keep],
            },
        }
        batch = self._batch
        self._batch += 1
        rec = out["recovered"]
        if merge_order.size:
            self._append(rec["deadline"], rec["cid"], rec["rid"], rec["kcls"],
                         batch=batch, view=new_view, recovered=True)
        # every live replica installs the merged log via StartView
        self.view = new_view
        self.sync_point[alive] = self.synced_len
        self.last_normal_view[alive] = new_view
        self.persist_point[alive & ~self.lossy] = self.synced_len
        self.spec_deadline = np.empty(0)
        self.spec_cid = np.empty(0, np.int64)
        self.spec_rid = np.empty(0, np.int64)
        self.spec_kcls = np.empty(0, np.int64)
        self.spec_admitted = np.empty((0, self.n), bool)
        return out

    # -- trace export --------------------------------------------------------
    def log_columns(self) -> dict[str, np.ndarray]:
        """The synced log as one column dict (concatenated lazily)."""
        dtypes = dict(deadline=np.float64, cid=np.int64, rid=np.int64,
                      kcls=np.int64, view=np.int64, batch=np.int64,
                      recovered=bool)
        return {c: (np.concatenate(ch) if ch else np.empty(0, dtypes[c]))
                for c, ch in self._chunks.items()}

    @property
    def has_holes(self) -> bool:
        return bool(self._holes)

    def replica_log_columns(self) -> dict[int, dict[str, np.ndarray]]:
        """Per-replica durable-log views: the shared synced log minus each
        replica's recorded durability holes. Identical views everywhere in
        honest runs; a LossyAcker's excised hole shifts its suffix, which is
        the positional divergence check_split_brain detects."""
        full = self.log_columns()
        out: dict[int, dict[str, np.ndarray]] = {}
        for r in range(self.n):
            holes = self._holes.get(r)
            if not holes:
                out[r] = full
                continue
            keep = np.ones(self.synced_len, bool)
            for lo, hi in holes:
                keep[lo:hi] = False
            out[r] = {c: v[keep] for c, v in full.items()}
        return out


class DomEngine:
    """Runs the staged DOM data plane, one epoch batch at a time.

    The engine owns the stage list, the compute tier, and the cross-epoch
    replica-log state feeding the recovery pipeline (`ReplicaLogState`);
    the cluster owns time, the pending buffer, fault events, view changes,
    and result accumulation. Fused tiers (jit, pallas) default to the
    single-dispatch pipeline (sample -> fused -> deliver -> log); the numpy
    tier keeps the staged reference path.
    """

    def __init__(self, cfg, net, n_replicas: int,
                 tier: Union[str, ComputeTier] = "numpy",
                 stages=None, track_logs: bool = True):
        self.cfg = cfg
        self.net = net
        self.n = n_replicas
        self.tier = make_tier(tier)
        if getattr(cfg, "sanitize", False) \
                or os.environ.get("REPRO_SANITIZE", "") not in ("", "0"):
            from repro.core.sanitizer import SanitizerTier

            if not isinstance(self.tier, SanitizerTier):
                self.tier = SanitizerTier(self.tier)
        self.track_logs = track_logs    # benchmarks measuring the pure data
        #   plane (benchmarks/dom_scale.py) opt out of log accumulation
        self.logs = ReplicaLogState(n_replicas, cfg.f)
        if stages is None:
            stages = FUSED_STAGES if self.tier.fused else DEFAULT_STAGES
        self.stages = [s() for s in stages]
        self.owd_pool = np.zeros(0)     # sliding OWD sample pool (StampStage)
        self._bound_cache: Optional[float] = None
        # Clock-fault state (scenario `ClockFault`/`ClockClear` events): per
        # node, the (mu, sigma) of the N(mu, sigma) error added to every
        # clock read. Separate rng stream so fault-free runs are untouched.
        self.replica_clock = np.zeros((n_replicas, 2))
        self.proxy_clock = np.zeros((getattr(cfg, "n_proxies", 1), 2))
        self.rng = np.random.default_rng(getattr(cfg, "seed", 0) + 0xC10C)
        # Per-pair network-fault state (Partition / GrayLink scenario
        # events), [P, R] over (proxy, replica) pairs and lazily allocated:
        # None means no pair fault has ever been active, so SampleStage
        # draws exactly the variates it drew before the adversarial family
        # existed. `unreachable` marks the partition minority: frozen
        # sync/persist points, non-viable leaders (the cluster consults it).
        self._pair_block: Optional[np.ndarray] = None       # [P, R] bool
        self._pair_gray_drop: Optional[np.ndarray] = None   # [P, R] drop prob
        self._pair_mu: Optional[np.ndarray] = None          # [P, R] delay mean
        self._pair_sigma: Optional[np.ndarray] = None       # [P, R] delay sigma
        self.unreachable = np.zeros(n_replicas, bool)
        # SkewedStamper (Byzantine-leaning): per-proxy deterministic stamp
        # bias, folded into the clock stamp_off operand by SampleStage.
        self.proxy_stamp_bias = np.zeros(getattr(cfg, "n_proxies", 1))
        # Modeled clock-sync loop (PR 10): regimes with
        # ``cfg.clock.sync_model`` attach a fleet daemon that owns clock
        # TRUTH (drift/wander/steps) and the MEASURED error bounds; DOM's
        # beta-margin then comes from measurements, not configuration.
        self.clocksync = None
        if getattr(getattr(cfg, "clock", None), "sync_model", False):
            from repro.core.clocksync import ClockSyncDaemon

            self.clocksync = ClockSyncDaemon(
                n_replicas, getattr(cfg, "n_proxies", 1), cfg.clock, net,
                seed=getattr(cfg, "seed", 0))
        self._margin_used: Optional[float] = None

    # -- clock faults (Appendix D) -------------------------------------------
    @property
    def clocks_faulty(self) -> bool:
        return bool(self.replica_clock.any() or self.proxy_clock.any())

    def set_clock_fault(self, role: str, idx: int, mu: float,
                        sigma: float) -> None:
        """Install N(mu, sigma) read error on one node's clock (0, 0 clears).

        ``role`` is "replica" or "proxy"; proxy indices wrap like
        `NezhaCluster.clock_of_proxy` does (non-proxy mode reuses the
        proxy-slot clocks)."""
        if role == "replica":
            if not (0 <= idx < self.n):
                raise ValueError(f"replica id {idx} out of range [0, {self.n})")
            self.replica_clock[idx] = (mu, sigma)
        elif role == "proxy":
            self.proxy_clock[idx % len(self.proxy_clock)] = (mu, sigma)
        else:
            raise ValueError(f"unknown clock role {role!r}")

    # -- per-pair network faults (Partition / GrayLink / SkewedStamper) ------
    @property
    def pairs_faulty(self) -> bool:
        """Any pair-fault state allocated: epochs carry pair operands and
        fall off the K-scan fast path (mirrors `clocks_faulty`)."""
        return self._pair_block is not None

    @property
    def gray_active(self) -> bool:
        return self._pair_gray_drop is not None and bool(
            self._pair_gray_drop.any() or self._pair_mu.any()
            or self._pair_sigma.any())

    @property
    def stampers_biased(self) -> bool:
        return bool(self.proxy_stamp_bias.any())

    # -- modeled clock sync (PR 10) ------------------------------------------
    @property
    def sync_active(self) -> bool:
        """A modeled sync daemon is attached: every epoch carries the
        fleet's effective residual offsets (and round epochs the probe
        operands), so sync regimes fall off the K-scan fast path exactly
        like injected clock faults do."""
        return self.clocksync is not None

    def advance_sync(self, t_end: float) -> None:
        """Advance the daemon's clock truth to the epoch boundary ``t_end``
        and queue any due probe round; no-op without a daemon. The cluster
        calls this once per epoch BEFORE running it."""
        if self.clocksync is not None:
            with obs.span("sync"):
                self.clocksync.advance(float(t_end))

    def _ensure_pair_state(self) -> None:
        if self._pair_block is None:
            P = len(self.proxy_stamp_bias)
            self._pair_block = np.zeros((P, self.n), bool)
            self._pair_gray_drop = np.zeros((P, self.n))
            self._pair_mu = np.zeros((P, self.n))
            self._pair_sigma = np.zeros((P, self.n))

    def _maybe_release_pair_state(self) -> None:
        # Drop back to None once every pair fault has cleared: later epochs
        # return to the exact fault-free draw sequence AND the scan path.
        if self._pair_block is not None and not (
                self._pair_block.any() or self._pair_gray_drop.any()
                or self._pair_mu.any() or self._pair_sigma.any()):
            self._pair_block = None
            self._pair_gray_drop = None
            self._pair_mu = None
            self._pair_sigma = None

    def set_partition(self, minority) -> None:
        """Cut the minority replicas off: no proxy reaches them, their
        replies never arrive, and their sync/persist points freeze (the
        cluster additionally rules them out as viable leaders)."""
        self._ensure_pair_state()
        minority = np.asarray(list(minority), np.int64)
        self.unreachable[:] = False
        self.unreachable[minority] = True
        self._pair_block[:, :] = False
        self._pair_block[:, minority] = True

    def clear_partition(self) -> None:
        self.unreachable[:] = False
        if self._pair_block is not None:
            self._pair_block[:, :] = False
            self._maybe_release_pair_state()

    def set_gray(self, proxy_ids, replica_ids, delay_mu: float,
                 delay_sigma: float, drop_prob: float) -> None:
        """Install a gray failure on the given (proxy, replica) pairs, both
        directions: extra N(mu, sigma)+ path delay and/or extra drops."""
        self._ensure_pair_state()
        ix = np.ix_(np.asarray(list(proxy_ids), np.int64),
                    np.asarray(list(replica_ids), np.int64))
        self._pair_mu[ix] = delay_mu
        self._pair_sigma[ix] = delay_sigma
        self._pair_gray_drop[ix] = drop_prob

    def clear_gray(self, proxy_ids, replica_ids) -> None:
        if self._pair_block is None:
            return
        ix = np.ix_(np.asarray(list(proxy_ids), np.int64),
                    np.asarray(list(replica_ids), np.int64))
        self._pair_mu[ix] = 0.0
        self._pair_sigma[ix] = 0.0
        self._pair_gray_drop[ix] = 0.0
        self._maybe_release_pair_state()

    def set_stamp_bias(self, proxy_id: int, bias: float) -> None:
        """SkewedStamper: proxy ``proxy_id`` stamps deadlines shifted by
        ``bias`` seconds (0 restores honesty). Indices wrap like
        `set_clock_fault` proxy slots do."""
        self.proxy_stamp_bias[proxy_id % len(self.proxy_stamp_bias)] = bias

    def observed_owd_samples(self, s: "EpochState") -> np.ndarray:
        """The OWD samples the proxies' estimators would OBSERVE: recv local
        read minus send local read, i.e. true OWD perturbed by both ends'
        clock errors. Faulty clocks poison the DOM bound pool exactly as the
        event backend's sliding-window estimator is poisoned (negative /
        inflated estimates fall back to the clamp, S4). Per-pair gray delay
        (GrayLink) joins the observed path first, for the same reason: a
        slow-but-alive link inflates the bound the proxies stamp with."""
        owd = s.owd_pr if s.pair_delay is None else s.owd_pr + s.pair_delay
        if s.clock_arr_off is None and s.clock_stamp_off is None:
            return owd
        return owd + s.clock_arr_off - s.clock_stamp_off[:, None]

    def device_pool_state(self) -> tuple[np.ndarray, np.int64, np.int64]:
        """(pool, ptr, cnt) ring-buffer operands mirroring `owd_pool`.

        The ring's live multiset equals the host sliding pool exactly; +inf
        fills the unfilled tail so the device sort-select sees the live
        samples first. Uploaded per dispatch -- a host->device transfer,
        not a synchronizing pull (the fold itself runs in-program).
        """
        W = self.cfg.dom.window * self.n
        pool = np.full(W, np.inf)
        L = self.owd_pool.size
        pool[:L] = self.owd_pool
        return pool, np.int64(L % W), np.int64(L)

    def bound_margin(self) -> float:
        """The clock-error margin added to the OWD percentile (one float64
        operand; host and device add the identical value).

        With a modeled sync daemon the margin is beta * (sigma_S + sigma_R)
        over the daemon's MEASURED per-node bounds at the current epoch
        boundary -- the paper's Eq. (1) fed by the estimator instead of by
        configuration, so degraded sync widens the stamped deadlines and
        recovered sync narrows them back. Without one, the legacy
        configured-residual margin is unchanged bit-for-bit."""
        if self.clocksync is not None:
            sig_s, sig_r = self.clocksync.margin_sigmas()
            return self.cfg.dom.beta * (sig_s + sig_r)
        return self.cfg.dom.beta * 2.0 * self.cfg.clock.residual_sigma

    def update_bound(self, owd_new: np.ndarray) -> float:
        """Fold new OWD samples into the sliding pool; return the DOM bound.

        The percentile is recomputed only when the pool actually changed
        (partition-based selection, O(pool)); an unchanged pool reuses the
        cached bound.
        """
        cfg = self.cfg
        margin = self.bound_margin()
        if margin != self._margin_used:
            # measured-margin drift (a sync round landed, or the reported
            # bound grew through an outage): the cached percentile+margin
            # value is stale even when the pool itself is unchanged
            self._margin_used = margin
            self._bound_cache = None
        new = np.ravel(owd_new)
        if new.size:
            pool = np.concatenate([self.owd_pool, new])
            self.owd_pool = pool[-cfg.dom.window * self.n:]
            self._bound_cache = None
        if self._bound_cache is None:
            if self.owd_pool.size == 0:
                bound = cfg.dom.clamp_d
            else:
                bound = _partition_percentile(self.owd_pool,
                                              cfg.dom.percentile) + margin
                if not (0.0 < bound < cfg.dom.clamp_d):
                    bound = cfg.dom.clamp_d
            self._bound_cache = float(bound)
        return self._bound_cache

    # -- node-id layout (single source; the cluster sizes the network from it)
    def proxy_nodes(self, proxy_ids):
        return self.n + proxy_ids

    def client_nodes(self, client_ids):
        return self.n + self.cfg.n_proxies + client_ids

    def run_epoch(self, due: np.ndarray, alive: np.ndarray, leader: int,
                  release_floor: float = 0.0,
                  dies_at: Optional[np.ndarray] = None) -> EpochState:
        """Push one structured batch (PENDING_DTYPE) through every stage."""
        s = EpochState(
            t=np.ascontiguousarray(due["t"]),
            t0=np.ascontiguousarray(due["t0"]),
            cid=np.ascontiguousarray(due["cid"]),
            rid=np.ascontiguousarray(due["rid"]),
            kcls=(np.ascontiguousarray(due["kcls"])
                  if getattr(self.cfg, "commutative", False) else None),
            alive=np.asarray(alive, bool),
            leader=int(leader),
            release_floor=float(release_floor),
            dies_at=dies_at,
        )
        dl = np.ascontiguousarray(due["dl"])
        if (dl > 0).any():
            # only multi-op-carrying epochs pay the pre_dl operand; all
            # others keep the unmodified (scan-eligible) program shape
            s.pre_deadline = dl
        for stage in self.stages:
            with obs.span(stage.name):
                stage.run(s, self)
        if self.clocksync is not None and self.clocksync.pending is not None:
            # staged tier: the due probe round lands via the numpy twin of
            # the in-program estimator at the SAME epoch slot the fused
            # path consumes it (FusedEpochStage) -- bit-identical fold
            self.clocksync.apply_pending()
        check = getattr(self.tier, "check_epoch", None)
        if check is not None:       # SanitizerTier (repro.core.sanitizer)
            check(s, self)
        return s

    def run_epoch_window(self, dues, alive: np.ndarray, leader: int,
                         release_floor: float = 0.0) -> list:
        """Run a window of fault-free epochs as ONE scanned device dispatch.

        ``dues`` is a sequence of PENDING_DTYPE batches, one per epoch in
        epoch order; its length should be a `SCAN_K_BUCKETS` value (callers
        pad with empty batches).  Empty batches are inert lanes of the scan
        (n_valid = 0: nothing folds, nothing commits) and yield None.

        Preconditions -- the cluster's fast-path guards own them: a fused
        tier, synced clocks, no crash inside the window (``dies_at`` is
        never carried), and alive/leader/release_floor constant across it.
        Host-side sampling, delivery, and log bookkeeping still run per
        epoch IN ORDER (identical rng streams), so the returned EpochStates
        are bit-for-bit identical to sequential `run_epoch` calls; the
        device data plane runs as one `lax.scan` with a single
        end-of-window pull -- zero per-epoch device round trips.
        """
        import jax

        if not self.tier.fused or self.clocks_faulty or self.pairs_faulty \
                or self.stampers_biased or self.sync_active \
                or any(d.size and (d["dl"] > 0).any() for d in dues):
            # (pre-stamped multi-op deadlines need the per-epoch step
            # program's pre_dl operand; the scan variant never carries it)
            return [self.run_epoch(d, alive, leader, release_floor)
                    if d.size else None for d in dues]
        sample = next((st for st in self.stages
                       if isinstance(st, SampleStage)), None)
        deliver = next((st for st in self.stages
                        if isinstance(st, DeliverStage)), None)
        log = next((st for st in self.stages
                    if isinstance(st, LogStage)), None)
        fused_ok = any(isinstance(st, FusedEpochStage) for st in self.stages)
        if sample is None or deliver is None or log is None or not fused_ok:
            # customized stage list: no fused pipeline to mirror
            return [self.run_epoch(d, alive, leader, release_floor)
                    if d.size else None for d in dues]
        cfg = self.cfg
        alive = np.asarray(alive, bool)
        commutative = bool(getattr(cfg, "commutative", False))
        K = len(dues)
        states: list = [None] * K
        for i, due in enumerate(dues):
            if due.size == 0:
                continue
            s = EpochState(
                t=np.ascontiguousarray(due["t"]),
                t0=np.ascontiguousarray(due["t0"]),
                cid=np.ascontiguousarray(due["cid"]),
                rid=np.ascontiguousarray(due["rid"]),
                kcls=(np.ascontiguousarray(due["kcls"])
                      if commutative else None),
                alive=alive,
                leader=int(leader),
                release_floor=float(release_floor),
            )
            with obs.span(sample.name):
                sample.run(s, self)
            states[i] = s
        if all(s is None for s in states):
            return states
        with obs.span("fused.pack"):
            cap = float(getattr(cfg, "deadline_cap", 0.0) or 0.0)
            scan = self.tier.epoch_scan(cfg.f, use_kcls=commutative,
                                        use_cap=cap > 0.0)
            args = self._stack_window(states, alive, leader, release_floor,
                                      cap)
        with jax.enable_x64(True):
            with obs.span("fused.dispatch"):
                out = scan(*args)
            with obs.span("fused.pull"):
                # lint: allow[HS003] the ONE per-window pull: K scanned epochs of fused outputs in a single transfer
                ys = [np.asarray(o) for o in out[:8]]
        check = getattr(self.tier, "check_epoch", None)
        for i, s in enumerate(states):
            if s is None:
                continue
            with obs.span("fused.unpack"):
                N = s.t.size
                (s.stamp, s.deadlines, s.arrivals, s.admitted, s.release,
                 s.commit_time, s.fast, s.committed) = \
                    [y[i][:N] for y in ys]
                # advance the host pool mirror in epoch order;
                # bit-identical to the scanned device fold by construction
                s.bound = self.update_bound(self.observed_owd_samples(s))
                rep = s.reply_owd.copy()
                rep[:, ~alive] = np.inf
                s.reply_owd = rep
                # every tier's device order now equals the stable argsort
                # exactly (int-key kernels break ties by message id), so
                # the log's execution order needs no extra device round trip
                s.exec_order = np.argsort(s.deadlines, kind="stable")
            with obs.span(deliver.name):
                deliver.run(s, self)
            with obs.span(log.name):
                log.run(s, self)
            if check is not None:   # SanitizerTier (repro.core.sanitizer)
                check(s, self)
        return states

    def _stack_window(self, states: list, alive: np.ndarray, leader: int,
                      release_floor: float, cap: float) -> tuple:
        """The scan program's operands, stacked [K, n_pad(, R)]."""
        cfg = self.cfg
        K = len(states)
        R = self.n
        n_pad = max(_pow2_bucket(s.t.size) if self.tier.pad_batches
                    else s.t.size for s in states if s is not None)
        # Stacked [K, n_pad(, R)] operands; one shared bucket across the
        # window (pad lanes are invisible to real rows by construction, so
        # sharing the max bucket is bitwise-inert).
        t = np.full((K, n_pad), np.inf)
        c2p = np.zeros((K, n_pad))
        owd = np.zeros((K, n_pad, R))
        drop = np.ones((K, n_pad, R), dtype=bool)
        reply = np.full((K, n_pad, R), np.inf)
        kcls = np.full((K, n_pad), -1, np.int64)
        n_valid = np.zeros(K, np.int64)
        for i, s in enumerate(states):
            if s is None:
                continue
            N = s.t.size
            t[i, :N] = s.t
            c2p[i, :N] = s.c2p
            owd[i, :N] = s.owd_pr
            drop[i, :N] = s.drop_pr
            reply[i, :N] = s.reply_owd
            if s.kcls is not None:
                kcls[i, :N] = s.kcls
            n_valid[i] = N
        pool, ptr, cnt = self.device_pool_state()
        return (pool, ptr, cnt, t, c2p, owd, drop, reply, kcls, n_valid,
                alive, int(leader), float(cfg.dom.percentile) / 100.0,
                self.bound_margin(), float(cfg.dom.clamp_d),
                float(cfg.leader_batch_delay), cap, float(release_floor))


__all__ = [
    "PENDING_DTYPE", "PendingBuffer",
    "ComputeTier", "NumpyTier", "JitTier", "PallasTier", "TIERS", "make_tier",
    "classify_commits", "SCAN_K_BUCKETS",
    "EpochState", "Stage", "SampleStage", "StampStage", "DomStage",
    "CommitStage", "DeliverStage", "LogStage", "FusedEpochStage",
    "DEFAULT_STAGES", "FUSED_STAGES", "ReplicaLogState", "DomEngine",
]
