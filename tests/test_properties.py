"""Hypothesis property tests for the system's invariants.

These model-check the pure protocol math (DOM ordering, hashing algebra,
merge-log durability) over randomized inputs, and the full event-driven
cluster over randomized crash schedules.
"""
import math

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need the optional "
                    "hypothesis dependency (see requirements-dev.txt)")
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core.dom import EarlyBuffer
from repro.core.hashing import IncrementalHash, entry_hash32_np, entry_hash_np, fold_hashes_np
from repro.core.messages import LogEntry, OpType, Request, ViewChange
from repro.core.quorum import QuorumTracker, fast_quorum_size
from repro.core.recovery import (
    aggregate_crash_vectors,
    merge_logs,
    merge_logs_vectorized,
    qualified_replicas,
)
from repro.core.vectorized import dom_release_schedule_chunked

# ---------------------------------------------------------------------------
# DOM consistent ordering (the paper's core invariant, S3/S4)
# ---------------------------------------------------------------------------
deadline_lists = st.lists(
    st.floats(min_value=0.0, max_value=1.0, allow_nan=False), min_size=1, max_size=40)


@settings(max_examples=200, deadline=None)
@given(
    deadlines=deadline_lists,
    seed=st.integers(0, 2**30),
)
def test_dom_consistent_ordering_any_arrival_order(deadlines, seed):
    """Two receivers processing the same messages in *any* arrival orders
    release non-commutative messages in the same relative order."""
    rng = np.random.default_rng(seed)
    n = len(deadlines)
    reqs = [Request(client_id=0, request_id=i, deadline=d, send_time=0.0,
                    latency_bound=d, op=OpType.WRITE, keys=())
            for i, d in enumerate(deadlines)]

    def run_receiver(perm, drop_mask):
        eb = EarlyBuffer(commutative=False)
        released = []
        for idx in perm:
            if drop_mask[idx]:
                continue
            # arrivals late enough that everything already queued released
            released += [r.request_id for r in eb.release_ready(reqs[idx].deadline + rng.random())]
            eb.insert(reqs[idx])
        released += [r.request_id for r in eb.release_ready(math.inf)]
        return released

    perm1, perm2 = rng.permutation(n), rng.permutation(n)
    drops1 = rng.random(n) < 0.2
    drops2 = rng.random(n) < 0.2
    r1, r2 = run_receiver(perm1, drops1), run_receiver(perm2, drops2)
    common = set(r1) & set(r2)
    f1 = [x for x in r1 if x in common]
    f2 = [x for x in r2 if x in common]
    assert f1 == f2, "consistent ordering violated"


def _exact_admission(deadlines, arrivals):
    """Replay arrivals through the event-driven EarlyBuffer."""
    n = len(deadlines)
    out = np.zeros((n, arrivals.shape[1]), dtype=bool)
    for rcv in range(arrivals.shape[1]):
        eb = EarlyBuffer(commutative=False)
        order = np.argsort(arrivals[:, rcv], kind="stable")
        for idx in order:
            eb.release_ready(arrivals[idx, rcv])
            out[idx, rcv] = eb.insert(
                Request(client_id=0, request_id=int(idx), deadline=float(deadlines[idx]),
                        send_time=0.0, latency_bound=0.0, op=OpType.WRITE))
    return out


@settings(max_examples=100, deadline=None)
@given(
    n=st.integers(2, 24),
    seed=st.integers(0, 2**30),
)
def test_vectorized_release_matches_exact(n, seed):
    """The scan-based vectorized DOM schedule equals the event-driven one,
    even under pathological reordering (arrival noise ~ deadline span)."""
    from repro.core.vectorized import dom_release_schedule

    rng = np.random.default_rng(seed)
    deadlines = np.sort(rng.uniform(0, 1.0, n)) + rng.uniform(0, 1e-6, n)
    arrivals = deadlines[:, None] + rng.normal(0, 0.3, (n, 2))  # heavy reorder
    admitted, _ = dom_release_schedule(deadlines, arrivals)
    np.testing.assert_array_equal(np.asarray(admitted), _exact_admission(deadlines, arrivals))


@settings(max_examples=50, deadline=None)
@given(
    n=st.integers(8, 200),
    seed=st.integers(0, 2**30),
)
def test_chunked_release_matches_exact_realistic(n, seed):
    """The chunked fast path is exact under realistic OWD spreads (arrival
    lateness << chunk deadline span)."""
    rng = np.random.default_rng(seed)
    send = np.sort(rng.uniform(0, 1.0, n))
    deadlines = send + 100e-6
    arrivals = send[:, None] + rng.lognormal(np.log(60e-6), 0.6, (n, 3))
    admitted, _ = dom_release_schedule_chunked(deadlines, arrivals, chunk=64)
    np.testing.assert_array_equal(np.asarray(admitted), _exact_admission(deadlines, arrivals))


# ---------------------------------------------------------------------------
# watermark admission (the O(N log N) production path) vs the exact oracle
# ---------------------------------------------------------------------------
def _adversarial_dom_instance(n, r, seed, grid, drop_p, late_scale,
                              inf_deadline_p, kill_receiver):
    """Adversarial DOM instances: duplicate deadlines (coarse f32-exact
    grid), arrivals far beyond the deadline, inf-dropped arrivals, whole
    receivers dropped, inf deadlines."""
    rng = np.random.default_rng(seed)
    if grid:
        deadlines = rng.integers(0, 8, n) / 64.0
        arrivals = rng.integers(0, 24, (n, r)) / 64.0
    else:
        deadlines = np.sort(rng.uniform(0, 1.0, n))
        arrivals = deadlines[:, None] + rng.uniform(-0.2, late_scale, (n, r))
    if inf_deadline_p:
        deadlines = deadlines.copy()
        deadlines[rng.random(n) < inf_deadline_p] = np.inf
    arrivals[rng.random((n, r)) < drop_p] = np.inf
    if kill_receiver:
        arrivals[:, rng.integers(0, r)] = np.inf
    return deadlines, arrivals


@settings(max_examples=60, deadline=None)
@given(
    n=st.integers(1, 48),
    r=st.integers(1, 3),
    seed=st.integers(0, 2**30),
    grid=st.booleans(),
    drop_p=st.sampled_from([0.0, 0.2, 0.6]),
    late_scale=st.sampled_from([0.05, 0.5, 2.0]),
    inf_deadline_p=st.sampled_from([0.0, 0.15]),
    kill_receiver=st.booleans(),
)
def test_watermark_admission_matches_exact_oracle(n, r, seed, grid, drop_p,
                                                  late_scale, inf_deadline_p,
                                                  kill_receiver):
    """The event-ordered watermark admission (numpy and jit tiers) equals
    the retained O(N^2) `dom_release_schedule` oracle on adversarial cases:
    late arrivals beyond the deadline, duplicate deadlines, inf-dropped
    arrivals, and all-dropped receivers."""
    import jax
    import jax.numpy as jnp

    from repro.core.vectorized import (
        _watermark_schedule_jit,
        dom_admit_watermark_np,
        dom_release_schedule,
    )

    deadlines, arrivals = _adversarial_dom_instance(
        n, r, seed, grid, drop_p, late_scale, inf_deadline_p, kill_receiver)
    with jax.enable_x64(True):
        want = np.asarray(dom_release_schedule(
            jnp.asarray(deadlines, jnp.float64),
            jnp.asarray(arrivals, jnp.float64))[0])
        got_jit = np.asarray(_watermark_schedule_jit(
            jnp.asarray(deadlines, jnp.float64),
            jnp.asarray(arrivals, jnp.float64))[0])
    np.testing.assert_array_equal(want, dom_admit_watermark_np(deadlines, arrivals))
    np.testing.assert_array_equal(want, got_jit)


@pytest.mark.pallas
@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 24),
    r=st.integers(1, 3),
    seed=st.integers(0, 2**30),
    drop_p=st.sampled_from([0.0, 0.3]),
    kill_receiver=st.booleans(),
)
def test_watermark_admission_pallas_matches_oracle(n, r, seed, drop_p,
                                                   kill_receiver):
    """All three tiers on one instance: the fused Pallas admit kernel must
    agree with the oracle on f32-exact grid instances (duplicate deadlines
    tie-break through the same integer aux key as the float64 paths)."""
    import jax
    import jax.numpy as jnp

    from repro.core.vectorized import dom_release_schedule
    from repro.kernels.ops import dom_admit

    deadlines, arrivals = _adversarial_dom_instance(
        n, r, seed, grid=True, drop_p=drop_p, late_scale=0.0,
        inf_deadline_p=0.1, kill_receiver=kill_receiver)
    with jax.enable_x64(True):
        want = np.asarray(dom_release_schedule(
            jnp.asarray(deadlines, jnp.float64),
            jnp.asarray(arrivals, jnp.float64))[0])
    np.testing.assert_array_equal(want, dom_admit(deadlines, arrivals,
                                                  use_pallas=False))
    np.testing.assert_array_equal(want, dom_admit(deadlines, arrivals,
                                                  use_pallas=True))


# ---------------------------------------------------------------------------
# hashing algebra
# ---------------------------------------------------------------------------
entry_tuples = st.lists(
    st.tuples(st.integers(0, 2**40), st.integers(0, 1000), st.integers(0, 2**20)),
    min_size=0, max_size=50, unique=True)


@settings(max_examples=200)
@given(entries=entry_tuples, seed=st.integers(0, 2**30))
def test_incremental_hash_equals_batch_hash(entries, seed):
    rng = np.random.default_rng(seed)
    inc = IncrementalHash()
    perm = rng.permutation(len(entries))
    for i in perm:
        inc.add(*entries[i])
    if entries:
        batch = fold_hashes_np(entry_hash_np(*map(np.asarray, zip(*entries))))
    else:
        batch = np.uint64(0)
    assert inc.set_hash == int(batch)


@settings(max_examples=200)
@given(entries=entry_tuples)
def test_hash_add_remove_inverse(entries):
    inc = IncrementalHash()
    for e in entries:
        inc.add(*e)
    for e in entries:
        inc.remove(*e)
    assert inc.set_hash == 0


@settings(max_examples=100)
@given(
    a=st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=32),
    b=st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=32),
)
def test_crash_vector_aggregate_lattice(a, b):
    n = min(len(a), len(b))
    a, b = tuple(a[:n]), tuple(b[:n])
    m = aggregate_crash_vectors([a, b])
    assert aggregate_crash_vectors([m, a]) == m        # absorbing
    assert aggregate_crash_vectors([b, a]) == m        # commutative
    assert all(x >= y for x, y in zip(m, a))           # dominates inputs


# ---------------------------------------------------------------------------
# merge-log durability (SB.1)
# ---------------------------------------------------------------------------
@settings(max_examples=150, deadline=None, suppress_health_check=[HealthCheck.filter_too_much])
@given(
    f=st.integers(1, 3),
    n_entries=st.integers(1, 12),
    seed=st.integers(0, 2**30),
)
def test_fast_committed_entries_survive_any_f_crashes(f, n_entries, seed):
    """If an entry is on the leader + f+ceil(f/2) followers (fast commit),
    it survives merge_logs over ANY f+1 survivors."""
    rng = np.random.default_rng(seed)
    n = 2 * f + 1
    fq = fast_quorum_size(f)
    deadlines = np.sort(rng.uniform(0, 1, n_entries))
    # every entry is placed on a random super quorum (fast-path commit)
    placement = np.zeros((n_entries, n), dtype=bool)
    for i in range(n_entries):
        placement[i, rng.choice(n, size=fq, replace=False)] = True
    logs = []
    for r in range(n):
        entries = [LogEntry(deadline=float(deadlines[i]), client_id=0, request_id=i,
                            request=Request(client_id=0, request_id=i,
                                            deadline=float(deadlines[i])))
                   for i in range(n_entries) if placement[i, r]]
        logs.append(entries)
    # crash any f replicas; merge over survivors (all NORMAL, sync_point=0)
    crashed = set(rng.choice(n, size=f, replace=False).tolist())
    survivors = [r for r in range(n) if r not in crashed][: f + 1]
    vcs = [ViewChange(replica_id=r, view_id=1, crash_vector=tuple([0] * n),
                      log=logs[r], sync_point=0, last_normal_view=0)
           for r in survivors]
    merged = merge_logs(vcs, f)
    merged_ids = {e.request_id for e in merged}
    for i in range(n_entries):
        # quorum intersection: fq + (f+1) - n = ceil(f/2)+1 copies remain
        assert i in merged_ids, f"fast-committed entry {i} lost (f={f})"


@settings(max_examples=100, deadline=None)
@given(f=st.integers(1, 3), seed=st.integers(0, 2**30))
def test_synced_prefix_survives(f, seed):
    """Slow-path commits (sync-point majority) survive: the merged log starts
    with the largest synced prefix among the qualified replicas."""
    rng = np.random.default_rng(seed)
    n = 2 * f + 1
    n_entries = 10
    deadlines = np.sort(rng.uniform(0, 1, n_entries))
    entries = [LogEntry(deadline=float(d), client_id=0, request_id=i,
                        request=Request(client_id=0, request_id=i, deadline=float(d)))
               for i, d in enumerate(deadlines)]
    sp = int(rng.integers(1, n_entries + 1))
    # f+1 replicas synced through sp (slow-path commit of entries < sp)
    vcs = []
    holders = rng.choice(n, size=f + 1, replace=False)
    for r in range(n):
        if r in holders:
            vcs.append(ViewChange(replica_id=r, view_id=1, crash_vector=tuple([0] * n),
                                  log=entries[:sp], sync_point=sp, last_normal_view=0))
    merged = merge_logs(vcs[: f + 1], f)
    assert [e.request_id for e in merged[:sp]] == list(range(sp))


# ---------------------------------------------------------------------------
# vectorized MERGE-LOG vs the Alg 4 oracle (the recovery stage's math)
# ---------------------------------------------------------------------------
def _entry(d: float, cid: int, rid: int) -> LogEntry:
    return LogEntry(deadline=float(d), client_id=int(cid), request_id=int(rid),
                    request=Request(client_id=int(cid), request_id=int(rid),
                                    deadline=float(d)))


def _random_recovery_state(f, n_synced, n_spec, seed):
    """A random engine-reachable recovery state: one shared synced log with
    per-replica sync-point prefixes, per-replica last-normal-views, a crash
    schedule (alive mask, >= f+1 alive), and uid-unique speculative entries
    with distinct deadlines interleaving the synced range."""
    rng = np.random.default_rng(seed)
    n = 2 * f + 1
    deadlines = np.sort(rng.choice(np.arange(1, 10 * (n_synced + n_spec)),
                                   size=n_synced + n_spec, replace=False)
                        .astype(float))
    sy_idx = np.sort(rng.choice(n_synced + n_spec, size=n_synced,
                                replace=False))
    sp_mask = np.ones(n_synced + n_spec, bool)
    sp_mask[sy_idx] = False
    synced_d = deadlines[sy_idx]
    spec_d = deadlines[sp_mask]
    synced = [_entry(d, 0, i) for i, d in enumerate(synced_d)]
    spec_cid = rng.integers(1, 4, n_spec)
    spec_rid = np.arange(n_spec)
    spec_adm = rng.random((n_spec, n)) < rng.uniform(0.2, 0.9)
    alive = np.zeros(n, bool)
    alive[rng.choice(n, size=int(rng.integers(f + 1, n + 1)),
                     replace=False)] = True
    lnv = rng.integers(-1, 3, n)
    lnv[np.flatnonzero(alive)[0]] = max(2, lnv.max())  # >=1 qualified survivor
    sp = rng.integers(0, n_synced + 1, n)
    best = lnv[alive].max()
    sp[alive & (lnv == best)] = np.sort(sp[alive & (lnv == best)])[::-1]
    return synced, spec_d, spec_cid, spec_rid, spec_adm, alive, lnv, sp


@settings(max_examples=150, deadline=None)
@given(
    f=st.integers(1, 3),
    n_synced=st.integers(0, 10),
    n_spec=st.integers(0, 16),
    seed=st.integers(0, 2**30),
)
def test_vectorized_merge_matches_merge_logs_oracle(f, n_synced, n_spec, seed):
    """Tentpole acceptance: the vectorized MERGE-LOG equals `merge_logs`
    (the Alg 4 oracle) entry-for-entry on random logs and crash schedules:
    same last-normal-view filter, same sync-point prefix copy, same
    ceil(f/2)+1 majority, same (deadline, client, request) order."""
    synced, spec_d, spec_cid, spec_rid, spec_adm, alive, lnv, sp = \
        _random_recovery_state(f, n_synced, n_spec, seed)
    # oracle: each live replica's ViewChange carries its synced prefix plus
    # its speculative tail, in log order
    vcs = []
    for r in np.flatnonzero(alive):
        tail = [_entry(spec_d[m], spec_cid[m], spec_rid[m])
                for m in np.flatnonzero(spec_adm[:, r])]
        tail.sort(key=lambda e: e.key3)
        vcs.append(ViewChange(
            replica_id=int(r), view_id=9, crash_vector=tuple([0] * len(alive)),
            log=synced[: sp[r]] + tail, sync_point=int(sp[r]),
            last_normal_view=int(lnv[r])))
    want = [e.key3 for e in merge_logs(vcs, f)]
    # vectorized: the engine's array-structured equivalent
    qualified = qualified_replicas(lnv, alive)
    prefix = int(sp[qualified].max())
    tail_d = synced[prefix - 1].deadline if prefix else -math.inf
    merge_order, keep = merge_logs_vectorized(
        spec_d, spec_cid, spec_rid, spec_adm, qualified, f,
        synced_tail_deadline=tail_d)
    got = [e.key3 for e in synced[:prefix]] + [
        (float(spec_d[m]), int(spec_cid[m]), int(spec_rid[m]))
        for m in merge_order]
    assert got == want
    assert keep.sum() == merge_order.size


@settings(max_examples=100, deadline=None)
@given(
    f=st.integers(1, 3),
    n_spec=st.integers(0, 20),
    seed=st.integers(0, 2**30),
)
def test_vectorized_merge_output_invariants(f, n_spec, seed):
    """On ANY random state: the merge output preserves every synced-prefix
    entry, executes nothing twice (uid-unique, even with duplicate-uid
    retry attempts in the input), and is key3-sorted."""
    rng = np.random.default_rng(seed)
    n = 2 * f + 1
    spec_d = rng.uniform(0, 1, n_spec)
    spec_cid = rng.integers(0, 3, n_spec)
    spec_rid = rng.integers(0, 4, n_spec)          # uid collisions likely
    spec_adm = rng.random((n_spec, n)) < 0.7
    qualified = rng.random(n) < 0.7
    qualified[rng.integers(0, n)] = True
    tail = float(rng.uniform(0, 0.5))
    merge_order, keep = merge_logs_vectorized(
        spec_d, spec_cid, spec_rid, spec_adm, qualified, f,
        synced_tail_deadline=tail)
    thresh = math.ceil(f / 2) + 1
    key3 = [(float(spec_d[m]), int(spec_cid[m]), int(spec_rid[m]))
            for m in merge_order]
    uids = [(c, r) for _, c, r in key3]
    assert len(set(uids)) == len(uids)             # at-most-once
    assert key3 == sorted(key3)                    # (deadline, cid, rid) order
    assert all(d >= tail for d, _, _ in key3)      # prefix stays authoritative
    counts = spec_adm[:, qualified].sum(axis=1)
    for m in merge_order:
        assert counts[m] >= thresh                 # majority-held only
    # anything majority-held, uid-unique and past the tail must survive
    from repro.core.recovery import pack_uids

    packed = pack_uids(spec_cid, spec_rid)
    uniq, cnt = np.unique(packed, return_counts=True)
    solo = np.isin(packed, uniq[cnt == 1])
    must_keep = solo & (counts >= thresh) & (spec_d >= tail)
    assert np.all(keep[must_keep])


# ---------------------------------------------------------------------------
# quorum tracker sanity under arbitrary reply interleavings
# ---------------------------------------------------------------------------
@settings(max_examples=200)
@given(
    f=st.integers(1, 3),
    seed=st.integers(0, 2**30),
)
def test_quorum_never_commits_without_leader(f, seed):
    rng = np.random.default_rng(seed)
    tr = QuorumTracker(f=f)
    n = 2 * f + 1
    for rid in range(1, n):            # every follower, never the leader
        if rng.random() < 0.5:
            tr.add_fast(rid, 0, hash_=7, result=None)
        else:
            tr.add_slow(rid, 0)
    assert tr.check_committed() is None


# ---------------------------------------------------------------------------
# vectorized commit classification sanity
# ---------------------------------------------------------------------------
@settings(max_examples=50, deadline=None)
@given(seed=st.integers(0, 2**30))
def test_vectorized_commit_times_sane(seed):
    """nezha_commit_times: fast implies committed; fast commits need the
    super quorum's replies; commit time >= leader reply arrival."""
    from repro.core.vectorized import nezha_commit_times

    rng = np.random.default_rng(seed)
    n, R, f = 60, 3, 1
    send = np.sort(rng.uniform(0, 0.01, n))
    owd = rng.lognormal(np.log(60e-6), 0.5, (n, R))
    deadlines = send + np.percentile(owd, 60)
    arrivals = send[:, None] + owd
    reply = rng.lognormal(np.log(60e-6), 0.5, (n, R))
    out = nezha_commit_times(deadlines, arrivals, reply, leader=0, f=f)
    fast, committed, ct = out["fast"], out["committed"], out["commit_time"]
    assert not np.any(fast & ~committed)
    assert np.all(np.isinf(ct) | (ct >= arrivals[:, 0] - 1e-12) | ~committed)
    # with generous deadlines everything should commit
    assert committed.mean() > 0.9
