"""Staged DOM engine: tier parity, epoch closed loop, fault epochs, and the
structured data-plane plumbing (pending buffer, key interning, paired reply
sampling)."""
import numpy as np
import pytest

from repro import obs
from repro.core import CommonConfig, make_cluster
from repro.core.engine import (
    PENDING_DTYPE,
    DomEngine,
    JitTier,
    NumpyTier,
    PallasTier,
    PendingBuffer,
    make_tier,
)
from repro.core.vectorized_cluster import VectorizedConfig
from repro.sim.network import CloudNetwork, NetworkParams
from repro.sim.workload import Workload, WorkloadDriver

RNG = np.random.default_rng(11)


def _instance(n=200, r=3, seed=0):
    """A realistic (deadlines, arrivals) DOM instance with distinct
    deadlines (>=1us spacing over a ~ms span)."""
    rng = np.random.default_rng(seed)
    send = np.sort(rng.uniform(0, 5e-3, n))
    send += np.arange(n) * 1e-6              # enforce distinct spacing
    deadlines = send + 120e-6
    arrivals = send[:, None] + rng.lognormal(np.log(60e-6), 0.6, (n, r))
    arrivals[rng.random((n, r)) < 0.02] = np.inf   # a few drops
    return deadlines, arrivals


def _adversarial_instance(style, n, r, seed):
    """DOM instances the watermark admission must survive exactly: late
    arrivals beyond the deadline, duplicate deadlines, inf-dropped arrivals,
    all-dropped receivers.  The Pallas kernels compare exact int32 key
    words, so every style -- continuous or grid-valued -- must match the
    float64 tiers bit-for-bit, ties included."""
    rng = np.random.default_rng(seed)
    if style == "late":            # arrivals up to 2x span past the deadline
        d = np.sort(rng.uniform(0, 1, n))
        a = d[:, None] + rng.uniform(0.0, 2.0, (n, r))
    elif style == "dup-deadlines":  # heavy deadline collisions, f32-exact
        d = rng.integers(0, 8, n) / 64.0
        a = rng.integers(0, 24, (n, r)) / 64.0
    elif style == "drops":          # inf arrivals + one all-dropped receiver
        d = rng.integers(0, 16, n) / 64.0
        a = (d[:, None] * 64 + rng.integers(-8, 16, (n, r))) / 64.0
        a[rng.random((n, r)) < 0.25] = np.inf
        a[:, 0] = np.inf
    else:                           # inf deadlines mixed in ("inf-deadlines")
        d = rng.integers(0, 8, n) / 64.0
        d[rng.random(n) < 0.15] = np.inf
        a = rng.integers(0, 16, (n, r)) / 64.0
    return d, a


def _exact_oracle_admission(d, a):
    """The retained O(N^2) scan oracle, traced in float64."""
    import jax
    import jax.numpy as jnp

    from repro.core.vectorized import dom_release_schedule

    with jax.enable_x64(True):
        adm, _ = dom_release_schedule(jnp.asarray(d, jnp.float64),
                                      jnp.asarray(a, jnp.float64))
        return np.asarray(adm)


def test_release_oracle_preserves_float64_without_caller_x64():
    """Regression (found by the ISSUE 6 dtype-parity linter):
    `dom_release_schedule` jitted its body without owning an enable_x64
    scope, so a BARE call (no caller-side enable_x64, unlike
    `_exact_oracle_admission` above) silently truncated float64 deadlines
    to float32 -- deadlines separated below the f32 ulp collapsed to one
    value and flipped admission. The oracle now enters enable_x64 itself."""
    from repro.core.vectorized import (dom_release_schedule,
                                       dom_release_schedule_chunked)

    d = np.array([1000.0, 1000.0 + 1e-5])   # < f32 ulp at 1000 (~6.1e-5)
    a = np.array([[999.0], [1000.5]])
    # B's deadline exceeds the watermark A released (1000.0) by 1e-5, so
    # f64 admits B at its late arrival; under f32 truncation the two
    # deadlines collapse and B is rejected
    admitted, release = dom_release_schedule(d, a)
    assert np.asarray(release).dtype == np.float64
    np.testing.assert_array_equal(np.asarray(admitted), [[True], [True]])
    np.testing.assert_array_equal(np.asarray(admitted),
                                  _exact_oracle_admission(d, a))
    # the chunked fast path feeds the oracle per-chunk and must agree
    adm_c, _ = dom_release_schedule_chunked(d, a, chunk=2)
    np.testing.assert_array_equal(np.asarray(adm_c), [[True], [True]])


# ---------------------------------------------------------------------------
# tier parity
# ---------------------------------------------------------------------------
ADVERSARIAL = ["late", "dup-deadlines", "drops", "inf-deadlines"]


@pytest.mark.parametrize("style", ADVERSARIAL)
def test_watermark_tiers_match_exact_oracle_adversarial(style):
    """Tentpole acceptance: the O(N log N) watermark admission (numpy + jit
    tiers) equals the retained O(N^2) scan oracle on adversarial cases."""
    for seed in range(5):
        d, a = _adversarial_instance(style, n=31, r=3, seed=seed)
        want = _exact_oracle_admission(d, a)
        adm_np, rel_np = NumpyTier().release_schedule(d, a)
        adm_jit, rel_jit = JitTier().release_schedule(d, a)
        np.testing.assert_array_equal(want, adm_np, err_msg=f"numpy {style}")
        np.testing.assert_array_equal(want, adm_jit, err_msg=f"jit {style}")
        np.testing.assert_array_equal(rel_np, rel_jit)
        # release = max(deadline, arrival) under admission, inf otherwise
        np.testing.assert_array_equal(
            rel_np, np.where(adm_np, np.maximum(d[:, None], a), np.inf))


@pytest.mark.pallas
@pytest.mark.parametrize("style", ADVERSARIAL)
def test_watermark_pallas_matches_oracle_adversarial(style):
    """The fused dom_admit kernel agrees too -- including the continuous
    "late" style whose sub-f32-resolution pairs used to sit inside the
    span-relative-f32 tie window. Exact int32 keys make parity
    unconditional."""
    for seed in range(3):
        d, a = _adversarial_instance(style, n=21, r=3, seed=seed)
        want = _exact_oracle_admission(d, a)
        adm, _ = PallasTier().release_schedule(d, a)
        np.testing.assert_array_equal(want, adm, err_msg=f"pallas {style}")


@pytest.mark.pallas
def test_pallas_exact_on_sub_microsecond_ties():
    """Acceptance: an adversarial instance stuffed with exact duplicates
    AND nanosecond-separated deadlines (far below the f32 ulp of the span,
    the old `F32TieRiskWarning` regime) orders and admits identically to
    the float64 tiers -- no tie-window exemption."""
    rng = np.random.default_rng(3)
    base = np.sort(rng.uniform(0, 5e-3, 64))
    # each base deadline spawns an exact duplicate and two 1ns-separated
    # neighbours: ~2.4e-10 relative spacing, unrepresentable span-relative
    d = (base[:, None] + np.array([0.0, 0.0, 1e-9, 2e-9])).ravel()
    perm = rng.permutation(d.size)
    d = d[perm]
    a = d[:, None] + rng.uniform(-2e-9, 2e-9, (d.size, 3))
    a[rng.random((d.size, 3)) < 0.1] = np.inf

    np.testing.assert_array_equal(PallasTier().deadline_order(d),
                                  np.argsort(d, kind="stable"))
    np.testing.assert_array_equal(PallasTier().deadline_order(d),
                                  NumpyTier().deadline_order(d))
    want = _exact_oracle_admission(d, a)
    adm_pal, rel_pal = PallasTier().release_schedule(d, a)
    adm_jit, rel_jit = JitTier().release_schedule(d, a)
    np.testing.assert_array_equal(want, adm_pal)
    np.testing.assert_array_equal(adm_jit, adm_pal)
    np.testing.assert_array_equal(rel_jit, rel_pal)


def test_numpy_jit_tier_parity():
    deadlines, arrivals = _instance(seed=1)
    a_np = NumpyTier(chunk=64).release_schedule(deadlines, arrivals)
    a_jit = JitTier().release_schedule(deadlines, arrivals)
    np.testing.assert_array_equal(a_np[0], a_jit[0])        # admission
    np.testing.assert_allclose(a_np[1], a_jit[1])           # release times
    np.testing.assert_array_equal(
        NumpyTier().deadline_order(deadlines), JitTier().deadline_order(deadlines))


@pytest.mark.pallas
def test_pallas_tier_parity():
    """Acceptance: all three tiers produce identical admission/release
    schedules and release (deadline) orders on the same instance."""
    deadlines, arrivals = _instance(seed=2)
    ref_adm, ref_rel = NumpyTier().release_schedule(deadlines, arrivals)
    pal = PallasTier()
    adm, rel = pal.release_schedule(deadlines, arrivals)
    np.testing.assert_array_equal(ref_adm, adm)
    np.testing.assert_allclose(ref_rel, rel)
    np.testing.assert_array_equal(
        NumpyTier().deadline_order(deadlines), pal.deadline_order(deadlines))


@pytest.mark.pallas
def test_pallas_tier_through_cluster_matches_numpy():
    """Same seed + workload through all three tier registry entries. With
    exact int32 kernel keys ALL tiers must agree bit-for-bit -- the old
    f32 tie tolerance on the pallas row is gone."""
    w = Workload(mode="open", rate_per_client=500.0, duration=0.08,
                 warmup=0.01, drain=0.05, seed=0)
    outs = {}
    for name in ("nezha-vectorized", "nezha-vectorized-jit",
                 "nezha-vectorized-pallas"):
        outs[name] = WorkloadDriver(w).run(
            make_cluster(name, CommonConfig(f=1, n_clients=2, seed=0)))
    base = outs["nezha-vectorized"]
    assert base["tier"] == "numpy"
    for name, tier in (("nezha-vectorized-jit", "jit"),
                       ("nezha-vectorized-pallas", "pallas")):
        out = outs[name]
        assert out["tier"] == tier
        assert out["committed"] == base["committed"]
        assert out["fast_commit_ratio"] == base["fast_commit_ratio"]
        assert out["median_latency"] == base["median_latency"]


def test_make_tier_rejects_unknown():
    with pytest.raises(KeyError, match="unknown compute tier"):
        make_tier("gpu")
    t = NumpyTier()
    assert make_tier(t) is t


# ---------------------------------------------------------------------------
# pending buffer + key interning
# ---------------------------------------------------------------------------
def test_pending_buffer_grows_and_pops_in_time_order():
    buf = PendingBuffer(capacity=2)
    ts = RNG.uniform(0, 1.0, 100)
    for i, t in enumerate(ts):
        buf.append(t, i % 5, i, i % 3)
    assert len(buf) == 100
    due = buf.pop_due(0.5)
    assert due.dtype == PENDING_DTYPE
    assert (due["t"] <= 0.5).all()
    assert (np.diff(due["t"]) >= 0).all()           # time-sorted
    assert len(buf) == 100 - due.size
    assert buf.min_time() > 0.5
    rest = buf.pop_due(np.inf)
    assert due.size + rest.size == 100
    assert buf.pop_due(np.inf).size == 0 and len(buf) == 0
    assert buf.min_time() == np.inf


def test_key_classes_are_interned_not_hashed():
    """Satellite fix: commutativity classes must be stable per cluster
    (insertion-order interning), not builtin-hash dependent."""
    cl = make_cluster("nezha-vectorized", CommonConfig(f=1, n_clients=1))
    cl.submit_at(0.0, 0, keys=(42,))
    cl.submit_at(0.0, 0, keys=(7, 9))
    cl.submit_at(0.0, 0, keys=(42,))
    cl.submit_at(0.0, 0)                            # keyless -> global class
    assert cl._key_classes == {(42,): 0, (7, 9): 1}
    due = cl._pending.pop_due(np.inf)
    np.testing.assert_array_equal(due["kcls"], [0, 1, 0, -1])


def test_same_seed_same_summary():
    """Seeds reproduce: two identical runs give identical summaries."""
    w = Workload(mode="open", rate_per_client=800.0, duration=0.08, seed=3)
    runs = [WorkloadDriver(w).run(
        make_cluster("nezha-vectorized", CommonConfig(f=1, n_clients=3, seed=5)))
        for _ in range(2)]
    assert runs[0] == runs[1]


# ---------------------------------------------------------------------------
# per-epoch network sampling
# ---------------------------------------------------------------------------
def test_sample_owd_pairs_uses_per_pair_paths():
    """Satellite fix: paired sampling must use each (src, dst) path's own
    persistent offset (the proxy->actual-client reply path), not one
    representative column."""
    params = NetworkParams(lognorm_sigma=1e-9, burst_prob=0.0, drop_prob=0.0,
                           path_offset_sigma=50e-6)
    net = CloudNetwork(6, params, seed=0)
    srcs = np.array([0, 1, 2, 0, 1])
    dsts = np.array([3, 4, 5, 4, 3])
    owd, dropped = net.sample_owd_pairs(srcs, dsts)
    assert owd.shape == (5,) and dropped.shape == (5,)
    assert not dropped.any()
    want = params.base_owd + net._path_offset[srcs, dsts] \
        + np.exp(params.lognorm_mu)
    np.testing.assert_allclose(owd, want, rtol=1e-3)


def _epoch_batch(n, n_clients=4, seed=11, kcls_n=5):
    rng = np.random.default_rng(seed)
    due = np.zeros(n, PENDING_DTYPE)
    due["t"] = np.sort(rng.uniform(0, 5e-3, n))
    due["t0"] = due["t"]
    due["cid"] = rng.integers(0, n_clients, n)
    due["rid"] = np.arange(n)
    due["kcls"] = rng.integers(0, kcls_n, n)
    return due


def _run_one_epoch(tier, due, cfg, alive=None, leader=0, net_seed=0):
    net = CloudNetwork(3 + cfg.n_proxies + cfg.n_clients, cfg.net, seed=net_seed)
    eng = DomEngine(cfg, net, 3, tier=tier)
    alive = np.ones(3, bool) if alive is None else alive
    return eng, eng.run_epoch(due.copy(), alive, leader=leader)


def test_fused_epoch_step_matches_staged_numpy_bitwise():
    """Satellite acceptance: the fused single-dispatch epoch program (jit
    tier) reproduces the staged numpy pipeline BIT-FOR-BIT -- including the
    float64-sensitive fast/slow boundary -- because it is traced under x64
    with the identical op order."""
    cfg = VectorizedConfig(f=1, n_clients=4, seed=0)
    due = _epoch_batch(50)
    eng_np, s_np = _run_one_epoch("numpy", due, cfg)
    eng_jit, s_jit = _run_one_epoch("jit", due, cfg)
    assert [st.name for st in eng_np.stages] == [
        "sample", "stamp", "dom", "commit", "deliver", "log"]
    assert [st.name for st in eng_jit.stages] == [
        "sample", "fused", "deliver", "log"]
    # both fast- and slow-path commits must be exercised for the boundary
    # comparison to mean anything
    assert 0 < int(np.sum(s_np.fast)) < int(np.sum(s_np.committed))
    for field in ("stamp", "deadlines", "arrivals", "admitted", "release",
                  "commit_time", "fast", "committed", "latency"):
        np.testing.assert_array_equal(
            getattr(s_np, field), getattr(s_jit, field), err_msg=field)
    assert s_np.bound == s_jit.bound


def test_fused_epoch_step_with_crashed_replica_matches_staged():
    """Fused path under partial outage: alive-masking, the slow-path fetch
    estimate and leader re-election inputs all live inside the fused
    program; they must still match the staged path exactly."""
    cfg = VectorizedConfig(f=1, n_clients=4, seed=0)
    due = _epoch_batch(40, seed=7)
    alive = np.array([False, True, True])
    _, s_np = _run_one_epoch("numpy", due, cfg, alive=alive, leader=1)
    _, s_jit = _run_one_epoch("jit", due, cfg, alive=alive, leader=1)
    for field in ("admitted", "release", "commit_time", "fast", "committed"):
        np.testing.assert_array_equal(
            getattr(s_np, field), getattr(s_jit, field), err_msg=field)


# ---------------------------------------------------------------------------
# device-resident bound / fetch estimators vs the host oracles
# ---------------------------------------------------------------------------
def test_tree_sum_is_pow2_padding_invariant():
    """The lemma the shared-bucket scan rests on: the fold-halves tree sum
    ignores zero padding up to any pow2 size, so padded device batches
    reduce to the exact host value."""
    from repro.core.engine import _tree_sum

    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 5, 8, 13, 100, 1000):
        x = rng.uniform(0, 1e-3, n)
        s = _tree_sum(x)
        for pad in (1, 3, 64):
            assert _tree_sum(np.concatenate([x, np.zeros(pad)])) == s
        np.testing.assert_allclose(s, x.sum(), rtol=1e-12)
    assert _tree_sum(np.array([])) == 0.0


def test_fetch_estimate_masks_nonfinite_and_handles_empty():
    from repro.core.engine import _fetch_estimate, _tree_sum

    rng = np.random.default_rng(1)
    x = rng.uniform(0, 1e-3, (7, 3))
    x[rng.random((7, 3)) < 0.3] = np.inf
    fin = np.isfinite(x)
    want = 3.0 * (_tree_sum(np.where(fin, x, 0.0).ravel()) / int(fin.sum()))
    assert _fetch_estimate(x) == want
    assert _fetch_estimate(np.full((2, 2), np.inf)) == np.inf


def test_device_percentile_and_ring_pool_match_host_sliding_pool():
    """Seeded sweep (hypothesis-style, without the dependency): the
    device order-statistic bound -- ring-pool fold + sort-select +
    `_lerp`-compatible interpolation -- equals the host
    `update_bound`/`_partition_percentile` pipeline EXACTLY, epoch for
    epoch, across pool sizes, duplicates, overflow, clamping, empty pools,
    and q endpoints; and the carried ring equals the host sliding pool."""
    import jax

    from repro.core.engine import _partition_percentile

    tier = JitTier()
    scan = tier.epoch_scan(1, use_kcls=False)
    r, K, n_pad = 3, 4, 8
    W = 18   # window*R; NOT a pow2, and one epoch (n_pad*r = 24) overflows it
    cases = [   # (seed, q, clamp_d, quantize)
        (0, 95.0, 1.0, False),
        (1, 0.0, 1.0, False),      # q=0 endpoint + empty-pool epochs
        (2, 100.0, 1.0, True),     # q=100 endpoint + heavy duplicates
        (3, 50.0, 1.0, True),
        (4, 25.0, 1.0, False),     # t < 0.5 interpolation branch
        (5, 77.3, 1.0, True),      # t >= 0.5 branch, duplicates
        (6, 95.0, 5e-4, False),    # clamp engages
    ]
    for seed, q, clamp_d, quantize in cases:
        rng = np.random.default_rng(seed)
        n_valid = rng.integers(0, n_pad + 1, K)
        n_hist = int(rng.integers(0, W))
        if seed == 1:               # cold start: bound = clamp until samples
            n_valid[:2] = 0
            n_hist = 0
        owd = rng.uniform(1e-5, 8e-4, (K, n_pad, r))
        if quantize:
            owd = np.round(owd, 4)
        hist = rng.uniform(1e-5, 8e-4, n_hist)
        pool0 = np.full(W, np.inf)
        pool0[:n_hist] = hist
        margin = 1e-4
        args = (pool0, np.int64(n_hist % W), np.int64(n_hist),
                np.tile(np.linspace(0, 1e-3, n_pad), (K, 1)),
                np.full((K, n_pad), 1e-5),
                owd,
                np.zeros((K, n_pad, r), bool),
                np.full((K, n_pad, r), 1e-4),
                np.zeros((K, n_pad), np.int64),
                n_valid.astype(np.int64),
                np.ones(r, bool), 0,
                float(q) / 100.0, margin, float(clamp_d), 0.0, 0.0, 0.0)
        with jax.enable_x64(True):
            out = scan(*args)
        bounds = np.asarray(out[8])
        pool_dev = np.asarray(out[9])
        ptr_dev, cnt_dev = int(out[10]), int(out[11])
        host: list = hist.tolist()
        for k in range(K):
            host.extend(owd[k, : n_valid[k]].ravel().tolist())
            host = host[-W:]
            if not host:
                want = clamp_d
            else:
                want = _partition_percentile(np.asarray(host), q) + margin
                if not (0.0 < want < clamp_d):
                    want = clamp_d
            assert bounds[k] == want, f"seed={seed} q={q} epoch={k}"
        live = (pool_dev[(ptr_dev + np.arange(W)) % W] if cnt_dev == W
                else pool_dev[:cnt_dev])
        np.testing.assert_array_equal(live, np.asarray(host),
                                      err_msg=f"seed={seed} ring vs pool")


# ---------------------------------------------------------------------------
# K-epochs-per-dispatch scan parity (the cluster fast path)
# ---------------------------------------------------------------------------
def _k_dispatch_cluster(name, k, crash=None):
    cfg = VectorizedConfig(f=1, n_clients=3, seed=0, client_timeout=5.0,
                           epochs_per_dispatch=k)
    cl = make_cluster(name, cfg)
    cl.start()
    rng = np.random.default_rng(42)
    for i, t in enumerate(np.sort(rng.uniform(0.0, 0.25, 200))):
        cl.submit_at(float(t), i % 3, keys=(i % 5,))
    if crash is not None:
        cl.crash_at(crash, 0)
    with obs.recording() as rec:
        cl.run_for(0.3)
    return cl, rec["counters"].get("epochs.scan", 0)


def _assert_bitwise_equal_runs(cl_a, cl_b):
    from repro.sim.trace import CommitTrace

    assert cl_a.summary() == cl_b.summary()
    np.testing.assert_array_equal(np.concatenate(cl_a._latencies),
                                  np.concatenate(cl_b._latencies))
    assert cl_a.epoch_leaders == cl_b.epoch_leaders
    np.testing.assert_array_equal(cl_a.engine.owd_pool, cl_b.engine.owd_pool)
    tr_a = CommitTrace.from_cluster(cl_a)
    tr_b = CommitTrace.from_cluster(cl_b)
    for col, arr in tr_a.log.items():
        np.testing.assert_array_equal(arr, tr_b.log[col],
                                      err_msg=f"log.{col}")
    for col, arr in tr_a.commits.items():
        np.testing.assert_array_equal(arr, tr_b.commits[col],
                                      err_msg=f"commits.{col}")


def test_k_scan_dispatch_is_bitwise_identical_to_per_epoch_jit():
    """Tentpole acceptance: K-epochs-per-dispatch (`run_epoch_window` via
    `lax.scan`) is bit-for-bit identical to the sequential per-epoch fused
    path on a fault-free run -- same commits, latencies, leaders, OWD
    pool, and committed sequence."""
    base, base_scanned = _k_dispatch_cluster("nezha-vectorized-jit", 1)
    scan, scanned = _k_dispatch_cluster("nezha-vectorized-jit", 64)
    # the fast path actually ran: the K=1 run never dispatches a scan
    # window, the K=64 run does
    assert base_scanned == 0
    assert scanned > 0
    _assert_bitwise_equal_runs(base, scan)


def test_k_scan_crash_segments_and_stays_bitwise_identical():
    """Fault boundaries segment the scan: a leader crash mid-run forces
    the per-epoch path through detection + view change, and the K>1 run
    still equals K=1 bitwise (recovery included)."""
    base, _ = _k_dispatch_cluster("nezha-vectorized-jit", 1, crash=0.05)
    scan, scanned = _k_dispatch_cluster("nezha-vectorized-jit", 64,
                                        crash=0.05)
    assert scan.summary()["view_changes"] == 1      # recovery exercised
    assert scanned > 0
    _assert_bitwise_equal_runs(base, scan)


@pytest.mark.pallas
def test_k_scan_dispatch_parity_pallas():
    """The scan fast path composes with the Pallas kernels: K=64 pallas ==
    K=1 pallas == K=1 jit, bitwise."""
    jit1, _ = _k_dispatch_cluster("nezha-vectorized-jit", 1)
    pal1, _ = _k_dispatch_cluster("nezha-vectorized-pallas", 1)
    pal64, scanned = _k_dispatch_cluster("nezha-vectorized-pallas", 64)
    assert scanned > 0
    _assert_bitwise_equal_runs(pal1, pal64)
    np.testing.assert_array_equal(np.concatenate(jit1._latencies),
                                  np.concatenate(pal64._latencies))


def test_engine_epoch_pipeline_smoke():
    cfg = VectorizedConfig(f=1, n_clients=4, seed=0)
    net = CloudNetwork(3 + cfg.n_proxies + cfg.n_clients, cfg.net, seed=0)
    eng = DomEngine(cfg, net, 3, tier="numpy")
    due = np.zeros(50, PENDING_DTYPE)
    due["t"] = np.sort(RNG.uniform(0, 5e-3, 50))
    due["t0"] = due["t"]
    due["cid"] = RNG.integers(0, 4, 50)
    due["rid"] = np.arange(50)
    due["kcls"] = RNG.integers(0, 5, 50)
    s = eng.run_epoch(due, np.ones(3, bool), leader=0)
    assert s.committed.sum() > 45
    lat = s.latency[s.committed]
    assert (lat > 0).all() and np.isfinite(lat).all()
    assert 0.0 < s.bound <= cfg.dom.clamp_d
    # stage names document the pipeline
    assert [st.name for st in eng.stages] == [
        "sample", "stamp", "dom", "commit", "deliver", "log"]


# ---------------------------------------------------------------------------
# closed-loop epoch approximation vs the exact event backend
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_closed_loop_event_vs_vectorized_parity():
    """Satellite: closed-loop fast-ratio and p50 latency agree between the
    exact event simulator and the epoch approximation on a small instance."""
    cfg = CommonConfig(f=1, n_clients=2, seed=0)
    w = Workload(mode="closed", duration=0.06, drain=0.05, seed=0)
    ev = WorkloadDriver(w).run(make_cluster("nezha", cfg))
    vec = WorkloadDriver(w).run(make_cluster("nezha-vectorized", cfg))
    assert vec["committed"] > 0 and ev["committed"] > 0
    assert 0.4 < vec["median_latency"] / ev["median_latency"] < 2.5
    assert abs(vec["fast_commit_ratio"] - ev["fast_commit_ratio"]) < 0.3


# ---------------------------------------------------------------------------
# fault epochs
# ---------------------------------------------------------------------------
def test_crash_mid_run_changes_leader_in_subsequent_epochs():
    """Acceptance: a crash mid-run re-elects the leader for later epochs and
    the run keeps committing (slow path) with a view-change penalty."""
    cl = make_cluster("nezha-vectorized", CommonConfig(f=1, n_clients=2, seed=0))
    cl.start()
    for i in range(200):
        cl.submit_at(i * 5e-4, i % 2, keys=(i % 7,))
    cl.crash_at(0.05, 0)                  # the leader dies mid-run
    cl.run_for(0.12)
    s = cl.summary()
    assert cl.leader_id == 1
    assert s["view_changes"] == 1
    assert s["committed"] == 200          # f=1 tolerates one failure
    leaders = np.asarray(cl.epoch_leaders)
    switch = np.flatnonzero(np.diff(leaders))
    assert switch.size == 1               # exactly one leader change...
    assert set(leaders[: switch[0] + 1]) == {0}
    assert set(leaders[switch[0] + 1:]) == {1}   # ...and it sticks


def test_view_change_cost_is_measured_not_constant():
    """Tentpole acceptance: recovery cost is the measured pipeline (failure
    detection + ViewChange quorum + StartView quorum over sampled OWDs), so
    requests caught by the crash stall for at least the detection window --
    and the measured completion time shows up in `view_change_events`."""
    cfg = VectorizedConfig(f=1, n_clients=1, seed=0, heartbeat_timeout=8e-3)
    pre = make_cluster("nezha-vectorized", cfg)
    post = make_cluster("nezha-vectorized", cfg)
    for cl in (pre, post):
        for i in range(40):
            # strictly after the crash instant: the t=0.05 epoch boundary
            # flushes submissions due AT the boundary with the old leader
            cl.submit_at(0.0501 + i * 1e-4, 0, keys=(i,))
    post.crash_at(0.05, 0)                # leader change right before batch
    pre.run_for(0.1)
    post.run_for(0.1)
    p50_pre = pre.summary()["median_latency"]
    p50_post = post.summary()["median_latency"]
    (vc,) = post.view_change_events
    # detection window + two sampled quorum legs, well under a constant-2ms
    # regime and well over the fault-free latency
    assert vc["t_done"] > vc["t_start"] + cfg.heartbeat_timeout
    assert vc["t_done"] < vc["t_start"] + cfg.heartbeat_timeout + 5e-3
    # every caught request commits only after the measured completion: even
    # the newest submission (t0 = 0.054) stalls until StartView
    lat = np.concatenate(post._latencies)
    finite = lat[np.isfinite(lat)]
    assert finite.size == 40
    assert finite.min() >= vc["t_done"] - 0.054 - 1e-12
    assert p50_post >= vc["t_done"] - 0.054
    assert p50_post > p50_pre + 3e-3          # the measured stall dominates


def test_relaunch_keeps_view_based_leader():
    """Leadership is view-based like the event backend: a relaunched old
    leader re-joins as a follower; the view (and its leader) stand until the
    CURRENT leader fails. A second view change then wraps past replica 2."""
    cl = make_cluster("nezha-vectorized", CommonConfig(f=1, n_clients=1, seed=0))
    cl.crash(0)
    assert cl.leader_id == 1
    cl.run_for(0.05)
    cl.relaunch(0)
    cl.run_for(0.05)
    assert cl.leader_id == 1                      # no flip-back
    assert cl.summary()["view_changes"] == 1      # one completed recovery
    cl.crash(1)
    cl.crash(2)                                   # view 2's leader is down too
    cl.run_for(0.08)
    assert cl.leader_id == 0                      # view 3 wraps to replica 0
    assert cl.summary()["view_changes"] == 3
    with pytest.raises(ValueError, match="out of range"):
        cl.crash(7)


@pytest.mark.pallas
def test_deadline_order_with_nonfinite_is_a_permutation():
    """Dropped stamps (inf deadlines) must not collide with the kernel's own
    pow2-padding lanes: the order must remain a permutation of [0, n)."""
    from repro.kernels.ops import dom_deadline_order

    d = np.array([1e-3, 2e-3, 3e-3, np.inf, 4e-3])   # n=5 -> padded to 8
    for use_pallas in (False, True):
        order = dom_deadline_order(d, use_pallas=use_pallas)
        assert sorted(order.tolist()) == [0, 1, 2, 3, 4]
        np.testing.assert_array_equal(order[:4], [0, 1, 2, 4])  # finite first
    assert dom_deadline_order(np.full(3, np.inf)).size == 3


def test_client_retry_revives_failed_attempts():
    """Satellite of the closed-loop fix: an attempt lost to a drop or outage
    is re-issued client_timeout later with its original latency baseline,
    so lanes survive instead of dying silently."""
    from repro.core.vectorized_cluster import VectorizedConfig

    cfg = VectorizedConfig(f=1, n_clients=1, seed=0, client_timeout=20e-3)
    cl = make_cluster("nezha-vectorized", cfg)
    cl.submit_at(1e-3, 0, keys=(1,))
    cl.crash(1)
    cl.crash(2)                 # quorum gone: every attempt fails
    cl.run_for(0.05)
    assert cl.summary()["committed"] == 0
    assert len(cl._pending) == 1                    # still retrying
    cl.relaunch(1)
    cl.run_for(0.1)
    s = cl.summary()
    assert s["committed"] == 1 and s["n_requests"] == 1   # retries aren't new
    # latency spans the outage: >= one full retry timeout
    assert s["median_latency"] > cfg.client_timeout


def test_nonpositive_epoch_duration_rejected():
    from repro.core.vectorized_cluster import VectorizedConfig

    with pytest.raises(ValueError, match="epoch_duration"):
        make_cluster("nezha-vectorized", VectorizedConfig(epoch_duration=0.0))


def test_retry_cap_abandons_request():
    from repro.core.vectorized_cluster import VectorizedConfig

    cfg = VectorizedConfig(f=1, n_clients=1, seed=0, client_timeout=5e-3,
                           max_retries=3)
    cl = make_cluster("nezha-vectorized", cfg)
    for rid in range(1, 3):
        cl.crash(rid)           # permanent quorum loss
    cl.submit_at(0.0, 0, keys=(1,))
    cl.run_for(0.2)
    assert len(cl._pending) == 0                    # gave up
    s = cl.summary()
    assert s["committed"] == 0 and s["n_requests"] == 1


def test_total_outage_epochs_commit_nothing():
    cl = make_cluster("nezha-vectorized", CommonConfig(f=1, n_clients=1, seed=0))
    for rid in range(3):
        cl.crash(rid)
    for i in range(20):
        cl.submit_at(i * 1e-3, 0, keys=(i,))
    cl.run_for(0.05)
    assert cl.summary()["committed"] == 0
    assert set(cl.epoch_leaders) == {-1}
    cl.relaunch(0)
    cl.relaunch(1)
    for i in range(20):
        cl.submit_at(0.05 + i * 1e-3, 0, keys=(i,))
    cl.run_for(0.05)
    assert cl.summary()["committed"] > 0          # quorum back -> commits again
