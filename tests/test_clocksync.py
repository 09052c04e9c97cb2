"""The modeled clock-sync loop (PR 10): clock processes + an NTP-style
multi-peer estimator whose MEASURED error bounds drive DOM's margin.

Covers: the shared estimator's numpy-vs-jit bitwise parity; the honest-
bound coverage property under drift/wander/step/bias adversaries; the
satellite regressions (smeared resync monotonicity, bound growth after a
daemon outage, staggered per-clock sync phases); the four cataloged sync
scenarios firing their paired invariants on event/numpy/jit with silent
controls; and cross-tier bitwise parity of the whole sync evidence.
"""
from dataclasses import replace

import numpy as np
import pytest

from repro.core.clock import Clock, ClockParams, SyncService
from repro.core.clocksync import ClockSyncDaemon, estimate_offsets
from repro.sim.events import EventScheduler
from repro.sim.network import CloudNetwork, NetworkParams
from repro.sim.scenario import (
    SYNC_SCENARIOS,
    ClockLeap,
    Scenario,
    SyncBias,
    SyncOutage,
    SyncRestore,
    get_scenario,
)
from repro.sim.trace import (
    ADVERSARIAL_CHECKS,
    check_adversarial,
    check_sync_coverage,
    check_trace,
    run_scenario_with_trace,
)
from repro.sim.workload import Workload

_SYNC_PARAMS = ClockParams(drift_ppm_sigma=50.0, sync_model=True)


def _paired(trace, name: str):
    return ADVERSARIAL_CHECKS[get_scenario(name).invariant](trace)


# ---------------------------------------------------------------------------
# the estimator: bitwise numpy-vs-jit parity + robustness
# ---------------------------------------------------------------------------
def _random_round(seed: int, m: int = None):
    rng = np.random.default_rng(seed)
    m = m or int(rng.integers(3, 12))
    theta = rng.normal(0.0, 5e-5, (m, m))
    rtt = rng.uniform(1e-4, 5e-4, (m, m))
    np.fill_diagonal(rtt, np.inf)
    if seed % 3 == 0:
        rtt[0, :] = np.inf          # a deaf node: every probe lost
    if seed % 4 == 0:
        rtt[1, 2] = np.inf          # one lost peer
    return theta, rtt


@pytest.mark.parametrize("seed", range(12))
def test_estimator_numpy_vs_jit_bitwise(seed):
    import jax
    import jax.numpy as jnp

    theta, rtt = _random_round(seed)
    safety, floor = np.float64(1.5), np.float64(200e-9)
    en, sn = estimate_offsets(theta, rtt, np, safety, floor)
    f = jax.jit(lambda t, r, s, fl: estimate_offsets(t, r, jnp, s, fl))
    with jax.enable_x64(True):
        ej, sj = f(theta, rtt, safety, floor)
    np.testing.assert_array_equal(en, np.asarray(ej))
    np.testing.assert_array_equal(sn, np.asarray(sj))


def test_estimator_rejects_congested_outlier():
    """A peer whose selected RTT blows past 3x the row median is cut, so
    its (badly biased) theta sample cannot move the estimate."""
    m = 6
    theta = np.zeros((m, m))
    rtt = np.full((m, m), 200e-6)
    np.fill_diagonal(rtt, np.inf)
    est0, _ = estimate_offsets(theta.copy(), rtt.copy(), np,
                               np.float64(1.5), np.float64(200e-9))
    theta[0, 1] = 5e-3              # wildly wrong sample...
    rtt[0, 1] = 5e-3                # ...on a visibly congested path
    est1, _ = estimate_offsets(theta, rtt, np,
                               np.float64(1.5), np.float64(200e-9))
    np.testing.assert_array_equal(est0, est1)


def test_estimator_deaf_row_reports_zero_with_floor():
    theta, rtt = _random_round(0)
    est, sigma = estimate_offsets(theta, rtt, np,
                                  np.float64(1.5), np.float64(200e-9))
    assert est[0] == 0.0            # deaf row: no estimate
    assert np.all(np.isfinite(sigma)) and np.all(sigma >= 200e-9)


# ---------------------------------------------------------------------------
# satellite 1: smeared resync keeps local time monotone
# ---------------------------------------------------------------------------
def test_resync_never_steps_time_backwards():
    """The old resync collapsed the offset to a fresh residual, discarding
    accrued drift: a clock 40us ahead stepped backwards. Corrections are
    now slew-smeared, so reads straddling a resync stay monotone."""
    p = ClockParams(drift_ppm_sigma=200.0, resync_interval=0.05,
                    read_jitter=0.0)
    clk = Clock(3, p, seed=7)
    clk.drift = abs(clk.drift) + 100e-6     # force visible forward drift
    last = -np.inf
    t = 0.0
    for k in range(400):
        t += 0.001
        if k and k % 50 == 0:
            clk.resync(t)
        now = clk.read(t)
        assert now > last, f"clock stepped backwards at t={t:.3f}"
        last = now


def test_correct_never_steps_time_backwards():
    """Same property for measured corrections (sync_model path), including
    a correction larger than the inter-read drift."""
    p = replace(_SYNC_PARAMS, read_jitter=0.0)
    clk = Clock(1, p, seed=11)
    last = -np.inf
    t = 0.0
    for k in range(300):
        t += 0.001
        if k and k % 40 == 0:
            clk.correct(t, -clk.probe_offset(t), 1e-6)
        now = clk.read(t)
        assert now > last, f"clock stepped backwards at t={t:.3f}"
        last = now


# ---------------------------------------------------------------------------
# satellite 2: the reported bound is measured and GROWS between syncs
# ---------------------------------------------------------------------------
def test_sigma_estimate_grows_after_service_stop():
    """Pre-PR-10, `sigma_estimate` stayed frozen at the configured constant
    after `SyncService.stop()` while drift accrued unbounded -- DOM kept
    trusting a dead daemon. Now the bound grows at the 3-sigma drift rate
    from the last measurement."""
    sched = EventScheduler()
    net = CloudNetwork(4, NetworkParams(), seed=0)
    clocks = [Clock(i, _SYNC_PARAMS, seed=3) for i in range(4)]
    svc = SyncService(clocks, sched, _SYNC_PARAMS, network=net, seed=3)
    assert svc._modeled
    svc.start()
    sched.run_for(0.2)
    t0 = sched.now
    synced = [c.sigma_at(t0) for c in clocks]
    svc.stop()
    stopped = [c.sigma_at(t0 + 1.0) for c in clocks]
    for s0, s1 in zip(synced, stopped):
        assert s1 > s0, "bound frozen after the sync service stopped"
    # growth rate: 3 sigma of drift + wander per second since measurement
    p = _SYNC_PARAMS
    rate = 3.0 * p.drift_ppm_sigma * 1e-6 + p.wander_sigma
    assert stopped[0] >= synced[0] + 0.9 * rate


def test_daemon_outage_bound_exceeds_synced_era():
    """Vectorized daemon flavor: during a probe outage the reported bound
    keeps growing; once probes resume it re-converges."""
    m_params = replace(_SYNC_PARAMS, sync_interval=0.02)
    net = CloudNetwork(5, NetworkParams(), seed=1)
    ds = ClockSyncDaemon(3, 2, m_params, net, seed=1)
    ds.advance(0.1)
    ds.apply_pending()
    synced = ds.sigma_report(0.1).max()
    ds.set_outage(True)
    ds.advance(0.4)
    outage = ds.sigma_report(0.4).max()
    assert outage > 2.0 * synced
    ds.set_outage(False)
    ds.advance(0.7)
    ds.apply_pending()
    recovered = ds.sigma_report(0.7).max()
    assert recovered < outage


# ---------------------------------------------------------------------------
# satellite 3: per-clock sync phases are staggered
# ---------------------------------------------------------------------------
def test_sync_ticks_are_staggered():
    """A same-instant fleet-wide resync erased all relative offset
    structure in one step. Per-clock phases carry seeded jitter: no two
    clocks tick at the same instant."""
    sched = EventScheduler()
    net = CloudNetwork(5, NetworkParams(), seed=0)
    clocks = [Clock(i, _SYNC_PARAMS, seed=5) for i in range(5)]
    svc = SyncService(clocks, sched, _SYNC_PARAMS, network=net, seed=5)
    svc.start()
    sched.run_for(3.0 * _SYNC_PARAMS.sync_interval)
    cols = svc.evidence_columns()
    per_node_first = {}
    for t, node in zip(cols["t"], cols["node"]):
        per_node_first.setdefault(int(node), float(t))
    times = sorted(per_node_first.values())
    assert len(times) == 5
    assert len(set(times)) == 5, f"clocks tick in lockstep: {times}"


# ---------------------------------------------------------------------------
# the coverage property: the reported bound covers the true offset
# ---------------------------------------------------------------------------
def _daemon_coverage(params: ClockParams, *, seed: int, t_end: float = 2.0,
                     mutate=None) -> float:
    net = CloudNetwork(5, NetworkParams(), seed=seed)
    ds = ClockSyncDaemon(3, 2, params, net, seed=seed)
    t, step = 0.0, 0.01
    while t < t_end:
        t = round(t + step, 10)
        ds.advance(t)
        if mutate is not None:
            mutate(ds, t)
    ds.apply_pending()
    cols = ds.evidence_columns()
    return float((np.abs(cols["err"]) <= 4.0 * cols["sigma"]).mean())


@pytest.mark.parametrize("seed", range(3))
def test_coverage_under_drift_and_wander(seed):
    p = replace(_SYNC_PARAMS, wander_sigma=3e-7)
    assert _daemon_coverage(p, seed=seed) >= 0.95


@pytest.mark.parametrize("seed", range(3))
def test_coverage_under_spontaneous_steps(seed):
    """VM-migration steps from the clock process itself: each step may
    legitimately miss ONE round (nothing bounds an unobserved leap); the
    confidence level absorbs it."""
    p = replace(_SYNC_PARAMS, step_rate=1.0, step_sigma=100e-6)
    assert _daemon_coverage(p, seed=seed) >= 0.95


def test_coverage_under_injected_leap():
    fired = []

    def mutate(ds, t):
        if not fired and t >= 1.0:
            ds.step([0], 300e-6)
            fired.append(t)

    assert _daemon_coverage(_SYNC_PARAMS, seed=4, mutate=mutate) >= 0.95
    assert fired


def test_coverage_under_probe_path_bias():
    """Biased probe paths shift the estimate, but the MAD-driven bound
    inflates to match: coverage holds because the bound is measured."""
    def mutate(ds, t):
        if t >= 0.5 and ds.probe_bias is None:
            ds.set_probe_bias([0, 1, 2, 3, 4], [1, 2], 140e-6)

    assert _daemon_coverage(_SYNC_PARAMS, seed=5, mutate=mutate) >= 0.95


def test_coverage_during_outage():
    """The grown bound must cover drift accrued while probes are down."""
    def mutate(ds, t):
        if 0.5 <= t < 1.5:
            ds.set_outage(True)
        else:
            ds.set_outage(False)

    assert _daemon_coverage(_SYNC_PARAMS, seed=6, mutate=mutate) >= 0.95


# ---------------------------------------------------------------------------
# scenario validation
# ---------------------------------------------------------------------------
def _sync_sc(faults) -> Scenario:
    return Scenario("t", environment="drifty-clocks", faults=faults,
                    workload=Workload(duration=0.3, drain=0.1))


def test_validation_rejects_restore_without_outage():
    with pytest.raises(ValueError, match="no open SyncOutage"):
        _sync_sc((SyncRestore(0.1),))


def test_validation_rejects_overlapping_outages():
    with pytest.raises(ValueError, match="already down"):
        _sync_sc((SyncOutage(0.05), SyncOutage(0.1), SyncRestore(0.2)))


def test_validation_rejects_bad_bias_selector():
    with pytest.raises(ValueError):
        _sync_sc((SyncBias(0.05, src="all", dst="replica:99", bias=1e-6),))


def test_validation_rejects_zero_leap():
    with pytest.raises(ValueError, match="finite and nonzero"):
        _sync_sc((ClockLeap(0.05, who="leader", delta=0.0),))


def test_sync_faults_skipped_without_modeled_sync():
    """On a non-sync regime (no modeled daemon) sync faults are counted
    skipped, not silently half-applied."""
    sc = Scenario("t", environment="gcp-intra-zone",
                  faults=(SyncOutage(0.05), SyncRestore(0.1)),
                  workload=Workload(duration=0.15, drain=0.05))
    from repro.sim.scenario import run_scenario
    res = run_scenario("nezha-vectorized", sc, tier="numpy")
    assert res.skipped_faults == 2 and res.applied_faults == 0


# ---------------------------------------------------------------------------
# the cataloged sync scenarios: paired invariant + honest coverage,
# on the event backend and both vectorized tiers
# ---------------------------------------------------------------------------
def _event_shrunk(sc: Scenario) -> Scenario:
    wl = replace(sc.workload, rate_per_client=1200.0 / sc.n_clients)
    return replace(sc, workload=wl)


@pytest.mark.slow
@pytest.mark.parametrize("sc_name", SYNC_SCENARIOS)
def test_event_backend_sync_invariant(sc_name):
    sc = _event_shrunk(get_scenario(sc_name))
    _, tr_f = run_scenario_with_trace("nezha", sc)
    assert _paired(tr_f, sc_name), f"{sc_name}: invariant silent on faults"
    assert check_sync_coverage(tr_f) == [], "reported bound was dishonest"
    _, tr_c = run_scenario_with_trace("nezha", sc.control())
    assert check_adversarial(tr_c) == [], \
        f"{sc_name}: checkers fired on the fault-free control"
    assert check_sync_coverage(tr_c) == []


@pytest.mark.parametrize("tier", ["numpy", "jit"])
@pytest.mark.parametrize("sc_name", SYNC_SCENARIOS)
def test_vectorized_sync_invariant(sc_name, tier):
    sc = get_scenario(sc_name)
    res, tr_f = run_scenario_with_trace("nezha-vectorized", sc, tier=tier)
    assert res.committed > 0
    assert _paired(tr_f, sc_name), f"{sc_name}: invariant silent on faults"
    assert check_sync_coverage(tr_f) == [], "reported bound was dishonest"
    assert not [v for v in check_trace(tr_f) if "sync" in v]
    res_c, tr_c = run_scenario_with_trace("nezha-vectorized", sc.control(),
                                          tier=tier)
    assert check_adversarial(tr_c) == [], \
        f"{sc_name}: checkers fired on the fault-free control"
    assert res_c.invariant_violations == 0


@pytest.mark.parametrize("sc_name", SYNC_SCENARIOS)
def test_sync_evidence_numpy_vs_jit_bitwise(sc_name):
    """The estimator runs INSIDE the fused program on the jit tier and as
    a staged numpy twin on the numpy tier: corrections, bounds, evidence
    rows, logs and commits must agree bit-for-bit."""
    sc = get_scenario(sc_name)
    _, tn = run_scenario_with_trace("nezha-vectorized", sc, tier="numpy")
    _, tj = run_scenario_with_trace("nezha-vectorized", sc, tier="jit")
    for col in ("t", "node", "err", "sigma"):
        np.testing.assert_array_equal(tn.sync[col], tj.sync[col],
                                      err_msg=f"sync.{col}")
    assert tn.sync["events"] == tj.sync["events"]
    for col in ("deadline", "cid", "rid", "view", "batch"):
        np.testing.assert_array_equal(tn.log[col], tj.log[col],
                                      err_msg=f"log.{col}")
    for col in ("t", "cid", "rid", "fast"):
        np.testing.assert_array_equal(tn.commits[col], tj.commits[col],
                                      err_msg=f"commits.{col}")


def test_degrade_recover_bound_recovers():
    """The sync-degrade-recover scenario's defining shape: the worst
    reported bound during the outage exceeds both the pre-outage and the
    end-of-run level (degradation is visible AND transient)."""
    _, tr = run_scenario_with_trace("nezha-vectorized",
                                    "sync-degrade-recover", tier="numpy")
    t, s = tr.sync["t"], tr.sync["sigma"]
    ticks, inv = np.unique(t, return_inverse=True)
    smax = np.zeros(ticks.size)
    np.maximum.at(smax, inv, s)
    peak_i = int(np.argmax(smax))
    assert 0 < peak_i < ticks.size - 1
    assert smax[peak_i] > 1.5 * smax[0]
    assert smax[-1] < 0.8 * smax[peak_i], "bound never recovered"


# ---------------------------------------------------------------------------
# DOM consumes the measured bound
# ---------------------------------------------------------------------------
def test_dom_margin_is_measured_under_sync_model():
    """Under the drifty regime the engine's beta-margin is computed from
    the daemon's measured per-node bounds, not the configured constant --
    and it moves as sync quality changes."""
    from repro.sim.scenario import run_scenario_on_cluster

    _, cluster = run_scenario_on_cluster(
        "nezha-vectorized", "sync-daemon-outage", tier="numpy")
    eng = cluster.engine
    assert eng.sync_active
    sig_s, sig_r = eng.clocksync.margin_sigmas()
    assert eng.bound_margin() == eng.cfg.dom.beta * (sig_s + sig_r)
    legacy = eng.cfg.dom.beta * 2.0 * eng.cfg.clock.residual_sigma
    assert eng.bound_margin() != legacy


def test_dom_margin_legacy_without_sync_model():
    from repro.sim.scenario import run_scenario_on_cluster

    _, cluster = run_scenario_on_cluster(
        "nezha-vectorized", "intra-zone", tier="numpy")
    eng = cluster.engine
    assert not eng.sync_active
    assert eng.bound_margin() == \
        eng.cfg.dom.beta * 2.0 * eng.cfg.clock.residual_sigma
