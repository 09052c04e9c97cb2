"""The fused epoch programs are shared by every tier of a class in one
process: a second cluster traces nothing the first ran, and its results are
those of a cluster that traced its own programs."""
from __future__ import annotations

from dataclasses import replace

import numpy as np
import pytest

from repro import obs
from repro.core.engine import JitTier, PallasTier, clear_epoch_programs
from repro.core.sanitizer import SanitizerTier
from repro.sim.scenario import Scenario, run_scenario_on_cluster
from repro.sim.trace import CommitTrace
from repro.sim.workload import Workload

STEADY = Scenario("registry-steady",
                  workload=Workload(mode="open", rate_per_client=1500.0,
                                    duration=0.12, warmup=0.02, drain=0.06,
                                    seed=5),
                  overrides={"n_proxies": 2, "epochs_per_dispatch": 16},
                  n_clients=4, seed=31)


def run_recorded(scenario=STEADY):
    with obs.recording() as rec:
        result, cluster = run_scenario_on_cluster("nezha-vectorized",
                                                  scenario, tier="jit")
    return result, CommitTrace.from_cluster(cluster), rec["counters"]


def traced(counters) -> dict:
    return {k: v for k, v in counters.items()
            if k.startswith("programs_traced.")}


def assert_same_run(a, b):
    (res_a, tr_a, _), (res_b, tr_b, _) = a, b
    assert res_a.as_dict() == res_b.as_dict()
    for col, arr in tr_a.log.items():
        np.testing.assert_array_equal(arr, tr_b.log[col],
                                      err_msg=f"log.{col}")
    for col, arr in tr_a.commits.items():
        np.testing.assert_array_equal(arr, tr_b.commits[col],
                                      err_msg=f"commits.{col}")


def test_second_cluster_traces_nothing_and_matches_a_fresh_trace():
    clear_epoch_programs()
    first = run_recorded()
    second = run_recorded()
    clear_epoch_programs()
    fresh = run_recorded()
    assert traced(first[2]) and traced(fresh[2])
    assert first[2]["programs_shared.miss"] > 0
    assert traced(second[2]) == {}
    assert second[2]["programs_shared.hit"] > 0
    assert "programs_shared.miss" not in second[2]
    assert second[2]["epochs.scan"] > 0          # both programs dispatched
    assert_same_run(second, fresh)
    assert_same_run(first, second)


def test_shared_programs_count_once_per_tier_and_key():
    clear_epoch_programs()
    a, b = JitTier(), JitTier()
    with obs.recording() as rec:
        for _ in range(3):
            a.epoch_step(1, use_kcls=False)
        b.epoch_step(1, use_kcls=False)
        b.epoch_scan(1, use_kcls=False)
    assert rec["counters"] == {"programs_shared.miss": 2,
                               "programs_shared.hit": 1}


@pytest.mark.parametrize("kind", ["epoch_step", "epoch_scan"])
def test_one_program_per_specialization(kind):
    def prog(tier, f=1, use_kcls=False, use_cap=False):
        return getattr(tier, kind)(f, use_kcls, use_cap=use_cap)

    base = prog(JitTier())
    assert prog(JitTier()) is base
    variants = [prog(JitTier(), f=2), prog(JitTier(), use_kcls=True),
                prog(JitTier(), use_cap=True),
                prog(JitTier(), use_kcls=True, use_cap=True)]
    assert len({id(p) for p in [base, *variants]}) == 5
    other = "epoch_scan" if kind == "epoch_step" else "epoch_step"
    assert getattr(JitTier(), other)(1, False) is not base


@pytest.mark.pallas
@pytest.mark.parametrize("kind", ["epoch_step", "epoch_scan"])
def test_pallas_tier_has_programs_of_its_own(kind):
    from repro.kernels.ops import interpret_mode

    jit = getattr(JitTier(), kind)(1, False)
    tier = PallasTier()
    pallas = getattr(tier, kind)(1, False)
    assert pallas is not jit
    assert getattr(tier, kind)(1, False) is pallas
    # shared on the TPU; interpreted on the CPU, kept per tier
    assert (getattr(PallasTier(), kind)(1, False) is pallas) \
        == (not interpret_mode())


@pytest.mark.parametrize("kind", ["epoch_step", "epoch_scan"])
def test_sanitized_tier_shares_the_bare_tiers_program(kind):
    bare = getattr(JitTier(), kind)(1, True)
    assert getattr(SanitizerTier("jit"), kind)(1, True) is bare
    assert getattr(SanitizerTier(JitTier()), kind)(1, True) is bare


def test_clear_builds_new_programs():
    before = JitTier().epoch_step(1, use_kcls=False)
    clear_epoch_programs()
    after = JitTier().epoch_step(1, use_kcls=False)
    assert after is not before
    assert JitTier().epoch_step(1, use_kcls=False) is after


def test_a_different_scenario_traces_only_its_new_shapes():
    """A second scenario in the process traces a program only for a batch
    bucket the first never ran (here: a heavier load, larger epochs)."""
    clear_epoch_programs()
    run_recorded()
    heavier = replace(STEADY, workload=replace(STEADY.workload,
                                               rate_per_client=6000.0))
    _, _, counters = run_recorded(heavier)
    assert "programs_shared.miss" not in counters
    again = run_recorded(heavier)[2]
    assert traced(again) == {}
    assert sum(traced(counters).values()) > 0
