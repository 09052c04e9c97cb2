"""CPU tests of the WAN deployment (`nezha-f1-wan`, the paper's Fig 13): a
tiny WAN traffic runs through the harness, is held to the reference, and
runs its work epochs through the K-epoch scan; the float32 control and a
fault planted in the scan path come out not correct.  Small sizes only;
no chip and no topology call."""
from __future__ import annotations

import json
import shutil

import numpy as np
import pytest

from bench import harness
from bench.tests.test_bench import BENCH, ROOT

TINY_WAN = {"name": "tiny-wan", "mode": "open", "rate_per_client": 500.0,
            "warmup": 0.02, "duration": 0.2, "drain": 0.45,
            "read_ratio": 0.5, "skew": 0.5, "n_keys": 1000,
            "environment": None, "faults": []}


@pytest.fixture(scope="module")
def wan_root(tmp_path_factory):
    """A checkout with the tiny WAN traffic as one more cell on the real
    `nezha-f1-wan` configuration, made of files only."""
    root = tmp_path_factory.mktemp("wan")
    (root / "bench" / "traffic").mkdir(parents=True)
    shutil.copytree(ROOT / "bench" / "configs", root / "bench" / "configs")
    shutil.copytree(ROOT / "bench" / "metrics", root / "bench" / "metrics")
    (root / "bench" / "traffic" / "tiny-wan.json").write_text(
        json.dumps(TINY_WAN))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(
        {"name": "tiny-wan", "config": "nezha-f1-wan", "traffic": "tiny-wan",
         "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the tests share their process with others: no persistent cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "enable_cache", lambda root=None: None)
        yield root


def wan_window(root, seed: int = 13):
    """One repetition of the tiny WAN cell: the cell, the repetitions with
    the cluster's configuration kept, and the recorder's epoch counts."""
    cell = harness.load_cell("tiny-wan", root)
    with harness.Session(cell, False, root) as s:
        reps = s.window(seed, 0.0)[0]
    cfg = reps[0].cluster.cfg
    for r in reps:
        r.extract()
    return cell, reps, cfg, dict(s.recorder.counters)


@pytest.fixture(scope="module")
def sound(wan_root):
    return wan_window(wan_root)


def test_wan_run_passes_and_control_fails(sound):
    cell, reps, _, _ = sound
    assert reps[0].result.committed > 0
    check = harness.check_reps(cell, reps)
    assert check["correct"], check
    assert not harness.check_reps(cell, reps, ft=np.float32)["correct"]


def test_wan_work_epochs_run_in_the_scan(sound):
    counters = sound[3]
    assert counters["work_epochs"] > 0
    assert counters["scan_epochs"] >= 0.9 * counters["work_epochs"]


def test_wan_cluster_takes_the_environments_tuning(sound):
    cfg = sound[2]
    assert cfg.dom.clamp_d == 0.08
    assert cfg.dom.window == 200
    assert cfg.client_proxy_lan == 150e-6
    assert cfg.client_timeout == 0.4


def test_fault_planted_in_the_scan_comes_out_not_correct(wan_root,
                                                         monkeypatch):
    """Every commit time the K-epoch scan program returns moves by 1 us;
    the per-epoch step, which the planted faults of test_bench.py alter,
    is left sound."""
    from repro.core import engine

    orig = engine.ComputeTier.epoch_scan
    planted = []

    def epoch_scan(self, f, use_kcls, use_cap=False):
        scan = orig(self, f, use_kcls, use_cap)

        def altered(*args):
            out = scan(*args)
            planted.append(True)
            return (*out[:5], out[5] + 1e-6, *out[6:])
        return altered

    monkeypatch.setattr(engine.ComputeTier, "epoch_scan", epoch_scan)
    cell, reps, _, _ = wan_window(wan_root)
    assert planted
    check = harness.check_reps(cell, reps)
    assert not check["correct"]
    assert check["numbers"]["commit_time_gap_s"][0] > 1e-7
