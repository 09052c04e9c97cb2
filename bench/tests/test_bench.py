"""CPU tests of the benchmark harness: loaders, the arrival copy, metric
readers, the trace reduction, the device check, and the correctness check
against the reference -- sound runs pass; the float32 control and planted
faults fail.  Small sizes only; no chip and no topology call."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import arrivals, harness, probes, reference
from bench import trace as bench_trace

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["per_layer"]]

TINY = {"name": "tiny", "mode": "open", "rate_per_client": 500.0,
        "warmup": 0.02, "duration": 0.05, "drain": 0.03, "read_ratio": 0.5,
        "skew": 0.5, "n_keys": 1000, "environment": None, "faults": []}
TINY_CRASH = dict(TINY, name="tiny-crash", rate_per_client=1500.0,
                  duration=0.12, drain=0.08, read_ratio=0.0, skew=0.0,
                  faults=[{"kind": "Crash", "t": 0.06, "rid": 0}])


@pytest.fixture
def root(tmp_path, monkeypatch):
    """A checkout with one more traffic file and one more cell, made of
    files only: the real configurations and metric readers."""
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    shutil.copytree(ROOT / "bench" / "configs", tmp_path / "bench" / "configs")
    shutil.copytree(ROOT / "bench" / "metrics", tmp_path / "bench" / "metrics")
    for t in (TINY, TINY_CRASH):
        (tmp_path / "bench" / "traffic" / f"{t['name']}.json").write_text(
            json.dumps(t))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"] += [
        {"name": "tiny-f1", "config": "nezha-f1-intra-zone",
         "traffic": "tiny", "chips": 1, "why": "test"},
        {"name": "tiny-crash", "config": "nezha-f1-intra-zone",
         "traffic": "tiny-crash", "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    # the tests share their process with others: no persistent cache
    monkeypatch.setattr(harness, "enable_cache", lambda root=None: None)
    return tmp_path


def window(root: Path, name: str, seed: int = 11):
    """The window and the extraction of a run, without the chip check or
    the warm-up: one repetition."""
    cell = harness.load_cell(name, root)
    with harness.Session(cell, False, root) as s:
        reps = s.window(seed, 0.0)[0]
    for r in reps:
        r.extract()
    return cell, reps


# -- files found by name --------------------------------------------------
@pytest.mark.parametrize("name", CELLS)
def test_each_cell_loads_from_its_files(name):
    cell = harness.load_cell(name, ROOT)
    assert cell.chips == 1
    assert cell.config["run"]["tier"] == "jit"
    assert cell.traffic["mode"] == "open"
    scenario = harness.make_scenario(cell, 2**31 + 12345, 3)
    assert scenario.workload.rate_per_client == cell.traffic["rate_per_client"]
    assert {m["name"] for m in cell.end_to_end} == {"committed_per_s",
                                                    "setup_s"}


@pytest.mark.parametrize("name", METRICS)
def test_each_metric_has_a_reader(name):
    assert callable(harness.metric_reader(name, ROOT))


def test_config_files_are_unique_and_complete():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert {"guarantees", "assumed", "deployment", "run"} <= set(cfg)


def test_a_new_traffic_file_runs_with_no_code_edit(root):
    cell, reps = window(root, "tiny-f1")
    check = harness.check_reps(cell, reps)
    assert check["correct"], check
    assert reps[0].result.committed > 0


def test_unknown_cell_is_an_error(root):
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", root)


# -- the arrival copy ------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 4_000_000_123])
def test_arrival_copy_matches_the_workload_driver(seed):
    from repro.core.registry import make_cluster
    from repro.sim.scenario import build_config
    from repro.sim.workload import WorkloadDriver

    cell = harness.load_cell(CELLS[0], ROOT)
    cell = harness.Cell(cell.name, 1, cell.config, TINY, [], [])
    sc = harness.make_scenario(cell, seed, 0)
    cluster = make_cluster("nezha-vectorized", build_config(
        "nezha-vectorized", sc))
    WorkloadDriver(sc.workload).inject_open_loop(cluster)
    stream = arrivals.open_loop_stream(TINY, sc.n_clients, sc.workload.seed)
    pending = cluster._pending._buf[: len(cluster._pending)]
    assert cluster.summary()["n_requests"] == stream.n
    np.testing.assert_array_equal(pending["t"], stream.t)
    np.testing.assert_array_equal(pending["cid"], stream.cid)
    np.testing.assert_array_equal(pending["rid"], stream.rid)
    np.testing.assert_array_equal(pending["kcls"], stream.kcls)


def test_repetition_seeds_differ_and_repeat():
    a = [harness.rep_seeds(2**31 + 9, i) for i in range(4)]
    assert a == [harness.rep_seeds(2**31 + 9, i) for i in range(4)]
    assert len(set(a)) == 4
    assert harness.rep_seeds(5, harness.WARMUP_REP) not in a


# -- metric readers on synthetic spans -------------------------------------
def _ctx(**kw):
    spans = {"inject": [(0.0, 1.0)], "sample": [(1.0, 1.5), (1.2, 2.0)],
             "deliver": [(2.0, 2.5)], "log": [(2.5, 3.0)],
             "compile": [(3.0, 5.0), (4.0, 4.5)], "sync": [(9.0, 9.5)]}
    base = dict(window=(0.0, 10.0), spans=spans,
                counters={"scan_epochs": 4, "work_epochs": 50},
                device={"busy_s": 0.25, "window_s": 5.0})
    base.update(kw)
    return harness.Context(**base)


@pytest.mark.parametrize("name,expected", [
    ("inject_share", 10.0), ("sample_share", 10.0),
    ("deliver_log_share", 10.0), ("compile_share", 20.0),
    ("sync_share", 5.0), ("scan_epoch_share", 8.0),
    ("device_idle_share", 95.0)])
def test_metric_reader_on_synthetic_spans(name, expected):
    assert harness.metric_reader(name, ROOT)(_ctx()) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["sync_share", "scan_epoch_share",
                                  "device_idle_share", "inject_share"])
def test_metric_reader_with_nothing_to_read_returns_none(name):
    ctx = _ctx(spans={}, counters={}, device=None)
    assert harness.metric_reader(name, ROOT)(ctx) is None


def test_union_length_clips_and_merges():
    assert probes.union_length([(0, 2), (1, 3), (5, 9)], 1.0, 6.0) == 3.0


# -- trace reduction -------------------------------------------------------
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 12000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 30000000 duration_ps: 9000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 14000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "sort.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench_repetition" } }
}
"""


def test_trace_reduction_on_a_small_trace():
    import jax

    data = jax.profiler.ProfileData.from_text_proto(XSPACE)
    # host spans in wall seconds; the marker started at wall 100.0 s
    spans = {"sample": [(100.0, 100.000008)], "log": [(100.000010, 100.00002)]}
    red = bench_trace.reduce(data, "bench_repetition", (100.0, 100.00002),
                             spans)
    assert red["window_s"] == pytest.approx(20e-6)
    # ops [0,6) and [12,14) us inside the 20 us window; [30,39) is outside
    assert red["busy_s"] == pytest.approx(8e-6)
    assert red["device_ops"][0][0] == "sort.2"
    idle = dict(red["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(12e-6, abs=3e-6)
    assert idle["log"] > idle.get("other", 0.0)
    assert "sample" in idle


def test_trace_reduction_without_marker_or_device_is_none():
    import jax

    data = jax.profiler.ProfileData.from_text_proto(
        XSPACE.replace("bench_repetition", "something_else"))
    assert bench_trace.reduce(data, "bench_repetition", (0.0, 1.0), {}) is None


# -- peaks -----------------------------------------------------------------
def test_peaks_of_v5e_and_unknown_device():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")


# -- the device check ------------------------------------------------------
def test_run_exits_nonzero_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs 1 TPU" in out.stderr
    assert out.stdout.strip() == ""


# -- the reference ---------------------------------------------------------
def test_early_buffer_admits_by_released_watermark():
    d = np.array([5.0, 3.0, 4.0, 9.0])
    a = np.array([[1.0], [4.5], [3.5], [np.inf]])
    # message 0 (d=5) waits; 2 arrives at 3.5 < 5 with d=4 > nothing released
    # yet: admitted; 1 arrives at 4.5, deadline 4 released before: 3 <= 4
    assert reference.early_buffer(d, a)[:, 0].tolist() == [True, False, True,
                                                            False]


def test_sound_run_passes_and_control_fails(root):
    cell, reps = window(root, "tiny-f1")
    assert harness.check_reps(cell, reps)["correct"]
    control = harness.check_reps(cell, reps, ft=np.float32)
    nums = {k: v for k, (v, _) in control["numbers"].items()}
    assert not control["correct"]
    assert nums["deadline_gap_s"] > 10 * harness.limits()["deadline_gap_s"]


def test_crash_run_with_merge_log_passes(root):
    cell, reps = window(root, "tiny-crash")
    assert any(k == "view_change" for k, _, _ in reps[0].records)
    assert reps[0].result.recovered_entries > 0
    check = harness.check_reps(cell, reps)
    assert check["correct"], check


def _broken(monkeypatch, fault):
    from repro.core import engine

    if fault == "state_unchanged":
        # the bound's OWD pool never takes the epoch's samples
        monkeypatch.setattr(engine.DomEngine, "update_bound",
                            lambda self, owd_new: self.cfg.dom.clamp_d)
        return
    orig = engine.FusedEpochStage.run

    def run(self, s, eng):
        orig(self, s, eng)
        for key in ("committed", "fast", "admitted", "commit_time"):
            setattr(s, key, np.array(getattr(s, key)))
        if fault == "half_batch":
            half = s.t.size // 2
            s.committed[half:] = False
            s.fast[half:] = False
            s.admitted[half:] = False
            s.commit_time[half:] = np.inf
        elif fault == "answer_altered":
            s.commit_time[0] += 1e-6
    monkeypatch.setattr(engine.FusedEpochStage, "run", run)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_planted_fault_comes_out_not_correct(root, monkeypatch, fault):
    _broken(monkeypatch, fault)
    cell, reps = window(root, "tiny-f1")
    assert not harness.check_reps(cell, reps)["correct"]
