"""The engine's spans and counters (`repro.obs`): transparent to the
results, complete where a scenario exercises them, and consistent with the
engine's own counts and with JAX's trace events."""
from __future__ import annotations

from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.core.engine import PENDING_DTYPE
from repro.sim.scenario import (Crash, Scenario,
                                make_scenario_cluster,
                                run_scenario_on_cluster)
from repro.sim.trace import CommitTrace
from repro.sim.workload import Workload

SHORT = Workload(mode="open", rate_per_client=1500.0, duration=0.12,
                 warmup=0.02, drain=0.06, seed=5)
SCAN = {"n_proxies": 2, "epochs_per_dispatch": 16}
STEADY = Scenario("obs-steady", workload=SHORT, overrides=SCAN, n_clients=4,
                  seed=7)
CRASH = Scenario("obs-crash", faults=(Crash(0.06, rid=0),),
                 workload=replace(SHORT, read_ratio=0.0, skew=0.0,
                                  drain=0.1),
                 overrides=SCAN, n_clients=4, seed=8)
SYNC = Scenario("obs-sync", environment="drifty-clocks", workload=SHORT,
                overrides=SCAN, n_clients=4, seed=9)
CLOSED = Scenario("obs-closed",
                  workload=Workload(mode="closed", duration=0.05, drain=0.03,
                                    seed=6),
                  overrides=SCAN, n_clients=3, seed=10)

LOOP_SPANS = {"scenario.build", "inject", "scenario.result", "loop.faults",
              "loop.pending", "loop.absorb", "sample", "deliver", "log",
              "fused", "fused.pack", "fused.dispatch", "fused.pull",
              "fused.unpack"}
FENCES = {"epochs_per_dispatch", "tier", "callbacks", "clock_fault",
          "pair_fault", "stamp_bias", "sync", "prestamped", "horizon",
          "fault_event", "retry_closure", "nothing_pending"}


def run(scenario, tier="jit"):
    result, cluster = run_scenario_on_cluster("nezha-vectorized", scenario,
                                              tier=tier)
    return result, cluster, CommitTrace.from_cluster(cluster)


@pytest.fixture(scope="module")
def recorded():
    """Each scenario once with recording on, by name."""
    out = {}
    for sc in (STEADY, CRASH, SYNC, CLOSED):
        with obs.recording() as rec:
            result, cluster, trace = run(sc)
        out[sc.name] = (rec, result, cluster, trace)
    return out


# -- off ---------------------------------------------------------------------
def test_off_records_nothing_and_shares_one_span():
    assert obs._record is None
    assert obs.span("a") is obs.span("b")
    with obs.span("a"):
        obs.count("c")
        obs.event("e", x=1)
    with obs.recording() as rec:
        pass
    assert rec == {"spans": {}, "counters": {}, "events": []}
    assert obs._record is None


def test_recording_nests_and_restores():
    with obs.recording() as outer:
        obs.count("a")
        with obs.recording() as inner:
            obs.count("b")
            with obs.span("s"):
                pass
        obs.count("a")
    assert outer["counters"] == {"a": 2}
    assert inner["counters"] == {"b": 1}
    (t0, t1), = inner["spans"]["s"]
    assert t0 <= t1


# -- transparent -------------------------------------------------------------
def _same(a, b) -> bool:
    if isinstance(a, float) and isinstance(b, float):
        return a == b or (np.isnan(a) and np.isnan(b))
    return a == b


@pytest.mark.parametrize("name", [STEADY.name, CRASH.name])
def test_recording_leaves_results_bit_identical(recorded, name):
    sc = {s.name: s for s in (STEADY, CRASH)}[name]
    _, on, _, trace_on = recorded[name]
    off, _, trace_off = run(sc)
    a, b = on.as_dict(), off.as_dict()
    assert a.keys() == b.keys()
    assert all(_same(a[k], b[k]) for k in a), (a, b)
    for cols_on, cols_off in ((trace_on.log, trace_off.log),
                              (trace_on.commits, trace_off.commits)):
        assert cols_on.keys() == cols_off.keys()
        for k in cols_on:
            np.testing.assert_array_equal(cols_on[k], cols_off[k])


# -- complete ----------------------------------------------------------------
@pytest.mark.parametrize("name,extra", [
    (STEADY.name, set()),
    (CRASH.name, {"recovery"}),
    (SYNC.name, {"sync"}),
])
def test_every_span_appears_where_exercised(recorded, name, extra):
    rec = recorded[name][0]
    assert LOOP_SPANS | extra <= set(rec["spans"]), \
        sorted(LOOP_SPANS | extra - set(rec["spans"]))
    for name_, iv in rec["spans"].items():
        assert all(t0 <= t1 for t0, t1 in iv), name_


def test_numpy_tier_records_the_staged_stages():
    with obs.recording() as rec:
        run(STEADY, tier="numpy")
    assert {"sample", "stamp", "dom", "commit", "deliver", "log"} \
        <= set(rec["spans"])
    assert "fused" not in rec["spans"]
    assert rec["counters"]["scan_fence.tier"] \
        == rec["counters"]["epochs.step"]


def test_step_path_fused_spans_nest_in_fused(recorded):
    spans = recorded[STEADY.name][0]["spans"]
    fused = sorted(spans["fused"])
    n_children = 0
    for child in ("fused.pack", "fused.dispatch", "fused.pull",
                  "fused.unpack"):
        for a, b in spans[child]:
            # a child inside no `fused` span ran on the scan path
            inside = [f for f in fused if f[0] <= a and b <= f[1]]
            n_children += bool(inside)
    # four children per step epoch
    assert n_children == 4 * len(fused)


def test_scan_unpack_spans_do_not_nest_in_deliver_or_log(recorded):
    spans = recorded[STEADY.name][0]["spans"]
    outer = spans["deliver"] + spans["log"]
    for a, b in spans["fused.unpack"]:
        assert not any(o0 < a and b < o1 for o0, o1 in outer)


# -- counters ----------------------------------------------------------------
@pytest.mark.parametrize("name", [STEADY.name, CRASH.name, SYNC.name,
                                  CLOSED.name])
def test_epoch_counters_sum_to_the_clusters_epochs(recorded, name):
    rec, result, cluster, _ = recorded[name]
    c = rec["counters"]
    total = sum(c.get(k, 0) for k in ("epochs.step", "epochs.scan",
                                      "epochs.stalled"))
    assert total == cluster._epochs == result.epochs
    fences = {k: v for k, v in c.items() if k.startswith("scan_fence.")}
    assert {k.split(".", 1)[1] for k in fences} <= FENCES
    assert sum(fences.values()) == c.get("epochs.step", 0)
    assert c["scenarios"] == 1


def test_steady_fences_are_retry_closure_and_the_drain(recorded):
    c = recorded[STEADY.name][0]["counters"]
    assert c["epochs.scan"] >= 4
    assert c["scan_fence.retry_closure"] > 0
    assert c.get("scan_fence.nothing_pending", 0) > 0
    assert c.get("epochs.stalled", 0) == 0


def test_crash_counts_stalled_epochs_and_fault_fences(recorded):
    c = recorded[CRASH.name][0]["counters"]
    assert c["epochs.stalled"] > 0
    assert c["scan_fence.fault_event"] > 0


@pytest.mark.parametrize("name,reason", [(SYNC.name, "sync"),
                                         (CLOSED.name, "callbacks")])
def test_a_fence_that_holds_all_run_blocks_every_step(recorded, name,
                                                      reason):
    c = recorded[name][0]["counters"]
    assert c["epochs.step"] > 0
    assert c[f"scan_fence.{reason}"] == c["epochs.step"]
    assert "epochs.scan" not in c


# -- the K-epoch scan windows ------------------------------------------------
def _spy_scan_windows(monkeypatch) -> list:
    """The K of each `_run_scan_window` call, in order."""
    from repro.core.vectorized_cluster import VectorizedNezhaCluster

    ks = []
    orig = VectorizedNezhaCluster._run_scan_window

    def spy(self, k):
        ks.append(k)
        return orig(self, k)

    monkeypatch.setattr(VectorizedNezhaCluster, "_run_scan_window", spy)
    return ks


def test_scan_windows_are_spanned_and_counted(monkeypatch):
    ks = _spy_scan_windows(monkeypatch)
    with obs.recording() as rec:
        run(replace(STEADY, seed=23))
    c, spans = rec["counters"], rec["spans"]
    assert ks
    assert c["scan.windows"] == len(ks) == len(spans["loop.scan"])
    assert sum(ks) == c["epochs.scan"]
    # a dispatch outside every `fused` step span ran on the scan path, and
    # lies inside its window's span
    fused = spans["fused"]
    scan_dispatch = [(a, b) for a, b in spans["fused.dispatch"]
                     if not any(f0 <= a and b <= f1 for f0, f1 in fused)]
    assert scan_dispatch
    for a, b in scan_dispatch:
        assert any(s0 <= a and b <= s1 for s0, s1 in spans["loop.scan"])


def test_each_dispatch_opens_one_pull(monkeypatch):
    """`epochs_per_pull` counts pulls as `fused.pull` spans: one per
    `run_epoch` step and one per scan window."""
    from repro.core.engine import DomEngine

    ks = _spy_scan_windows(monkeypatch)
    steps = []
    orig = DomEngine.run_epoch

    def spy(self, *args, **kw):
        steps.append(True)
        return orig(self, *args, **kw)

    monkeypatch.setattr(DomEngine, "run_epoch", spy)
    with obs.recording() as rec:
        run(replace(STEADY, seed=23))
    assert ks and steps
    assert len(rec["spans"]["fused.pull"]) == len(ks) + len(steps)


def test_scan_window_span_and_counter_are_inert_when_off(monkeypatch):
    ks = _spy_scan_windows(monkeypatch)
    seen = []
    span, count = obs.span, obs.count

    def spy_span(name):
        out = span(name)
        seen.append((name, out))
        return out

    def spy_count(name, n=1):
        seen.append((name, obs._record))
        count(name, n)

    monkeypatch.setattr(obs, "span", spy_span)
    monkeypatch.setattr(obs, "count", spy_count)
    run(replace(STEADY, seed=23))
    assert ks and obs._record is None
    scan = [out for name, out in seen if name == "loop.scan"]
    windows = [out for name, out in seen if name == "scan.windows"]
    assert len(scan) == len(windows) == len(ks)
    assert all(out is obs._OFF for out in scan)
    assert all(out is None for out in windows)


def _reader(name):
    from bench import harness

    return harness.metric_reader(name, Path(__file__).resolve().parents[1])


def _ctx(program_spans=None, program_counters=None, counters=None):
    from bench import harness

    return harness.Context(window=(0.0, 10.0), spans={},
                           counters=counters or {}, device=None,
                           program_spans=program_spans or {},
                           program_counters=program_counters or {})


def _pulls(n):
    return {"fused.pull": [(0.1 * i, 0.1 * i + 0.05) for i in range(n)]}


@pytest.mark.parametrize("work,pulls,expected", [
    (64, 6, 64 / 6),        # every work epoch in six K=16 windows
    (50, 50, 1.0),          # every epoch stepped
    (64, 4, 16.0),
    (70, 10, 7.0)])         # six windows and four stepped work epochs
def test_epochs_per_pull_on_a_synthetic_record(work, pulls, expected):
    ctx = _ctx(program_spans=_pulls(pulls),
               counters={"work_epochs": work, "scan_epochs": 0})
    assert _reader("epochs_per_pull")(ctx) == pytest.approx(expected)


def test_scan_window_share_on_a_synthetic_record():
    spans = {"loop.scan": [(0.0, 4.0), (3.0, 5.0), (9.0, 11.0)],
             "fused.pull": [(1.0, 2.0)]}
    assert _reader("scan_window_share")(_ctx(program_spans=spans)) \
        == pytest.approx(60.0)


@pytest.mark.parametrize("name,ctx", [
    ("scan_window_share", _ctx()),
    ("scan_window_share", _ctx(program_spans={"loop.pending": [(0, 1)]})),
    ("epochs_per_pull", _ctx()),
    # work epochs counted by the benchmark, but no program record
    ("epochs_per_pull", _ctx(counters={"work_epochs": 64,
                                       "scan_epochs": 64}))])
def test_scan_readers_without_a_record_return_none(name, ctx):
    assert _reader(name)(ctx) is None


# -- the fence of one window, constructed ----------------------------------
def _cluster(tier="jit", environment="gcp-intra-zone", **over):
    sc = Scenario("obs-unit", environment=environment, workload=SHORT,
                  overrides={**SCAN, **over}, n_clients=2)
    return make_scenario_cluster("nezha-vectorized", sc, tier=tier)[0]


BUILD = {"tier": {"tier": "numpy"},
         "epochs_per_dispatch": {"epochs_per_dispatch": 1},
         "sync": {"environment": "drifty-clocks"}}


def _fence_case(case):
    c = _cluster(**BUILD.get(case, {}))
    horizon = 1.0
    if case == "callbacks":
        c.on_commit = lambda cid, rid: None
    elif case == "clock_fault":
        c.engine.set_clock_fault("replica", 0, 1e-5, 1e-6)
    elif case == "pair_fault":
        c.engine.set_partition([2])
    elif case == "stamp_bias":
        c.engine.set_stamp_bias(0, 1e-5)
    elif case == "prestamped":
        row = np.zeros(1, PENDING_DTYPE)
        row["t"], row["dl"] = 0.005, 0.01
        c._pending.extend(row)
    elif case == "horizon":
        horizon = 0.03
    elif case == "fault_event":
        c.crash_at(0.02, 1)
    elif case == "retry_closure":
        c.submit_at(0.005, 0)
    elif case == "scan":
        c.submit_at(0.035, 0)
    return c._scan_window(horizon)


@pytest.mark.parametrize("case", sorted(FENCES))
def test_each_fence_names_the_guard_that_refused(case):
    assert _fence_case(case) == (0, case)


def test_the_first_guard_in_order_is_named():
    c = _cluster(environment="drifty-clocks")
    c.on_commit = lambda cid, rid: None
    c.submit_at(0.005, 0)
    assert c._scan_window(1.0) == (0, "callbacks")
    c.on_commit = None
    assert c._scan_window(1.0) == (0, "sync")


def test_an_open_window_names_no_fence():
    # one request due inside a 4-epoch window, its retry outside it
    assert _fence_case("scan") == (4, None)


# -- programs traced ---------------------------------------------------------
def test_programs_traced_match_jax_trace_events():
    import jax
    from jax._src.dispatch import JAXPR_TRACE_EVENT

    from repro.core.engine import clear_epoch_programs

    # a first trace of each program, whatever ran earlier in this process
    clear_epoch_programs()
    seen = []

    def on_span(event, start, end, **kw):
        if event == JAXPR_TRACE_EVENT \
                and kw.get("fun_name") in ("step", "scan_fn"):
            seen.append(kw["fun_name"])

    jax.monitoring.register_event_time_span_listener(on_span)
    try:
        with obs.recording() as rec:
            run(replace(STEADY, seed=21))
    finally:
        jax.monitoring.unregister_event_time_span_listener(on_span)
    c = rec["counters"]
    assert c["programs_traced.step"] == seen.count("step") > 0
    assert c["programs_traced.scan"] == seen.count("scan_fn") > 0
    events = [e for e in rec["events"] if e[0].startswith("programs_traced")]
    assert len(events) == len(seen)
    for name, t, fields in events:
        assert fields["n_pad"] & (fields["n_pad"] - 1) == 0     # pow2 bucket
        assert ("K" in fields) == name.endswith(".scan")
    # each dispatch that traced holds its trace event
    dispatch = rec["spans"]["fused.dispatch"]
    assert all(any(a <= t <= b for a, b in dispatch) for _, t, _ in events)


# -- on the profiler's clock -------------------------------------------------
def test_span_names_reach_the_trace_host_plane(tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    sc = replace(STEADY, seed=22)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("obs_marker"):
            with obs.recording() as rec:
                run(sc)
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    events = [ev for plane in data.planes if plane.name.startswith("/host")
              for line in plane.lines for ev in line.events]
    mark = next(ev for ev in events if ev.name == "obs_marker")
    inside = {ev.name for ev in events
              if mark.start_ns <= ev.start_ns and ev.end_ns <= mark.end_ns}
    assert set(rec["spans"]) <= inside, sorted(set(rec["spans"]) - inside)


# -- device operations by part -----------------------------------------------
SCOPES = ("pool_fold", "bound", "fetch", "stamp", "admit", "order", "commit",
          "sync_estimate")


def test_epoch_program_parts_name_their_device_ops():
    import jax

    from repro.analysis.lint.trace_safety import _fused_step_args
    from repro.core.engine import JitTier

    step = JitTier().epoch_step(1, use_kcls=True)
    with jax.enable_x64(True):
        hlo = step.lower(**_fused_step_args(8, 3, sync=True)) \
            .compile().as_text()
    names = set()
    for line in hlo.splitlines():
        if 'op_name="' in line:
            names.update(line.split('op_name="', 1)[1].split('"', 1)[0]
                         .split("/"))
    assert set(SCOPES) <= names, sorted(set(SCOPES) - names)
