"""CPU tests of what a metric reader can see beyond the benchmark's own
wrappers: the program's spans and counters (recorded in traced windows
only), every device operation's time, the traced repetition's epoch shapes
and the device's peaks; the DOM kernels' HBM work (bench/roofline.py); and
a reader added as a new file with no other edit."""
from __future__ import annotations

import json

import pytest

from bench import harness, roofline
from bench.tests.test_bench import ROOT, root  # noqa: F401  (fixture)

V5E = harness.peaks("TPU v5 lite")


def _cell(root):
    return harness.load_cell("tiny-f1", root)


# -- recording: off untraced, on traced ----------------------------------
def test_untraced_window_leaves_program_recording_off(root, monkeypatch):
    from repro import obs

    seen = []
    run = harness.run_repetition

    def spy(cell, scenario):
        seen.append(obs._record)
        return run(cell, scenario)

    monkeypatch.setattr(harness, "run_repetition", spy)
    with harness.Session(_cell(root), False, root) as s:
        s.window(3, 0.0)
    assert seen == [None]
    assert s.program is None


def test_traced_window_brings_program_spans_and_counters(root):
    from repro import obs
    from repro.core.engine import clear_epoch_programs

    clear_epoch_programs()          # so that this window traces its programs
    with harness.Session(_cell(root), True, root) as s:
        reps, w0, w1, _, profile = s.window(5, 0.0)
    assert obs._record is None
    ctx = harness.traced_context(s, reps, w0, w1, profile)
    assert ctx.reps == len(reps) == 1
    assert ctx.program_spans["loop.pending"]
    assert ctx.program_spans["fused.pull"]
    traced = (ctx.program_counters.get("programs_traced.step", 0)
              + ctx.program_counters.get("programs_traced.scan", 0))
    assert traced > 0
    assert ctx.program_counters["scenarios"] == 1
    # the benchmark's own spans are kept apart, as before
    assert "loop.pending" not in ctx.spans and ctx.spans["inject"]
    assert ctx.epochs and all(e["R"] == 3 and e["n"] > 0 for e in ctx.epochs)
    assert sum(e["n"] for e in ctx.epochs) == sum(
        rec["t"].size for k, rec, _ in reps[0].records if k == "epoch")
    for name in ("epoch_host_share", "fused_pull_share"):
        value = harness.metric_reader(name, ROOT)(ctx)
        assert 0.0 < value < 100.0
    assert harness.metric_reader("program_traces_per_rep", ROOT)(ctx) \
        == traced


# -- the three readers on a synthetic Context ----------------------------
def _ctx(**kw):
    base = dict(window=(0.0, 10.0), spans={}, counters={}, device=None,
                program_spans={"loop.pending": [(0.0, 1.0), (0.5, 1.5)],
                               "loop.absorb": [(2.0, 2.5)],
                               "loop.faults": [(3.0, 3.5)],
                               "fused.pull": [(4.0, 4.25), (5.0, 5.25)]},
                program_counters={"scenarios": 4, "programs_traced.step": 3,
                                  "programs_traced.scan": 1},
                reps=4)
    base.update(kw)
    return harness.Context(**base)


@pytest.mark.parametrize("name,expected", [
    ("epoch_host_share", 25.0), ("fused_pull_share", 5.0),
    ("program_traces_per_rep", 1.0)])
def test_program_reader_on_a_synthetic_record(name, expected):
    assert harness.metric_reader(name, ROOT)(_ctx()) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["epoch_host_share", "fused_pull_share",
                                  "program_traces_per_rep"])
def test_program_reader_without_a_program_record_returns_none(name):
    ctx = harness.Context(window=(0.0, 10.0), spans={"inject": [(0, 1)]},
                          counters={"work_epochs": 3}, device=None)
    assert harness.metric_reader(name, ROOT)(ctx) is None


def test_program_traces_read_zero_when_nothing_was_traced():
    ctx = _ctx(program_counters={"scenarios": 4, "epochs.step": 60})
    assert harness.metric_reader("program_traces_per_rep", ROOT)(ctx) == 0.0


# -- the DOM kernels' HBM work -------------------------------------------
@pytest.mark.parametrize("n,R,admit,release", [
    (1, 1, 8 * 2 + 1, 8 + 1 + 4),
    (1000, 3, 8 * (1000 + 3000) + 3000, 13 * 1000),
    (2048, 5, 8 * (2048 + 10240) + 10240, 13 * 2048)])
def test_dom_kernel_bytes_at_known_shapes(n, R, admit, release):
    assert roofline.dom_admit_bytes(n, R) == admit
    assert roofline.dom_release_bytes(n) == release


def _device(ops):
    return {"busy_s": 1.0, "window_s": 2.0, "ops": ops,
            "kind": "TPU v5 lite", "peaks": V5E}


def test_hbm_share_of_a_named_kernel_op():
    # op names as the v5e's trace gives them, layouts shortened
    ops = {"%dom_admit_pallas.1 = s32[3,32,128]{2,1,0} custom-call(s32[3,32,"
           "128]{2,1,0} %copy_bitcast_fusion.5), custom_call_target="
           "\"tpu_custom_call\"": 1.5e-3,
           "%dom_admit_pallas.12 = s32[3,32,128]{2,1,0} custom-call(s32[3,32,"
           "128]{2,1,0} %copy_bitcast_fusion.12)": 0.5e-3,
           # reads the kernel's output: not the kernel's time
           "%copy.9 = s32[3,32,128]{2,1,0} copy(s32[3,32,128]{2,1,0} "
           "%dom_admit_pallas.1)": 5.0,
           "%fusion.7 = f32[2048]{0} fusion(f32[2048]{0} %p)": 5.0}
    ctx = _ctx(device=_device(ops))
    seconds = roofline.kernel_seconds(ctx, "dom_admit_pallas")
    assert seconds == pytest.approx(2e-3)
    # 819e9 B/s * 2 ms = 1.638e9 bytes at 100%; a tenth of it reads 10%
    assert roofline.hbm_share(1.638e8, seconds, V5E) == pytest.approx(10.0)
    assert roofline.kernel_seconds(ctx, "dom_release_pallas") is None
    assert roofline.kernel_seconds(_ctx(), "dom_admit_pallas") is None


# -- a new reader as a new file --------------------------------------------
READER = '''
from bench import roofline


def read(ctx):
    seconds = roofline.kernel_seconds(ctx, "dom_admit_pallas")
    if seconds is None or not ctx.epochs:
        return None
    work = sum(roofline.dom_admit_bytes(e["n"], e["R"]) for e in ctx.epochs)
    return roofline.hbm_share(work, seconds, ctx.device["peaks"])
'''


def test_a_new_reader_file_reads_ops_epochs_and_peaks(root):
    (root / "bench" / "metrics" / "probe_roofline.py").write_text(READER)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append(
        {"name": "probe_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels",
         "moves": "committed_per_s", "workloads": ["tiny-f1"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("tiny-f1", root)
    ops = {"%dom_admit_pallas.1 = s32[3,32,128] custom-call()": 4e-6}
    ctx = _ctx(device=_device(ops), epochs=[{"n": 1000, "R": 3}] * 2)
    got = harness.per_layer_metrics(cell, ctx, root)["probe_roofline"]
    want = 100.0 * 2 * (8 * 4000 + 3000) / 4e-6 / 819e9
    assert got == {"value": pytest.approx(want), "unit": "%"}
    # a cell without the kernel's op reads nothing, not 0
    ctx = _ctx(device=_device({}), epochs=[{"n": 1000, "R": 3}])
    assert "probe_roofline" not in harness.per_layer_metrics(cell, ctx, root)


# -- the trace: every op uncut, idle time by the innermost span -------------
def test_trace_keeps_every_op_and_paints_idle_by_the_innermost_span():
    import jax

    from bench import trace as bench_trace
    from bench.tests.test_bench import XSPACE

    data = jax.profiler.ProfileData.from_text_proto(XSPACE)
    # the benchmark's `fused` wraps the whole repetition; the program's
    # `fused.pull` lies inside it, over 2 of the 12 idle microseconds
    spans = {"fused": [(100.0, 100.00002)],
             "fused.pull": [(100.000007, 100.000009)]}
    red = bench_trace.reduce(data, "bench_repetition", (100.0, 100.00002),
                             spans)
    assert red["ops"] == pytest.approx({"fusion.1": 4e-6, "sort.2": 6e-6})
    idle = dict(red["idle_gaps"])
    assert idle["fused.pull"] == pytest.approx(2e-6, abs=1.5e-6)
    assert idle["fused"] > idle["fused.pull"]
    assert "other" not in idle
