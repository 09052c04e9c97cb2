#!/usr/bin/env python3
"""Smoke run of the vectorized Nezha engine on one TPU chip.

    python chip_smoke.py

Drives `run_scenario` through the fused device tiers at a deployment's
rate and holds every run to the host-only numpy tier: the same committed
log (packed uids in log order), the same result counts, and green trace
invariants.  One process, no subprocess; JAX runs on the TPU or the
script exits non-zero before printing any result.

Phases:
  (a) device check: platform, kind and count of JAX's devices;
  (b) jit tier, the cataloged intra-zone deployment (f=1, 2 proxies, 10
      open-loop clients) at 20K req/s per client -- 200K req/s, the top of
      the Fig 14/15 rate sweep -- for 0.5 s simulated, up to 16 epochs
      per dispatch (retry closure cuts the K-scan windows to 4 here);
  (c) the same run on the pallas tier, its kernels compiled; the compiled
      K-scan program must hold a `tpu_custom_call`;
  (d) leader-crash at its cataloged size on the jit tier: recovery and the
      fault-operand step program.

Each phase prints one JSON line: committed count, wall and compile
seconds, the epoch programs traced (`repro.obs`'s ``programs_traced``
events) with their pow2 batch buckets and the trace, lower and
compile-or-load seconds of the dispatch that traced them, how many of
its programs came from an earlier phase (``programs_shared``: the epoch
programs are shared by every cluster of a tier class in the process, so
a phase traces only the programs and buckets no earlier phase ran, and
says so where that is none), and whether the float64 outputs (log
deadlines, client commit times) matched numpy bit for bit, with their
largest difference in seconds (on the TPU float64 is a pair of float32
words, so they need not; the committed log must).  The last line is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent


def device_check() -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    print("device", json.dumps(info), flush=True)
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX's devices are {info}")
    return info


class CompileSpans:
    """JAX's own compile spans in this process (jax.monitoring): tracing,
    lowering, and backend compile or persistent-cache load."""

    KINDS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
             "/jax/core/compile/backend_compile_duration": "compile_s"}

    def __init__(self):
        import jax

        self.spans: list = []
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, **kw):
        if event in self.KINDS:
            self.spans.append((self.KINDS[event], start, end))

    def take(self) -> list:
        spans, self.spans = self.spans, []
        return spans


def program_rows(rec: dict, spans: list) -> list:
    """The engine's epoch programs traced in a recording: one row per
    `programs_traced.*` event, with the compile seconds inside the
    `fused.dispatch` span that holds it."""
    dispatch = rec["spans"].get("fused.dispatch", [])
    out = []
    for name, t, shape in rec["events"]:
        if not name.startswith("programs_traced."):
            continue
        a, b = next(((a, b) for a, b in dispatch if a <= t <= b), (t, t))
        row = {"program": name.split(".", 1)[1], "bucket": shape["n_pad"]}
        if "K" in shape:
            row["K"] = shape["K"]
        for kind in CompileSpans.KINDS.values():
            row[kind] = union_s([(s, e) for k, s, e in spans
                                 if k == kind and a <= s and e <= b])
        out.append(row)
    return out


def union_s(intervals) -> float:
    """Length of the union of ``intervals``: JAX traces the jitted helpers
    a program calls inside the program's own trace span."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        a = max(a, end)
        if b > a:
            total += b - a
            end = b
    return total


def counts(result) -> dict:
    return {k: v for k, v in result.as_dict().items()
            if not isinstance(v, (float, str))}


def max_abs_diff(a, b) -> float:
    """Largest |a - b| over the finite pairs; equal infinities count 0."""
    import numpy as np

    fin = np.isfinite(a) & np.isfinite(b)
    if not np.array_equal(a[~fin], b[~fin]):
        return float("inf")
    return float(np.max(np.abs(a[fin] - b[fin]), initial=0.0))


def run_phase(label: str, tier: str, scenario, compiles: CompileSpans):
    import numpy as np

    from repro import obs
    from repro.sim.scenario import run_scenario_on_cluster
    from repro.sim.trace import (CommitTrace, assert_trace_ok,
                                 check_adversarial)

    def run(t):
        result, cluster = run_scenario_on_cluster(
            "nezha-vectorized", scenario, tier=t)
        trace = CommitTrace.from_cluster(cluster)
        assert_trace_ok(trace)
        result.invariant_violations = len(check_adversarial(trace))
        return result, trace, cluster

    compiles.take()
    with obs.recording() as rec:
        t0 = time.perf_counter()
        res, trace, cluster = run(tier)
        wall = time.perf_counter() - t0
    spans = compiles.take()
    ref, ref_trace, _ = run("numpy")
    if not np.array_equal(trace.log_uids, ref_trace.log_uids):
        raise RuntimeError(f"{label}: committed log differs from numpy")
    if counts(res) != counts(ref):
        raise RuntimeError(f"{label}: counts {counts(res)} != numpy "
                           f"{counts(ref)}")
    if res.committed == 0:
        raise RuntimeError(f"{label}: nothing committed")
    f64_equal = bool(
        np.array_equal(trace.log["deadline"], ref_trace.log["deadline"])
        and np.array_equal(trace.commits["t"], ref_trace.commits["t"]))
    # the logs are equal in order, so the deadlines pair up; the client
    # deliveries pair up where they come in the same order
    deliveries_equal = np.array_equal(trace.commit_uids, ref_trace.commit_uids)
    programs = program_rows(rec, spans)
    shared = {k.split(".", 1)[1]: v for k, v in rec["counters"].items()
              if k.startswith("programs_shared.")}
    report = {
        "phase": label, "tier": tier, "scenario": scenario.name,
        "n_requests": res.n_requests, "committed": res.committed,
        "view_changes": res.view_changes, "epochs": res.epochs,
        "log_equal_numpy": True, "counts_equal_numpy": True,
        "trace_ok": True, "f64_bits_equal_numpy": f64_equal,
        "deadline_max_abs_diff_s": max_abs_diff(trace.log["deadline"],
                                                ref_trace.log["deadline"]),
        "commit_t_max_abs_diff_s": (
            max_abs_diff(trace.commits["t"], ref_trace.commits["t"])
            if deliveries_equal else None),
        "wall_s": wall,
        "compile_s": union_s([(s, e) for k, s, e in spans
                              if k == "compile_s"]),
        "programs": programs,
        "buckets": sorted({p["bucket"] for p in programs}),
        "programs_shared": shared,
    }
    if not programs:
        report["programs_note"] = (
            "every epoch program and batch bucket was traced by an earlier "
            "phase of this process; none was traced or compiled here")
    return report, cluster


def pallas_custom_call(cluster, programs) -> bool:
    """Whether the pallas tier's compiled K-scan program calls the kernels,
    compiled at the window length and bucket the run used."""
    import jax

    from repro.analysis.lint.trace_safety import _fused_scan_args

    cfg = cluster.cfg
    scan = cluster.engine.tier.epoch_scan(
        cfg.f, use_kcls=bool(getattr(cfg, "commutative", False)),
        use_cap=float(getattr(cfg, "deadline_cap", 0.0) or 0.0) > 0.0)
    used = next(p for p in programs if p["program"] == "scan")
    args = _fused_scan_args(used["K"], used["bucket"], cluster.engine.n,
                            window=cfg.dom.window)
    with jax.enable_x64(True):
        text = scan.lower(**args).compile().as_text()
    return "tpu_custom_call" in text


def main() -> None:
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.sim.scenario import get_scenario

    info = device_check()
    print("compile_cache", enable_compile_cache(), flush=True)
    compiles = CompileSpans()
    base = get_scenario("intra-zone")
    at_scale = dataclasses.replace(
        base, name="intra-zone-200k",
        workload=dataclasses.replace(base.workload, rate_per_client=20000.0,
                                     duration=0.5),
        overrides={**base.overrides, "epochs_per_dispatch": 16})
    report, _ = run_phase("b", "jit", at_scale, compiles)
    print(json.dumps(report), flush=True)
    report, cluster = run_phase("c", "pallas", at_scale, compiles)
    print(json.dumps(report), flush=True)
    has_kernel = pallas_custom_call(cluster, report["programs"])
    print(json.dumps({"phase": "c", "tpu_custom_call": has_kernel}),
          flush=True)
    if not has_kernel:
        raise RuntimeError("c: the pallas program holds no tpu_custom_call")
    report, _ = run_phase("d", "jit", get_scenario("leader-crash"), compiles)
    print(json.dumps(report), flush=True)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
