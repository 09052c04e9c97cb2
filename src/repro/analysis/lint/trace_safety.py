"""Layer 2: jaxpr trace-safety checks (TS001-TS003).

Static source checks can miss what jit *actually* stages, so this layer
traces the real programs -- the fused epoch step and the K-epoch
`lax.scan` program (`repro.core.engine`) across their specialization axes,
plus the Pallas kernel wrappers -- and walks the jaxprs:

  TS001  the fused step/scan are the bit-for-bit contract's hot path;
         every floating aval in their traces must be float64 (an f32 aval
         means an operand silently dropped out of the time plane). The
         kernel wrappers are held to the same rule: their sort keys are
         exact int32 (hi, lo) words bitcast from the caller-precision
         deadlines, so no sub-f64 float compute belongs in those traces
         either;
  TS002  no host-callback primitives inside any fused/kernel trace (a
         callback is a hidden host sync AND a nondeterminism hazard);
  TS003  shape stability: fused tiers must pad epoch batches to pow2
         buckets, and the worst-case compile count across the scenario
         catalog (specialization keys x pow2 buckets x K-epoch scan
         buckets) must stay bounded. The sharded backend adds a G axis:
         each distinct group count reachable by a cataloged sharded
         scenario is one more leading-dim shape of the vmapped group
         program (`ShardedNezhaCluster` always dispatches all G groups,
         so G is config-static, and its groups share ONE tier instance,
         so the per-group fused programs compile once -- not G times).
"""
from __future__ import annotations

import math
from typing import Iterable, Iterator

import numpy as np

from repro.analysis.lint.findings import Finding

ENGINE_PATH = "src/repro/core/engine.py"
OPS_PATH = "src/repro/kernels/ops.py"

# worst-case jit-compile budget for one full catalog sweep on one tier
COMPILE_LIMIT = 128
# headroom factor on the per-epoch batch estimate (retries, drain bursts)
_BATCH_SLACK = 4.0

_CALLBACK_PRIMS = {"outside_call", "infeed", "outfeed"}


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------
def _sub_jaxprs(value) -> Iterator:
    if hasattr(value, "jaxpr"):          # ClosedJaxpr
        yield value.jaxpr
    elif hasattr(value, "eqns"):         # Jaxpr
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_jaxprs(jaxpr) -> Iterator:
    """The jaxpr plus every nested sub-jaxpr (pjit bodies, branches...)."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_jaxprs(sub)


def non_f64_float_ops(jaxpr) -> list[tuple[str, str]]:
    """(primitive, dtype) for every eqn touching a float aval != float64."""
    out = []
    for j in iter_jaxprs(jaxpr):
        for eqn in j.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is not None and np.issubdtype(dt, np.floating) \
                        and dt != np.float64:
                    out.append((eqn.primitive.name, str(dt)))
    return out


def callback_prims(jaxpr) -> list[str]:
    out = []
    for j in iter_jaxprs(jaxpr):
        for eqn in j.eqns:
            name = eqn.primitive.name
            if "callback" in name or name in _CALLBACK_PRIMS:
                out.append(name)
    return out


# ---------------------------------------------------------------------------
# tracing the real programs
# ---------------------------------------------------------------------------
def _fused_step_args(n: int, r: int, *, dies_at=False, clock=False,
                     pair=False, sync=False, window: int = 16) -> dict:
    rng = np.random.default_rng(0)
    kw = dict(
        pool=np.full(window * r, np.inf),
        ptr=np.int64(0),
        cnt=np.int64(0),
        t=rng.uniform(0.0, 1.0, n),
        c2p=rng.uniform(0.0, 1e-3, n),
        owd_pr=rng.uniform(0.0, 1e-3, (n, r)),
        drop_pr=np.zeros((n, r), bool),
        reply_owd=rng.uniform(0.0, 1e-3, (n, r)),
        alive=np.ones(r, bool),
        kcls=np.zeros(n, np.int64),
        leader=0,
        n_valid=n,
        pq01=0.95,
        margin=1e-4,
        clamp_d=1e-3,
        batch_delay=0.0,
        cap=1.0,
        floor=0.0,
    )
    if dies_at:
        kw["dies_at"] = np.full(r, np.inf)
    if clock:
        kw["stamp_off"] = np.zeros(n)
        kw["arr_off"] = np.zeros((n, r))
    if pair:
        kw["pair_drop"] = np.zeros((n, r), bool)
        kw["pair_delay"] = np.zeros((n, r))
    if sync:
        # sync-round operands (PR 10): probe matrices over M = replicas +
        # proxies sync nodes, plus the two estimator scalars -- all float64
        m = r + 1
        kw["sync_theta"] = rng.uniform(-1e-4, 1e-4, (m, m))
        kw["sync_rtt"] = rng.uniform(1e-4, 1e-3, (m, m))
        kw["sync_safety"] = np.float64(1.5)
        kw["sync_floor"] = np.float64(200e-9)
    return kw


def _fused_scan_args(k: int, n: int, r: int, *, window: int = 16) -> dict:
    rng = np.random.default_rng(0)
    return dict(
        pool=np.full(window * r, np.inf),
        ptr=np.int64(0),
        cnt=np.int64(0),
        t=rng.uniform(0.0, 1.0, (k, n)),
        c2p=rng.uniform(0.0, 1e-3, (k, n)),
        owd_pr=rng.uniform(0.0, 1e-3, (k, n, r)),
        drop_pr=np.zeros((k, n, r), bool),
        reply_owd=rng.uniform(0.0, 1e-3, (k, n, r)),
        kcls=np.zeros((k, n), np.int64),
        n_valid=np.full(k, n, np.int64),
        alive=np.ones(r, bool),
        leader=0,
        pq01=0.95,
        margin=1e-4,
        clamp_d=1e-3,
        batch_delay=0.0,
        cap=1.0,
        floor=0.0,
    )


def _trace_contract(jaxpr, label: str, path: str) -> list[Finding]:
    """TS001 + TS002 on one jaxpr."""
    findings: list[Finding] = []
    bad = non_f64_float_ops(jaxpr)
    if bad:
        prims = ", ".join(f"{p}[{d}]" for p, d in bad[:4])
        findings.append(Finding(
            rule="TS001", path=path, line=0, col=0, symbol=label,
            message=f"{len(bad)} non-float64 float op(s) in the trace: "
                    f"{prims}",
            extra={"ops": bad[:32]}))
    cbs = callback_prims(jaxpr)
    if cbs:
        findings.append(Finding(
            rule="TS002", path=path, line=0, col=0, symbol=label,
            message=f"host callback primitive(s) in the trace: "
                    f"{', '.join(sorted(set(cbs)))}"))
    return findings


def check_fused_step(f: int = 1, n: int = 8) -> list[Finding]:
    """Trace the jit tier's fused step (and the K-epoch scan program)
    across their specialization axes and assert the float64-end-to-end +
    no-callback contract on each jaxpr."""
    import jax

    from repro.core.engine import JitTier

    findings: list[Finding] = []
    tier = JitTier()
    r = 2 * f + 1
    variants = [
        (False, False, {}),
        (True, False, {}),
        (False, True, {}),
        (True, True, {}),
        (False, False, dict(dies_at=True)),
        (False, False, dict(clock=True)),
        (False, False, dict(pair=True)),
        (False, False, dict(clock=True, sync=True)),
        (False, False, dict(pair=True, clock=True, dies_at=True, sync=True)),
    ]
    for use_kcls, use_cap, fault in variants:
        label = (f"_build_fused_step(use_kcls={use_kcls}, "
                 f"use_cap={use_cap}"
                 + (f", {'/'.join(fault)}" if fault else "") + ")")
        step = tier.epoch_step(f, use_kcls=use_kcls, use_cap=use_cap)
        kw = _fused_step_args(n, r, **fault)
        with jax.enable_x64(True):
            jaxpr = jax.make_jaxpr(step)(**kw)
        findings.extend(_trace_contract(jaxpr, label, ENGINE_PATH))
    # the K-epoch scan shares the epoch body but stages it under lax.scan
    # with the ring-pool carry threaded through -- trace it separately so
    # a scan-only regression (e.g. an f32 carry init) cannot hide
    for use_kcls in (False, True):
        label = f"_build_fused_scan(K=4, use_kcls={use_kcls})"
        scan = tier.epoch_scan(f, use_kcls=use_kcls)
        kw = _fused_scan_args(4, n, r)
        with jax.enable_x64(True):
            jaxpr = jax.make_jaxpr(scan)(**kw)
        findings.extend(_trace_contract(jaxpr, label, ENGINE_PATH))
    return findings


def check_kernel_wrappers(n: int = 8, r: int = 3) -> list[Finding]:
    """TS001 + TS002 on the Pallas kernel wrappers: the int32 (hi, lo) key
    encoding means the whole trace is integer lanes plus float64 inputs --
    any sub-f64 float op is a regression toward the old span-relative-f32
    keys and their tie window."""
    import jax

    findings: list[Finding] = []
    try:
        from repro.kernels.ops import (dom_admit_traced,
                                       dom_deadline_order_traced)
        rng = np.random.default_rng(0)
        d = rng.uniform(0.0, 1.0, n)
        a = rng.uniform(0.0, 1.0, (n, r))
        with jax.enable_x64(True):
            traces = {
                "dom_admit_traced":
                    jax.make_jaxpr(
                        lambda dd, aa: dom_admit_traced(
                            dd, aa, use_pallas=True))(d, a),
                "dom_deadline_order_traced":
                    jax.make_jaxpr(
                        lambda dd: dom_deadline_order_traced(
                            dd, use_pallas=True))(d),
            }
    except Exception as exc:    # surface, never crash the lint run
        return [Finding(
            rule="TS002", path=OPS_PATH, line=0, col=0,
            message=f"failed to trace kernel wrappers: {exc!r}")]
    for name, jaxpr in traces.items():
        findings.extend(_trace_contract(jaxpr, name, OPS_PATH))
    return findings


# ---------------------------------------------------------------------------
# TS003: shape stability / bounded compile count over the catalog
# ---------------------------------------------------------------------------
def _scenario_batch_estimate(sc) -> int:
    """Worst-case rows in one epoch batch for a cataloged scenario."""
    from repro.core.vectorized_cluster import VectorizedConfig

    w = sc.workload
    epoch = float(sc.overrides.get(
        "epoch_duration", VectorizedConfig.epoch_duration))
    if w.mode == "closed":
        per_epoch = sc.n_clients * max(w.lanes, 1)
    else:
        per_epoch = w.rate_per_client * sc.n_clients * epoch
    return max(1, int(math.ceil(per_epoch * _BATCH_SLACK)))


def check_compile_stability(scenarios: Iterable = None) -> list[Finding]:
    from repro.core.engine import SCAN_K_BUCKETS, TIERS, _pow2_bucket

    findings: list[Finding] = []
    # fused tiers must pad: without pow2 bucketing every distinct batch
    # size is a fresh XLA compile (the O(log N) guarantee evaporates)
    for name, cls in TIERS.items():
        if cls.fused and not cls.pad_batches:
            findings.append(Finding(
                rule="TS003", path=ENGINE_PATH, line=0, col=0,
                symbol=f"{cls.__name__}",
                message=f"fused tier {name!r} has pad_batches=False: "
                        "per-epoch batch shapes become unbounded compile "
                        "keys"))
    if scenarios is None:
        from repro.sim.scenario import SCENARIOS
        scenarios = SCENARIOS.values()
    buckets: set[int] = set()
    spec_keys: set[tuple] = set()
    # K=1 is the fused step; each K in SCAN_K_BUCKETS a scenario's
    # epochs-per-dispatch setting can reach is a distinct scan program
    # (the scan length is a static shape axis of its stacked operands)
    k_buckets: set[int] = {1}
    # the G axis: every distinct group count a sharded scenario can reach
    # is one leading-dim variant of the vmapped group program (all-groups
    # dispatch makes G config-static; the G=1/sequential paths reuse the
    # tier's own fused step, shared across groups via the one tier
    # instance, so only G > 1 adds programs)
    g_buckets: set[int] = set()
    for sc in scenarios:
        n_max = _pow2_bucket(_scenario_batch_estimate(sc))
        b = 1
        while b <= n_max:
            buckets.add(b)
            b *= 2
        use_kcls = bool(sc.overrides.get("commutative", False))
        use_cap = float(sc.overrides.get("deadline_cap", 0.0) or 0.0) > 0.0
        # pair-mask operands (partition/gray faults) are an optional-operand
        # specialization of the fused step: scenarios that can reach them
        # compile BOTH the masked and unmasked variants (fault-free
        # stretches release the pair state and return to the bare program)
        from repro.sim.scenario import NET_FAULT_KINDS
        has_pair = any(getattr(ev, "kind", None) in NET_FAULT_KINDS
                       for ev in sc.faults)
        spec_keys.add((sc.f, use_kcls, use_cap, False))
        if has_pair:
            spec_keys.add((sc.f, use_kcls, use_cap, True))
        # the sync axis (PR 10): a modeled-sync regime attaches probe-round
        # operands to the epochs that land on a round boundary, so such
        # scenarios compile BOTH the sync and bare variants of the step
        # (the bare key is already in). Sync runs are fenced off the
        # K-scan and vmapped-group fast paths, so no K/G cross product.
        if bool(getattr(sc.env.clock, "sync_model", False)):
            spec_keys.add((sc.f, use_kcls, use_cap, has_pair, "sync"))
        g = int(getattr(sc, "groups", 1) or 1)
        if g > 1:
            g_buckets.add(g)
            # the vmapped group program: same epoch body, leading G axis
            spec_keys.add((sc.f, use_kcls, use_cap, False, g))
        epd = int(sc.overrides.get("epochs_per_dispatch", 1) or 1)
        k_buckets.update(k for k in SCAN_K_BUCKETS if k <= epd)
    worst = len(buckets) * len(spec_keys) * len(k_buckets)
    if worst > COMPILE_LIMIT:
        findings.append(Finding(
            rule="TS003", path="src/repro/sim/scenario.py", line=0, col=0,
            symbol="SCENARIOS",
            message=f"catalog sweep worst-case compile count {worst} "
                    f"({len(spec_keys)} specialization keys x "
                    f"{len(buckets)} pow2 buckets x "
                    f"{len(k_buckets)} K buckets; G buckets "
                    f"{sorted(g_buckets) or [1]}) exceeds "
                    f"{COMPILE_LIMIT}",
            extra={"buckets": sorted(buckets),
                   "keys": sorted(spec_keys, key=str),
                   "k_buckets": sorted(k_buckets),
                   "g_buckets": sorted(g_buckets)}))
    return findings


def trace_findings() -> list[Finding]:
    """All layer-2 findings (traces the real programs; needs jax)."""
    return (check_fused_step() + check_kernel_wrappers()
            + check_compile_stability())


__all__ = ["iter_jaxprs", "non_f64_float_ops", "callback_prims",
           "check_fused_step", "check_kernel_wrappers",
           "check_compile_stability", "trace_findings", "COMPILE_LIMIT"]
