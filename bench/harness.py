"""The benchmark's run: set-up, measured window, and the check after it.

Everything a cell needs is found by name: the cell in BENCHMARK.json, its
configuration in the file that entry names, its traffic in
``bench/traffic/<traffic>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  Nothing here names a cell.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import hashlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

from bench import arrivals, probes, reference

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = Path(__file__).resolve().parent
SWEEP_MAX = 8192            # largest pow2 epoch bucket the warm-up compiles


# ---------------------------------------------------------------------------
# Cells, configurations, traffic, metric readers: all by name
# ---------------------------------------------------------------------------
def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; available: "
                       + ", ".join(cells))
    w = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def peaks(device_kind: str) -> dict:
    """Published per-chip peaks of ``device_kind`` (bench/peaks.json); a
    device missing from the table is an error."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device {device_kind!r}")
    return table[device_kind]


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def rep_seeds(seed: int, i: int) -> tuple[int, int]:
    """(cluster seed, workload seed) of repetition ``i`` of run ``seed``."""
    ss = np.random.SeedSequence([seed % 2**63, i % 2**32])
    a, b = ss.generate_state(2, np.uint32)
    return int(a), int(b)


WARMUP_REP = 2**32 - 1      # a repetition index the window never reaches


def make_scenario(cell: Cell, seed: int, i: int):
    """The scenario of repetition ``i``: the cell's deployment and traffic,
    with seeds drawn from the run's seed."""
    from repro.sim import scenario as sc
    from repro.sim.workload import Workload

    dep, run, tr = cell.config["deployment"], cell.config["run"], cell.traffic
    s_seed, w_seed = rep_seeds(seed, i)
    faults = tuple(getattr(sc, ev["kind"])(**{k: v for k, v in ev.items()
                                               if k != "kind"})
                   for ev in tr.get("faults", []))
    workload = Workload(mode=tr["mode"], rate_per_client=tr["rate_per_client"],
                        duration=tr["duration"], warmup=tr["warmup"],
                        drain=tr["drain"], read_ratio=tr["read_ratio"],
                        skew=tr["skew"], n_keys=tr["n_keys"], seed=w_seed)
    overrides = {"n_proxies": dep["n_proxies"],
                 "epochs_per_dispatch": run["epochs_per_dispatch"],
                 "epoch_duration": run["epoch_duration"],
                 "client_timeout": run["client_timeout"]}
    return sc.Scenario(f"{cell.name}#{i}",
                       environment=tr.get("environment") or dep["environment"],
                       faults=faults, workload=workload, f=dep["f"],
                       n_clients=dep["n_clients"], seed=s_seed,
                       overrides=overrides)


def run_repetition(cell: Cell, scenario):
    from repro.sim.scenario import run_scenario_on_cluster

    return run_scenario_on_cluster(cell.config["deployment"]["protocol"],
                                   scenario, tier=cell.config["run"]["tier"])


# ---------------------------------------------------------------------------
# Compile cache and the warm-up's bucket sweep
# ---------------------------------------------------------------------------
def cache_dir(root: Path = ROOT) -> Path:
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return Path(env) if env else root / ".jax_cache"


def enable_cache(root: Path = ROOT) -> Path:
    """JAX's persistent cache at the fixed ``.jax_cache/`` of the checkout,
    or where ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept."""
    import jax

    path = cache_dir(root)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _pow2_buckets(lo: int, hi: int) -> list[int]:
    out, b = [], 1
    while b <= hi:
        if b >= lo:
            out.append(b)
        b *= 2
    return out


def _aval(x) -> str:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return f"{x.dtype}{tuple(x.shape)}"
    return type(x).__name__


def sweep_buckets(captured: list, err=sys.stderr) -> int:
    """Compile every captured epoch program at every pow2 batch bucket a
    repetition can reach, so that no seed compiles inside the window.

    A program's batch axis is that of its ``t`` operand (the attempt times:
    [n_pad] per epoch, [K, n_pad] per scan window)."""
    import inspect

    import jax

    done = 0
    seen: set = set()
    for fn, args, kwargs in captured:
        names = list(inspect.signature(fn).parameters)
        if "t" not in names:
            print(f"warm-up: no batch operand in {fn}; sweep skipped",
                  file=err)
            continue
        t = args[names.index("t")]
        axis, n_pad = t.ndim - 1, t.shape[-1]
        lo = 1 if axis == 0 else max(1, n_pad // 2)

        def resized(x, b):
            if isinstance(x, np.ndarray) and x.ndim > axis \
                    and x.shape[axis] == n_pad:
                shape = x.shape[:axis] + (b,) + x.shape[axis + 1:]
                return jax.ShapeDtypeStruct(shape, x.dtype)
            return x

        for b in _pow2_buckets(lo, SWEEP_MAX):
            a_b = [resized(a, b) for a in args]
            kw_b = {k: resized(v, b) for k, v in kwargs.items()}
            key = (id(fn), tuple(map(_aval, a_b)),
                   tuple((k, _aval(v)) for k, v in sorted(kw_b.items())))
            if b == n_pad or key in seen:
                continue
            seen.add(key)
            with jax.enable_x64(True):
                fn.lower(*a_b, **kw_b).compile()
            done += 1
    return done


def sweep_marker(cell: Cell, captured: list, root: Path = ROOT) -> Path:
    """A file in the cache directory that says the sweep of this cell's
    programs, for this program source and JAX, is in the cache."""
    import jax

    h = hashlib.sha256()
    h.update(cell.name.encode())
    h.update(jax.__version__.encode())
    h.update(json.dumps(cell.config, sort_keys=True).encode())
    for sig in sorted(probes.signature(a, k) for _, a, k in captured):
        h.update(sig.encode())
    for path in sorted((root / "src").rglob("*.py")):
        h.update(path.read_bytes())
    return cache_dir(root) / f"bench-sweep-{h.hexdigest()[:24]}.done"


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def ref_params(cluster) -> reference.Params:
    cfg = cluster.cfg
    return reference.Params(
        f=cfg.f, window=cfg.dom.window, percentile=cfg.dom.percentile,
        clamp_d=cfg.dom.clamp_d, batch_delay=cfg.leader_batch_delay,
        client_timeout=cfg.client_timeout, sigma_safety=cfg.clock.sigma_safety,
        sigma_floor=cfg.clock.sigma_floor)


@dataclasses.dataclass
class Rep:
    """One repetition of the window, as the check needs it."""

    index: int
    seed: int                   # the workload seed (arrival stream)
    t0: float
    t1: float
    result: object              # ScenarioResult
    records: list
    cluster: object = None
    # filled from the cluster after the window, when it is freed
    log_uids: Optional[np.ndarray] = None
    commit_uids: Optional[np.ndarray] = None
    view_changes: Optional[list] = None
    trace_violations: int = 0
    params: Optional[reference.Params] = None

    def extract(self) -> None:
        from repro.sim.trace import CommitTrace, check_trace

        c = self.cluster
        tr = CommitTrace.from_cluster(c)
        self.log_uids = tr.log_uids
        self.commit_uids = tr.commit_uids
        self.view_changes = list(c.view_change_events)
        self.trace_violations = len(check_trace(tr))
        self.params = ref_params(c)
        self.cluster = None


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> Optional[int]:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class Session:
    """The probes of one process over a cell: installed on entry, removed on
    exit.  `warm_up` is the set-up; `window` measures."""

    def __init__(self, cell: Cell, trace: bool, root: Path = ROOT,
                 err=sys.stderr):
        self.cell, self.trace, self.root, self.err = cell, trace, root, err
        enable_cache(root)
        self.watch = probes.CompileWatch()
        self.recorder = probes.Recorder()
        self.spans = probes.Spans() if trace else None
        self.program: Optional[dict] = None     # repro.obs's record
        self._stack = None

    def __enter__(self):
        self._stack = contextlib.ExitStack()
        self._stack.enter_context(self.recorder.installed())
        if self.spans:
            self._stack.enter_context(self.spans.installed())
        return self

    def __exit__(self, *exc):
        self._stack.close()
        self.watch.close()
        return False

    def warm_up(self, seed: int) -> None:
        """One repetition with a seed outside the window's, then, the first
        time in this cache, every epoch program at every batch bucket."""
        capture = probes.ProgramCapture()
        with capture.installed():
            run_repetition(self.cell, make_scenario(self.cell, seed,
                                                    WARMUP_REP))
        marker = sweep_marker(self.cell, capture.calls, self.root)
        if not marker.exists():
            n = sweep_buckets(capture.calls, self.err)
            marker.parent.mkdir(parents=True, exist_ok=True)
            marker.write_text(f"{n} programs\n")
        del capture
        self.recorder.clear()
        if self.spans:
            self.spans.clear()
        gc.collect()

    def window(self, seed: int, seconds: float):
        """Repetitions back to back until ``seconds`` have passed; the last
        runs to its end.  Returns (reps, start, end, cache misses, trace).

        A traced window records the program's own spans and counters
        (`repro.obs`) into ``self.program``; an untraced one leaves
        recording off, so its path is the one users run."""
        from repro import obs

        misses_before = self.watch.cache_misses
        reps: list[Rep] = []
        profile = None
        recording = obs.recording() if self.trace \
            else contextlib.nullcontext()
        with recording as self.program:
            w0 = time.time()
            while not reps or time.time() - w0 < seconds:
                i = len(reps)
                scenario = make_scenario(self.cell, seed, i)
                self.recorder.begin()
                tracer = (probes.DeviceTrace() if self.trace and i == 0
                          else contextlib.nullcontext())
                with tracer:
                    r0 = time.time()
                    result, cluster = run_repetition(self.cell, scenario)
                    r1 = time.time()
                if self.trace and i == 0:
                    profile = tracer
                reps.append(Rep(index=i, seed=rep_seeds(seed, i)[1], t0=r0,
                                t1=r1, result=result,
                                records=self.recorder.take(),
                                cluster=cluster))
        return (reps, w0, reps[-1].t1, self.watch.cache_misses - misses_before,
                profile)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: Path = ROOT, err=sys.stderr) -> dict:
    """Set up, measure and check one cell; returns the result line's object.

    ``t_start`` is the wall time (time.time) at which the process started.
    """
    cell = load_cell(name, root)
    with Session(cell, trace, root, err) as session:
        session.warm_up(seed)
        reps, w0, w1, misses, profile = session.window(seed, seconds)
    setup_s = w0 - t_start
    span_s = w1 - w0
    peak = memory_peak_bytes()
    # -- after the window: free the program's state, then check -------------
    for r in reps:
        r.extract()
    gc.collect()
    check = check_reps(cell, reps)
    committed = sum(r.result.committed for r in reps)
    attempted = sum(r.result.n_requests for r in reps)
    device = {**device_info(), "memory_peak_bytes": peak}
    if trace:
        ctx = traced_context(session, reps, w0, w1, profile)
        if ctx.device is not None:
            device["busy_s"] = ctx.device["busy_s"]
            device["window_s"] = ctx.device["window_s"]
        metrics = per_layer_metrics(cell, ctx, session.root)
    else:
        metrics = {"committed_per_s": {"value": committed / span_s,
                                       "unit": "req/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        metrics = {k: v for k, v in metrics.items()
                   if any(m["name"] == k for m in cell.end_to_end)}
    print(f"window: {len(reps)} repetitions in {span_s} s, "
          f"{committed} of {attempted} requests committed, "
          f"{misses} persistent-cache misses inside the window", file=err)
    for key, (value, limit) in check["numbers"].items():
        print(f"check {key}: {value} (limit {limit})", file=err)
    line = {"correct": check["correct"], "attempted": attempted,
            "failed": attempted - committed, "metrics": metrics,
            "device": device}
    if trace and profile is not None and profile.breakdown:
        line["breakdown"] = profile.breakdown
    line["check"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in check["numbers"].items()}
    return line


# ---------------------------------------------------------------------------
# The check: program against reference
# ---------------------------------------------------------------------------
LIMITS_FILE = BENCH_DIR / "limits.json"


def limits() -> dict:
    return {k: v["limit"] for k, v in load_json(LIMITS_FILE).items()}


def attempts_wrong(stream: arrivals.Stream, records: list, ref_outs: list,
                   p: reference.Params, vc_times: list) -> int:
    """Attempts that injection and the client retry model did not make.

    Each request's first attempt is at its time in the arrival copy, with
    its key class; a later attempt follows an undelivered one by the client
    timeout, or is pulled up to a view change's completion.  Every request
    due before the run ended is attempted."""
    wrong = 0
    first_t = dict(zip(stream.uids.tolist(), stream.t.tolist()))
    kcls = dict(zip(stream.uids.tolist(), stream.kcls.tolist()))
    last: dict = {}              # uid -> (t, delivered) of its latest attempt
    for (kind, rec, _), want in zip(records, ref_outs):
        if kind != "epoch":
            continue
        uids = reference.pack(rec["cid"], rec["rid"]).tolist()
        ts, t0s = rec["t"].tolist(), rec["t0"].tolist()
        kc = rec["kcls"].tolist() if rec.get("kcls") is not None else None
        for j, u in enumerate(uids):
            if u not in first_t or t0s[j] != first_t[u] \
                    or (kc is not None and kc[j] != kcls[u]):
                wrong += 1
                continue
            prev = last.get(u)
            if prev is None:
                wrong += ts[j] != first_t[u]
            else:
                due = prev[0] + p.client_timeout
                wrong += prev[1] or not (ts[j] == due or (
                    ts[j] < due and ts[j] in vc_times))
            last[u] = (ts[j], bool(want["delivered"][j]))
    horizon = max((float(rec["t"].max()) for k, rec, _ in records
                   if k == "epoch" and rec["t"].size), default=0.0)
    wrong += sum(1 for u, t in first_t.items() if u not in last and t <= horizon)
    return wrong


def commits_wrong(rep: Rep, records: list, ref_outs: list,
                  log_ref: reference.Log) -> int:
    """Requests whose commit result, or whose count in the result, differs
    from the reference's."""
    want = []
    for (kind, rec, _), out in zip(records, ref_outs):
        if kind == "epoch":
            want.append(reference.pack(rec["cid"], rec["rid"])[out["delivered"]])
        elif kind == "view_change":
            want.append(out["recovered"])
    want_u = np.concatenate(want) if want else np.empty(0, np.int64)
    got_u = np.asarray(rep.commit_uids, np.int64)
    wrong = int(np.setxor1d(want_u, got_u).size)
    wrong += abs(int(np.unique(got_u).size) - int(got_u.size))
    res = rep.result
    wrong += abs(res.committed - int(np.unique(want_u).size))
    wrong += abs(res.recovered_entries - log_ref.n_recovered)
    wrong += abs(res.dropped_speculative - log_ref.n_dropped)
    return wrong


def compare_rep(rep: Rep, stream: arrivals.Stream, ft=None) -> dict:
    """The numbers of one repetition, held to the float64 reference: the
    program's outputs, or with ``ft`` the control's -- the reference
    computed in ``ft`` in the program's place."""
    p, records = rep.params, rep.records
    ref_outs, log_ref = reference.replay_run(records, p, np.float64)
    if ft is None:
        program = [out for _, _, out in records]
        log_prog = np.asarray(rep.log_uids, np.int64)
    else:
        program, ctl_log = reference.replay_run(records, p, ft)
        log_prog = np.asarray(ctl_log.uids, np.int64)
    nums = reference.compare(program, ref_outs, log_prog, log_ref)
    vc_times = [v["t_done"] for v in (rep.view_changes or [])]
    nums["requests_wrong"] = abs(rep.result.n_requests - stream.n)
    nums["attempts_wrong"] = attempts_wrong(stream, records, ref_outs, p,
                                            vc_times)
    nums["commits_wrong"] = commits_wrong(rep, records, ref_outs, log_ref)
    nums["trace_violations"] = rep.trace_violations
    return nums


def fold(all_nums: list[dict]) -> dict:
    out: dict = {}
    for nums in all_nums:
        for k, v in nums.items():
            out[k] = max(out.get(k, 0), v) if k.endswith("_s") \
                else out.get(k, 0) + v
    return out


def check_reps(cell: Cell, reps: list, ft=None) -> dict:
    """Hold every repetition of the window to the reference.

    ``ft`` None checks the program; a float type puts the reference,
    computed in that type, in the program's place (the control)."""
    lim = limits()
    n_clients = cell.config["deployment"]["n_clients"]
    nums = fold([compare_rep(r, arrivals.open_loop_stream(cell.traffic,
                                                          n_clients, r.seed),
                             ft) for r in reps])
    has_sync = any(rec.get("sync_theta") is not None
                   for r in reps for k, rec, _ in r.records if k == "epoch")
    if not has_sync:
        nums.pop("sync_gap_s", None)
    numbers = {k: (nums[k], lim[k]) for k in lim if k in nums}
    correct = all(v <= limit for v, limit in numbers.values())
    return {"correct": bool(correct), "numbers": numbers}


# ---------------------------------------------------------------------------
# Per-layer metrics (traced runs)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Context:
    """What a metric reader reads over the traced window.

      spans, counters    the benchmark's own wrappers (`bench/probes.py`)
                         and JAX's compile spans (``compile``);
      program_spans,     the program's own record (`repro.obs`), kept
      program_counters   apart; empty where nothing was recorded;
      device             from the profiler trace of repetition 0: busy_s,
                         window_s, ops (every operation's device seconds by
                         its trace name), kind (the device kind) and peaks
                         (its entry in bench/peaks.json); None untraced;
      reps               the window's repetitions;
      epochs             each epoch of repetition 0: {"n": requests (not
                         padded), "R": receivers}.
    """

    window: tuple               # (t0, t1) wall seconds
    spans: dict                 # name -> [(t0, t1)] wall seconds
    counters: dict              # name -> int
    device: Optional[dict]
    program_spans: dict = dataclasses.field(default_factory=dict)
    program_counters: dict = dataclasses.field(default_factory=dict)
    reps: int = 0
    epochs: list = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _share(self, spans: dict, names) -> Optional[float]:
        iv = [s for n in names for s in spans.get(n, [])]
        if not iv:
            return None
        return 100.0 * probes.union_length(iv, *self.window) / self.window_s

    def share(self, *names: str) -> Optional[float]:
        """Percent of the window inside the union of the named spans, or
        None where no such span was recorded."""
        return self._share(self.spans, names)

    def program_share(self, *names: str) -> Optional[float]:
        """`share` over the program's own spans."""
        return self._share(self.program_spans, names)


def epoch_shapes(records: list) -> list:
    """Requests and receivers of each epoch record of one repetition."""
    return [{"n": int(rec["t"].size), "R": int(rec["alive"].size)}
            for kind, rec, _ in records if kind == "epoch"]


def traced_context(session: Session, reps: list, w0: float, w1: float,
                   profile) -> Context:
    """The Context of a traced window.  The device trace's idle time is
    painted by the benchmark's spans and the program's, innermost first."""
    spans = dict(session.spans.spans)
    spans["compile"] = list(session.watch.spans)
    program = session.program or {"spans": {}, "counters": {}}
    painted = {k: list(v) for k, v in spans.items()}
    for k, v in program["spans"].items():
        painted.setdefault(k, []).extend(v)
    dev = None
    if profile is not None:
        profile.reduce(painted)
        if profile.busy_s is not None:
            kind = device_info()["kind"]
            dev = {"busy_s": profile.busy_s, "window_s": profile.window_s,
                   "ops": profile.ops, "kind": kind, "peaks": peaks(kind)}
    return Context(window=(w0, w1), spans=spans,
                   counters=dict(session.recorder.counters), device=dev,
                   program_spans=dict(program["spans"]),
                   program_counters=dict(program["counters"]),
                   reps=len(reps),
                   epochs=epoch_shapes(reps[0].records) if reps else [])


def per_layer_metrics(cell: Cell, ctx: Context, root: Path = ROOT) -> dict:
    """Each of the cell's per-layer metrics its reader finds."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"], root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
