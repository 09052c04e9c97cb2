"""What the benchmark installs around the program's public calls.

  Recorder         every run: each epoch's inputs and outputs, crashes,
                   view changes and abandoned requests, in order -- what the
                   check replays against the reference; and the counts of
                   epochs run by single dispatches and by K-epoch scans;
  Spans            traced runs only: wall-time spans around the calls into
                   each layer (injection, engine stages, clock sync);
  CompileWatch     JAX's own compile spans (tracing, lowering, backend
                   compile or cache load) and persistent-cache misses;
  ProgramCapture   the warm-up only: one call of each epoch program, so the
                   warm-up can compile it at every batch bucket;
  DeviceTrace      traced runs only: a profiler trace of one repetition.

The program's own spans and counters (`repro.obs`) are recorded by the
harness in traced windows, not here.

Each installs by replacing a method on its class and puts the original
back on exit.
"""
from __future__ import annotations

import contextlib
import shutil
import tempfile
import time
from typing import Optional

import numpy as np


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


@contextlib.contextmanager
def patched(*triples):
    """Replace ``cls.name`` by ``make(original)`` for each (cls, name, make)."""
    saved = []
    try:
        for cls, name, make in triples:
            orig = cls.__dict__[name]
            saved.append((cls, name, orig))
            setattr(cls, name, make(orig))
        yield
    finally:
        for cls, name, orig in reversed(saved):
            setattr(cls, name, orig)


# ---------------------------------------------------------------------------
_EPOCH_INPUTS = ("t", "t0", "cid", "rid", "kcls", "alive", "leader",
                 "release_floor", "dies_at", "c2p", "p2c", "owd_pr", "drop_pr",
                 "reply_owd", "pair_drop", "pair_delay", "clock_stamp_off",
                 "clock_arr_off", "pre_deadline", "sync_theta", "sync_rtt")
_EPOCH_OUTPUTS = ("deadlines", "admitted", "committed", "fast", "delivered",
                  "commit_at_client", "sync_est", "sync_sigma")


def epoch_record(s, margin: float) -> tuple:
    inputs = {k: getattr(s, k) for k in _EPOCH_INPUTS}
    # the cluster flips liveness in place at a crash: keep this epoch's
    inputs["alive"] = np.array(inputs["alive"], bool)
    if inputs["pre_deadline"] is not None:
        raise ValueError("pre-stamped deadlines are outside the reference")
    inputs["margin"] = margin
    return ("epoch", inputs, {k: getattr(s, k) for k in _EPOCH_OUTPUTS})


class Recorder:
    def __init__(self):
        self.records: list = []
        self.counters = {"scan_epochs": 0, "work_epochs": 0}
        self._in_window = 0
        self._fallback = 0

    def begin(self) -> None:
        self.records = []

    def take(self) -> list:
        out, self.records = self.records, []
        return out

    def clear(self) -> None:
        self.records = []
        self.counters = {"scan_epochs": 0, "work_epochs": 0}

    def installed(self):
        from repro.core.engine import DomEngine, ReplicaLogState

        rec = self

        def run_epoch(orig):
            def wrapper(self, due, alive, leader, release_floor=0.0,
                        dies_at=None):
                margin = self.bound_margin()
                s = orig(self, due, alive, leader, release_floor, dies_at)
                if rec._in_window:
                    rec._fallback += 1
                else:
                    rec.counters["work_epochs"] += 1
                rec.records.append(epoch_record(s, margin))
                return s
            return wrapper

        def run_epoch_window(orig):
            def wrapper(self, dues, alive, leader, release_floor=0.0):
                margin = self.bound_margin()
                rec._in_window += 1
                rec._fallback = 0
                try:
                    states = orig(self, dues, alive, leader, release_floor)
                finally:
                    rec._in_window -= 1
                work = sum(1 for s in states if s is not None)
                rec.counters["work_epochs"] += work
                if rec._fallback == 0:      # the scan ran, not run_epoch
                    rec.counters["scan_epochs"] += work
                    rec.records.extend(epoch_record(s, margin)
                                       for s in states if s is not None)
                return states
            return wrapper

        def on_crash(orig):
            def wrapper(self, rid):
                rec.records.append(("crash", {"rid": int(rid)}, None))
                return orig(self, rid)
            return wrapper

        def drop_uids(orig):
            def wrapper(self, cid, rid):
                rec.records.append(("abandon", {"cid": np.asarray(cid).copy(),
                                                "rid": np.asarray(rid).copy()},
                                    None))
                return orig(self, cid, rid)
            return wrapper

        def view_change(orig):
            def wrapper(self, new_view, alive):
                alive_c = np.asarray(alive, bool).copy()
                res = orig(self, new_view, alive)
                got = res["recovered"]
                rec.records.append((
                    "view_change", {"view": int(new_view), "alive": alive_c},
                    {"recovered": (np.asarray(got["cid"], np.int64) << 32)
                     | np.asarray(got["rid"], np.int64)}))
                return res
            return wrapper

        return patched((DomEngine, "run_epoch", run_epoch),
                       (DomEngine, "run_epoch_window", run_epoch_window),
                       (ReplicaLogState, "on_crash", on_crash),
                       (ReplicaLogState, "drop_uids", drop_uids),
                       (ReplicaLogState, "view_change", view_change))


# ---------------------------------------------------------------------------
class Spans:
    """Wall-time spans (time.time) around the calls into each layer."""

    LAYERS = (
        ("repro.sim.workload", "WorkloadDriver", "inject_open_loop", "inject"),
        ("repro.core.engine", "SampleStage", "run", "sample"),
        ("repro.core.engine", "FusedEpochStage", "run", "fused"),
        ("repro.core.engine", "DeliverStage", "run", "deliver"),
        ("repro.core.engine", "LogStage", "run", "log"),
    )

    def __init__(self):
        self.spans: dict = {}

    def clear(self) -> None:
        self.spans = {}

    def _wrap(self, label: str):
        def make(orig):
            def wrapper(*a, **kw):
                t0 = time.time()
                try:
                    return orig(*a, **kw)
                finally:
                    self.spans.setdefault(label, []).append((t0, time.time()))
            return wrapper
        return make

    def installed(self):
        import importlib

        from repro.core.engine import DomEngine

        triples = []
        for mod, cls, meth, label in self.LAYERS:
            klass = getattr(importlib.import_module(mod), cls)
            triples.append((klass, meth, self._wrap(label)))
        spans = self

        def advance_sync(orig):
            def wrapper(self, t_end):
                if self.clocksync is None:
                    return orig(self, t_end)
                t0 = time.time()
                try:
                    return orig(self, t_end)
                finally:
                    spans.spans.setdefault("sync", []).append((t0, time.time()))
            return wrapper

        triples.append((DomEngine, "advance_sync", advance_sync))
        return patched(*triples)


class CompileWatch:
    """JAX's compile spans and persistent-cache misses in this process."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.spans: list = []
        self.cache_misses = 0
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start, end, **kw):
        if event in self.EVENTS:
            self.spans.append((start, end))

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_time_span_listener(self._on_span)
        jax.monitoring.unregister_event_listener(self._on_event)


def signature(args, kwargs) -> str:
    def one(x):
        if isinstance(x, np.ndarray):
            return f"{x.dtype}{list(x.shape)}"
        return type(x).__name__
    return ";".join([one(a) for a in args]
                    + [f"{k}={one(v)}" for k, v in sorted(kwargs.items())])


class ProgramCapture:
    """The first call of each epoch program (per operand signature)."""

    def __init__(self):
        self.calls: list = []
        self._seen: set = set()

    def installed(self):
        from repro.core.engine import ComputeTier

        cap = self

        class Capturing:
            def __init__(self, fn):
                self.fn = fn

            def __call__(self, *a, **kw):
                key = (id(self.fn), signature(a, kw))
                if key not in cap._seen:
                    cap._seen.add(key)
                    cap.calls.append((self.fn, a, kw))
                return self.fn(*a, **kw)

        def factory(orig):
            def wrapper(self, *a, **kw):
                return Capturing(orig(self, *a, **kw))
            return wrapper

        return patched((ComputeTier, "epoch_step", factory),
                       (ComputeTier, "epoch_scan", factory))


# ---------------------------------------------------------------------------
class DeviceTrace:
    """A JAX profiler trace around one repetition; reduced after the window
    (`reduce`).  The trace goes to a temporary directory that `reduce`
    removes."""

    MARK = "bench_repetition"

    def __init__(self):
        self.dir: Optional[str] = None
        self.wall: tuple = (0.0, 0.0)
        self.busy_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.ops: dict = {}
        self.breakdown: Optional[dict] = None

    def __enter__(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(self.MARK)
        self._ann.__enter__()
        self.wall = (time.time(), 0.0)
        return self

    def __exit__(self, *exc):
        import jax

        self.wall = (self.wall[0], time.time())
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return False

    def reduce(self, spans: dict) -> None:
        """Busy time, every device op's time, the top ones, and idle time
        by host activity (``spans``: label -> [(t0, t1)] wall seconds)."""
        from pathlib import Path

        from bench import trace as tr

        try:
            files = sorted(Path(self.dir).rglob("*.xplane.pb"))
            if not files:
                return
            import jax

            data = jax.profiler.ProfileData.from_file(str(files[-1]))
            red = tr.reduce(data, self.MARK, self.wall, spans)
            if red is not None:
                self.busy_s = red["busy_s"]
                self.window_s = red["window_s"]
                self.ops = red["ops"]
                self.breakdown = {"device_ops": red["device_ops"],
                                  "idle_gaps": red["idle_gaps"]}
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
