"""Reduction of a profiler trace of one repetition to device metrics.

Read with `jax.profiler.ProfileData`: device planes (``/device:TPU:<i>``)
hold the device's operations on their ``XLA Ops`` line; host planes hold
the benchmark's marker annotation around the repetition, which fixes the
window and maps the host's wall clock (time.time) onto the trace's clock.

  busy_s      the union of operation intervals inside the window, averaged
              over the device planes;
  window_s    the marker's length;
  ops         every operation's device seconds inside the window, summed
              over the device planes, keyed by the trace's name for it, uncut;
  device_ops  the 10 operation names with the most device time, their
              layouts removed and cut to NAME_CHARS;
  idle_gaps   device idle time inside the window by what the host was doing:
              the innermost host span open at each instant (``other`` where
              none was), the 10 largest.  The spans are the benchmark's
              wrappers, JAX's compile spans and the program's own spans
              (`repro.obs`), which nest inside the wrappers.
"""
from __future__ import annotations

import re
from typing import Optional

import numpy as np

OPS_LINES = ("XLA Ops", "XLA Modules")
TOP = 10
NAME_CHARS = 96


def op_name(text: str) -> str:
    """An operation's HLO text without its layouts, cut to NAME_CHARS."""
    return re.sub(r"\{[^{}]*\}", "", text)[:NAME_CHARS]


def device_planes(data) -> list:
    return [p for p in data.planes
            if p.name.startswith("/device:") and "CPU" not in p.name
            and "SparseCore" not in p.name]


def ops_line(plane):
    lines = {ln.name: ln for ln in plane.lines}
    for name in OPS_LINES:
        if name in lines:
            return lines[name]
    return None


def find_mark(data, mark: str):
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == mark:
                    return ev
    return None


def merge(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(data, mark: str, wall: tuple, spans: dict) -> Optional[dict]:
    """``wall`` is the (start, end) wall time of the marked repetition and
    ``spans`` the host spans by label, in wall seconds."""
    ev = find_mark(data, mark)
    planes = device_planes(data)
    if ev is None or not planes:
        return None
    t0, t1 = float(ev.start_ns), float(ev.end_ns)
    offset = t0 - wall[0] * 1e9            # trace ns = wall s * 1e9 + offset
    busy_each = []
    ops: dict = {}
    busy_all: list = []
    for plane in planes:
        line = ops_line(plane)
        if line is None:
            continue
        iv = []
        for e in line.events:
            a, b = max(float(e.start_ns), t0), min(float(e.end_ns), t1)
            if b > a:
                iv.append((a, b))
                ops[e.name] = ops.get(e.name, 0.0) + (b - a) * 1e-9
        merged = merge(iv)
        busy_each.append(sum(b - a for a, b in merged) * 1e-9)
        busy_all.extend(merged)
    if not busy_each:
        return None
    window_s = (t1 - t0) * 1e-9
    by_short: dict = {}
    for name, s in ops.items():
        short = op_name(name)
        by_short[short] = by_short.get(short, 0.0) + s
    device_ops = sorted(by_short.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": float(np.mean(busy_each)), "window_s": window_s,
            "ops": ops, "device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": idle_by_host(merge(busy_all), spans, t0, t1, offset)}


def idle_by_host(busy: list, spans: dict, t0: float, t1: float,
                 offset: float) -> list:
    """Idle device time in [t0, t1] (trace ns) by the innermost host span,
    at microsecond resolution."""
    n = int((t1 - t0) // 1000) + 1
    idle = np.ones(n, bool)
    for a, b in busy:
        idle[int((a - t0) // 1000): int((b - t0) // 1000) + 1] = False
    labels = ["other"] + sorted(spans)
    owner = np.zeros(n, np.int16)
    painted = []
    for k, name in enumerate(labels[1:], start=1):
        for a, b in spans[name]:
            painted.append((b - a, k, a * 1e9 + offset, b * 1e9 + offset))
    for _, k, a, b in sorted(painted, reverse=True):    # longest first
        lo, hi = max(a, t0), min(b, t1)
        if hi > lo:
            owner[int((lo - t0) // 1000): int((hi - t0) // 1000) + 1] = k
    us = np.bincount(owner[idle], minlength=len(labels))
    out = [[labels[k], float(us[k]) * 1e-6] for k in range(len(labels))
           if us[k] > 0]
    return sorted(out, key=lambda kv: -kv[1])[:TOP]
