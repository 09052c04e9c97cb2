"""The benchmark's own copy of the open-loop arrival arithmetic.

The served path draws its requests with `WorkloadDriver.inject_open_loop`:
one `numpy.random.default_rng(seed)` stream, clients in id order, and for
each request an exponential gap, a key (uniform integer, or a zipf inverse
CDF draw) and a read/write draw, in that order.  This module repeats that
draw order without importing the program, so the benchmark knows how many
requests a seed makes, when each is due and which key it carries.  A
repetition whose request stream differs from this copy is not the work the
benchmark measures.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Stream:
    """Every request one repetition submits, in submission order."""

    t: np.ndarray        # [n] submit time (simulated s)
    cid: np.ndarray      # [n] client id
    rid: np.ndarray      # [n] per-client request id, from 0
    kcls: np.ndarray     # [n] key class: index of the key's first appearance

    @property
    def n(self) -> int:
        return int(self.t.size)

    @property
    def uids(self) -> np.ndarray:
        return (self.cid << 32) | self.rid


def _key(rng: np.random.Generator, n_keys: int, theta: float) -> int:
    if theta <= 0.0:
        return int(rng.integers(0, n_keys))
    u = rng.random()
    x = n_keys * (u ** (1.0 / (1.0 - min(theta, 0.999))))
    return min(int(x), n_keys - 1)


def open_loop_stream(traffic: dict, n_clients: int, seed: int) -> Stream:
    """The requests of one open-loop repetition, drawn from ``seed``."""
    if traffic.get("mode", "open") != "open":
        raise ValueError(f"traffic {traffic.get('name')!r}: only open-loop "
                         "mixes are supported")
    if float(traffic.get("multiop_ratio", 0.0)) > 0.0:
        raise ValueError(f"traffic {traffic.get('name')!r}: multi-key "
                         "requests are not supported")
    rate = float(traffic["rate_per_client"])
    warmup = float(traffic["warmup"])
    duration = float(traffic["duration"])
    read_ratio = float(traffic["read_ratio"])
    skew = float(traffic["skew"])
    n_keys = int(traffic["n_keys"])
    rng = np.random.default_rng(seed)
    t_out: list[float] = []
    c_out: list[int] = []
    r_out: list[int] = []
    k_out: list[int] = []
    classes: dict[int, int] = {}
    for cid in range(n_clients):
        t = warmup
        rid = 0
        while t < duration:
            t += rng.exponential(1.0 / rate)
            key = _key(rng, n_keys, skew)
            rng.random()                # the read/write draw
            t_out.append(t)
            c_out.append(cid)
            r_out.append(rid)
            k_out.append(classes.setdefault(key, len(classes)))
            rid += 1
    return Stream(t=np.asarray(t_out, np.float64),
                  cid=np.asarray(c_out, np.int64),
                  rid=np.asarray(r_out, np.int64),
                  kcls=np.asarray(k_out, np.int64))
