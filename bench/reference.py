"""Plain reference of the simulated Nezha data plane, and the comparison.

The reference imports nothing of the program.  It recomputes, from the
inputs of every epoch the timed path ran, what Nezha's semantics say the
epoch produces:

  stamping      deadline = proxy stamp + sliding-percentile OWD bound +
                clock-error margin, clamped to D (paper Sec 4, Eq. 1);
  DOM           each receiver's early buffer, processed message by message
                in arrival order: a message enters it iff its deadline
                exceeds every deadline already released when it arrives;
                it releases at max(deadline, arrival) (Sec 4);
  commit        fast path: the leader and 1+f+ceil(f/2)-1 more replicas
                admitted it with the leader's log prefix in its key class;
                slow path: the leader's log modification reaches f
                followers (Sec 5-6); the earlier of the two wins;
  delivery      the client holds the reply at commit + proxy->client delay;
  replica log   committed entries enter the one synced log in deadline order,
                uncommitted admitted ones are speculative, and a view change
                runs MERGE-LOG: entries in ceil(f/2)+1 qualified logs past the
                synced tail, in (deadline, client, request) order (Alg 4);
  sync rounds   per node, the median offset over peers whose probe RTT is at
                most 3x the median RTT, and 1.4826 * MAD * safety + floor.

What the reference takes from the run are the draws of the simulated
environment (arrival times of attempts, network delays and drops, clock
read errors, which replicas are alive, when a view change completes) and
the deployment's configuration.  It takes none of the program's results:
bounds, deadlines, admissions, releases, commit times, verdicts and the log
are all its own.  ``ft`` is the float type of every time computation:
float64 as the configuration states, float32 for the control.
"""
from __future__ import annotations

import heapq
import math
from dataclasses import dataclass, field

import numpy as np


def pack(cid, rid) -> np.ndarray:
    return (np.asarray(cid, np.int64) << 32) | np.asarray(rid, np.int64)


@dataclass(frozen=True)
class Params:
    """The deployment's configuration, as the reference needs it."""

    f: int
    window: int                 # OWD samples per replica in the pool
    percentile: float
    clamp_d: float
    batch_delay: float          # leader log-modification batching
    client_timeout: float
    sigma_safety: float = 1.5
    sigma_floor: float = 200e-9

    @property
    def n(self) -> int:
        return 2 * self.f + 1

    @property
    def fast_quorum(self) -> int:
        return 1 + self.f + math.ceil(self.f / 2)

    @property
    def slow_quorum(self) -> int:
        return 1 + self.f


def percentile(pool: np.ndarray, q: float, ft) -> float:
    """Linear-interpolation percentile of ``pool``, computed in ``ft``."""
    srt = np.sort(pool.astype(ft))
    pos = ft(q / 100.0) * ft(srt.size - 1)
    lo = int(np.floor(pos))
    hi = int(np.ceil(pos))
    frac = ft(pos - ft(lo))
    return ft(srt[lo] + frac * (srt[hi] - srt[lo]))


def early_buffer(deadlines: np.ndarray, arrivals: np.ndarray) -> np.ndarray:
    """DOM admission at every receiver: [N], [N, R] -> [N, R] bool.

    Messages are taken in arrival order (ties in index order).  Admitted
    deadlines wait in a heap until they release; a message is admitted iff
    its deadline exceeds the largest deadline released by its arrival.
    """
    n, r_count = arrivals.shape
    admitted = np.zeros((n, r_count), bool)
    d = deadlines.tolist()
    for r in range(r_count):
        col = arrivals[:, r]
        order = np.argsort(col, kind="stable").tolist()
        a = col.tolist()
        waiting: list[float] = []
        released = -math.inf
        for i in order:
            ai = a[i]
            if not math.isfinite(ai):
                break
            while waiting and waiting[0] <= ai:
                released = max(released, heapq.heappop(waiting))
            if d[i] > released:
                admitted[i, r] = True
                heapq.heappush(waiting, d[i])
    return admitted


def sync_round(theta: np.ndarray, rtt: np.ndarray, p: Params, ft):
    """Per-node offset estimate and error bound of one probe round."""
    m = theta.shape[0]
    est = np.zeros(m, ft)
    sigma = np.zeros(m, ft)
    for i in range(m):
        fin = np.isfinite(rtt[i])
        if not fin.any():
            sigma[i] = ft(p.sigma_floor)
            continue
        med_rtt = np.median(rtt[i][fin].astype(ft))
        valid = fin & (rtt[i].astype(ft) <= ft(3.0) * med_rtt)
        th = theta[i][valid].astype(ft)
        e = np.median(th) if th.size else ft(0.0)
        mad = np.median(np.abs(th - e)) if th.size else ft(0.0)
        est[i] = e
        sigma[i] = mad * ft(1.4826) * ft(p.sigma_safety) + ft(p.sigma_floor)
    return est, sigma


class Epochs:
    """The data plane of one run, epoch after epoch (the bound's OWD pool
    is carried across epochs)."""

    def __init__(self, p: Params, ft=np.float64):
        self.p = p
        self.ft = ft
        self.pool = np.zeros(0, ft)

    def run(self, e: dict) -> dict:
        p, ft = self.p, self.ft
        n_rep = p.n
        alive = np.asarray(e["alive"], bool)
        leader = int(e["leader"])
        t = e["t"].astype(ft)
        owd = e["owd_pr"].astype(ft)
        if e.get("pair_delay") is not None:
            owd = owd + e["pair_delay"].astype(ft)
        stamp_off = e.get("clock_stamp_off")
        arr_off = e.get("clock_arr_off")
        if stamp_off is not None:
            stamp_off = stamp_off.astype(ft)
            arr_off = arr_off.astype(ft)
        # -- stamping: the proxies' bound from the sliding OWD pool -------
        obs = owd if stamp_off is None else owd + arr_off - stamp_off[:, None]
        self.pool = np.concatenate([self.pool, obs.ravel()])[-p.window * n_rep:]
        clamp = ft(p.clamp_d)
        if self.pool.size == 0:
            bound = clamp
        else:
            bound = ft(percentile(self.pool, p.percentile, ft) + ft(e["margin"]))
            if not (ft(0.0) < bound < clamp):
                bound = clamp
        stamp = t + e["c2p"].astype(ft)
        deadlines = stamp + bound
        if stamp_off is not None:
            deadlines = deadlines + stamp_off
        dropped = np.asarray(e["drop_pr"], bool) | ~alive[None, :]
        if e.get("pair_drop") is not None:
            dropped = dropped | np.asarray(e["pair_drop"], bool)
        arrivals = np.where(dropped, ft(np.inf), stamp[:, None] + owd)
        arrivals = np.maximum(arrivals, ft(e["release_floor"]))
        if e.get("dies_at") is not None:
            dies = np.asarray(e["dies_at"]).astype(ft)
            arrivals = np.where(arrivals > dies[None, :], ft(np.inf), arrivals)
        # -- DOM: early buffers in each receiver's local clock frame ------
        local = arrivals if arr_off is None else arrivals + arr_off
        admitted = early_buffer(deadlines, local)
        release = np.where(admitted, np.maximum(deadlines[:, None], local),
                           ft(np.inf))
        if arr_off is not None:
            release = release - arr_off
        # -- commit: prefix agreement with the leader per key class -------
        kcls = e.get("kcls")
        order = np.argsort(deadlines, kind="stable")
        if kcls is not None:
            order = order[np.argsort(kcls[order], kind="stable")]
        adm_sorted = admitted[order]
        disagree = (adm_sorted != adm_sorted[:, [leader]]).astype(np.int64)
        before = np.cumsum(disagree, axis=0) - disagree
        if kcls is not None and order.size:
            ks = kcls[order]
            start = np.r_[True, ks[1:] != ks[:-1]]
            first = np.maximum.accumulate(np.where(start, np.arange(ks.size), 0))
            before = before - before[first]
        prefix_ok = np.empty_like(before, dtype=bool)
        prefix_ok[order] = before == 0
        reply = e["reply_owd"].astype(ft)
        reply = np.where(alive[None, :], reply, ft(np.inf))
        ok = admitted & prefix_ok & admitted[:, [leader]]
        ok_t = np.where(ok, release + reply, ft(np.inf))
        fq, sq = p.fast_quorum, p.slow_quorum
        kth = (np.sort(ok_t, axis=1)[:, fq - 1] if fq <= n_rep
               else np.full(t.size, ft(np.inf)))
        fast_t = np.where(np.isfinite(ok_t[:, leader]), kth, ft(np.inf))
        fast_t = np.maximum(fast_t, ok_t[:, leader])
        arr_lead = arrivals[:, leader]
        leader_t = np.where(admitted[:, leader], release[:, leader], arr_lead)
        leader_t = np.where(np.isfinite(arr_lead), leader_t, ft(np.inf))
        fin = np.isfinite(reply)
        fetch = (ft(3.0) * ft(reply[fin].sum(dtype=ft) / ft(fin.sum()))
                 if fin.any() else ft(np.inf))
        sync_t = leader_t[:, None] + ft(p.batch_delay) + reply
        have_t = np.where(np.isfinite(arrivals), arrivals,
                          leader_t[:, None] + fetch)
        slow_reply = np.maximum(sync_t, have_t) + reply
        slow_reply[:, leader] = leader_t + reply[:, leader]
        slow_t = np.maximum(np.sort(slow_reply, axis=1)[:, sq - 1],
                            leader_t + reply[:, leader])
        commit_t = np.minimum(fast_t, slow_t)
        committed = np.isfinite(commit_t)
        # -- delivery at the client ---------------------------------------
        at_client = commit_t + e["p2c"].astype(ft)
        delivered = committed & np.isfinite(at_client - e["t0"].astype(ft))
        out = {"deadlines": deadlines, "admitted": admitted,
               "committed": committed, "fast": (fast_t <= slow_t) & committed,
               "delivered": delivered, "commit_at_client": at_client}
        if e.get("sync_theta") is not None:
            out["sync_est"], out["sync_sigma"] = sync_round(
                e["sync_theta"], e["sync_rtt"], p, ft)
        return out


@dataclass
class Log:
    """The replicas' logs: one synced log plus speculative tails."""

    p: Params
    view: int = 0
    uids: list = field(default_factory=list)
    recovered: list = field(default_factory=list)
    tail: float = -math.inf
    replay: np.ndarray = field(default_factory=lambda: np.empty(0, np.int64))
    n_recovered: int = 0
    n_dropped: int = 0

    def __post_init__(self):
        self.last_normal = np.zeros(self.p.n, np.int64)
        self._clear_spec()

    def _clear_spec(self):
        self.spec_d = np.empty(0)
        self.spec_uid = np.empty(0, np.int64)
        self.spec_adm = np.empty((0, self.p.n), bool)

    def _append(self, deadlines, uids, recovered=False):
        self.uids.extend(np.asarray(uids).tolist())
        self.recovered.extend([recovered] * len(uids))
        if len(uids):
            self.tail = float(deadlines[-1])

    def epoch(self, e: dict, out: dict) -> None:
        committed = out["committed"]
        delivered = out["delivered"]
        row_uids = pack(e["cid"], e["rid"])
        order = np.argsort(out["deadlines"], kind="stable")
        idx = order[committed[order]]
        uids = row_uids[idx]
        if self.replay.size:
            again = np.isin(uids, self.replay)
            if again.any():
                done = uids[again][delivered[idx[again]]]
                self.replay = self.replay[~np.isin(self.replay, done)]
                idx, uids = idx[~again], uids[~again]
        self._append(out["deadlines"][idx], uids)
        lost_reply = ~delivered[idx]
        if lost_reply.any():
            self.replay = np.concatenate([self.replay, uids[lost_reply]])
        self.last_normal[np.asarray(e["alive"], bool)] = self.view
        spec = ~committed & out["admitted"].any(axis=1)
        if spec.any() and self.replay.size:
            spec &= ~np.isin(row_uids, self.replay)
        if self.spec_uid.size:
            keep = ~np.isin(self.spec_uid, row_uids[spec | committed])
            self.spec_d = self.spec_d[keep]
            self.spec_uid = self.spec_uid[keep]
            self.spec_adm = self.spec_adm[keep]
        if spec.any():
            self.spec_d = np.concatenate([self.spec_d,
                                          out["deadlines"][spec].astype(float)])
            self.spec_uid = np.concatenate([self.spec_uid, row_uids[spec]])
            self.spec_adm = np.concatenate([self.spec_adm,
                                            out["admitted"][spec]])

    def crash(self, rid: int) -> None:
        self.spec_adm[:, rid] = False
        self.last_normal[rid] = -1

    def abandon(self, uids: np.ndarray) -> None:
        keep = ~np.isin(self.spec_uid, uids)
        self.spec_d = self.spec_d[keep]
        self.spec_uid = self.spec_uid[keep]
        self.spec_adm = self.spec_adm[keep]

    def view_change(self, new_view: int, alive: np.ndarray) -> np.ndarray:
        """MERGE-LOG; returns the recovered uids in log order."""
        alive = np.asarray(alive, bool)
        qualified = alive & (self.last_normal == self.last_normal[alive].max())
        votes = self.spec_adm[:, qualified].sum(axis=1)
        keep = (votes >= math.ceil(self.p.f / 2) + 1) & (self.spec_d >= self.tail)
        cid, rid = self.spec_uid >> 32, self.spec_uid & 0xFFFFFFFF
        order = [i for i in np.lexsort((rid, cid, self.spec_d)) if keep[i]]
        seen: set = set()
        merged = []
        for i in order:
            u = int(self.spec_uid[i])
            if u not in seen:
                seen.add(u)
                merged.append(i)
        merged = np.asarray(merged, np.int64)
        self._append(self.spec_d[merged], self.spec_uid[merged], recovered=True)
        self.n_recovered += merged.size
        self.n_dropped += self.spec_uid.size - merged.size
        self.view = new_view
        self.last_normal[alive] = new_view
        recovered = self.spec_uid[merged]
        self._clear_spec()
        return recovered


def replay_run(records: list, p: Params, ft=np.float64):
    """Run the reference over one repetition's records, in their order.

    ``records`` holds (kind, inputs, program outputs) triples; the reference
    reads the inputs only.  Returns its outputs, one per record (None where
    a record has none), and its Log."""
    plane = Epochs(p, ft)
    log = Log(p)
    outs: list = []
    for kind, rec, _ in records:
        out = None
        if kind == "epoch":
            out = plane.run(rec)
            log.epoch(rec, out)
        elif kind == "crash":
            log.crash(rec["rid"])
        elif kind == "abandon":
            log.abandon(pack(rec["cid"], rec["rid"]))
        elif kind == "view_change":
            out = {"recovered": log.view_change(rec["view"], rec["alive"])}
        outs.append(out)
    return outs, log


def _gap(a: np.ndarray, b: np.ndarray) -> float:
    """Widest |a - b|; inf where one side is finite and the other is not."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    fa, fb = np.isfinite(a), np.isfinite(b)
    if (fa != fb).any():
        return math.inf
    both = fa & fb
    return float(np.max(np.abs(a[both] - b[both]), initial=0.0))


def compare(program: list, ref: list, log_prog: np.ndarray,
            log_ref: "Log") -> dict:
    """What the timed path produced against the reference, one repetition.

    ``program`` holds the program's outputs per record, in the layout of the
    reference's ``ref``; ``log_prog`` is its synced log (packed uids in log
    order)."""
    verdicts = 0
    deadline_gap = 0.0
    commit_gap = 0.0
    sync_gap = 0.0
    recovered = 0
    for got, want in zip(program, ref):
        if want is None:
            continue
        if "recovered" in want:
            g, w = np.asarray(got["recovered"]), want["recovered"]
            m = min(g.size, w.size)
            recovered += abs(g.size - w.size) + int((g[:m] != w[:m]).sum())
            continue
        for key in ("admitted", "committed", "fast", "delivered"):
            verdicts += int((np.asarray(got[key], bool) != want[key]).sum())
        deadline_gap = max(deadline_gap, _gap(got["deadlines"], want["deadlines"]))
        both = np.asarray(got["delivered"], bool) & want["delivered"]
        commit_gap = max(commit_gap, _gap(
            np.asarray(got["commit_at_client"])[both],
            want["commit_at_client"][both]))
        if "sync_est" in want:
            sync_gap = max(sync_gap, _gap(got["sync_est"], want["sync_est"]),
                           _gap(got["sync_sigma"], want["sync_sigma"]))
    want_log = np.asarray(log_ref.uids, np.int64)
    m = min(want_log.size, log_prog.size)
    log_wrong = abs(int(want_log.size) - int(log_prog.size)) + int(
        (want_log[:m] != log_prog[:m]).sum())
    return {"verdicts_wrong": verdicts, "deadline_gap_s": deadline_gap,
            "commit_time_gap_s": commit_gap, "sync_gap_s": sync_gap,
            "log_entries_wrong": log_wrong + recovered}
