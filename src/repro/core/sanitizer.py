"""Layer 3 of the determinism contract: the runtime invariant sanitizer.

`SanitizerTier` wraps any ComputeTier and checks per-epoch invariants on the
finished `EpochState` -- the runtime complement to the static linter
(`repro.analysis.lint`) and the jaxpr trace pass:

  * no NaN in deadlines / arrivals / release / commit times;
  * admitted-mask ⊆ alive-mask (a dead replica admits nothing);
  * admitted ⟹ finite local arrival (you cannot admit what never arrived);
  * finite release ⟹ admitted, and release == max(deadline, arrival) in the
    receiver's local clock frame (modulo the documented fp round-trip when
    clock-fault offsets shift frames);
  * release_floor respected: nothing releases before the StartView instant;
  * watermark monotonicity: per receiver, release order IS deadline order
    (the paper's DOM guarantee) -- capped leader entries (SD.2.4) are the
    documented exception and are exempted exactly as `_apply_deadline_cap`
    computes them;
  * commit sanity: committed ⟺ finite commit time; fast ⟹ committed;
  * pre-stamped deadline preservation: an entry carrying a fixed global
    deadline (a sharded MultiOp sub-entry) keeps it bit-for-bit -- stamping
    must never re-derive it, or the cross-group atomic-order guarantee dies.

The wrapper is PURE delegation -- every compute call goes to the inner tier
untouched, `name` reports the inner tier's name, and the fused epoch
programs are the inner tier's (shared with every bare tier of its class)
-- so a sanitized run is bit-for-bit identical to an unwrapped one
(asserted by tests/test_sanitizer.py).

Enable via `VectorizedConfig(sanitize=True)` or the ``REPRO_SANITIZE=1``
environment variable; the CI recovery smoke runs with it on.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import numpy as np

from repro.core.engine import ComputeTier, make_tier

if TYPE_CHECKING:
    from repro.core.engine import DomEngine, EpochState

# fp slack for cross-frame round trips (release - off + off) under
# clock-fault offsets; exact-frame checks still compare equal because every
# tier computes release as the same np.maximum on the same operands
_EPS = 1e-9


class SanitizerError(AssertionError):
    """An epoch violated a runtime invariant of the DOM data plane."""


class SanitizerTier(ComputeTier):
    """Transparent ComputeTier wrapper with per-epoch invariant checks."""

    def __init__(self, inner):
        self.inner = make_tier(inner)
        self.epochs_checked = 0
        self.violations: list[str] = []     # kept for post-mortem inspection

    # -- pure delegation (bit-for-bit transparency) --------------------------
    @property
    def name(self) -> str:          # summaries/labels report the inner tier
        return self.inner.name

    @property
    def pad_batches(self) -> bool:
        return self.inner.pad_batches

    @property
    def fused(self) -> bool:
        return self.inner.fused

    def release_schedule(self, deadlines, arrivals):
        return self.inner.release_schedule(deadlines, arrivals)

    def deadline_order(self, deadlines):
        return self.inner.deadline_order(deadlines)

    def admit_traced(self, deadlines, arrivals):
        return self.inner.admit_traced(deadlines, arrivals)

    def order_traced(self, deadlines):
        return self.inner.order_traced(deadlines)

    def epoch_step(self, f: int, use_kcls: bool, use_cap: bool = False):
        return self.inner.epoch_step(f, use_kcls, use_cap=use_cap)

    def epoch_scan(self, f: int, use_kcls: bool, use_cap: bool = False):
        return self.inner.epoch_scan(f, use_kcls, use_cap=use_cap)

    # -- the invariant checks ------------------------------------------------
    def check_epoch(self, s: "EpochState", eng: "DomEngine") -> None:
        """Validate one finished EpochState; raise SanitizerError with every
        violated invariant (called by DomEngine.run_epoch after the stages).
        """
        bad: list[str] = []
        n = s.t.size
        if n == 0 or s.deadlines is None:
            self.epochs_checked += 1
            return
        d = s.deadlines
        adm = s.admitted
        rel = s.release
        off = s.clock_arr_off          # [N, R] or None
        a_loc = s.arrivals if off is None else s.arrivals + off
        rel_loc = rel if off is None else rel + off

        # capped leader entries (SD.2.4): released at arrival, slow-path
        # only -- the one documented deadline-order exception
        cap = float(getattr(eng.cfg, "deadline_cap", 0.0) or 0.0)
        capped = np.zeros(n, bool)
        if cap > 0.0:
            a_lead = a_loc[:, s.leader]
            capped = np.isfinite(a_lead) & (d > a_lead + cap)

        for label, arr in (("deadlines", d), ("arrivals", s.arrivals),
                           ("release", rel), ("commit_time", s.commit_time)):
            if arr is not None and np.isnan(arr).any():
                bad.append(f"NaN in {label}")

        if adm is not None:
            dead = ~s.alive
            if dead.any() and adm[:, dead].any():
                bad.append("admitted-mask exceeds alive-mask: dead "
                           f"replica(s) {np.flatnonzero(dead).tolist()} "
                           "admitted entries")
            ghost = adm & ~np.isfinite(a_loc)
            if ghost.any():
                bad.append(f"{int(ghost.sum())} admitted cell(s) with "
                           "non-finite local arrival")

        if rel is not None and adm is not None:
            fin_rel = np.isfinite(rel)
            if (fin_rel & ~adm).any():
                bad.append("finite release on non-admitted cell(s)")
            # release == max(deadline, local arrival) in the local frame,
            # except capped leader cells (released at arrival)
            expect = np.where(adm, np.maximum(d[:, None], a_loc), np.inf)
            mask = adm & np.isfinite(expect)
            if capped.any():
                mask[capped, s.leader] = False
            if not np.allclose(rel_loc[mask], expect[mask],
                               rtol=0.0, atol=_EPS):
                worst = float(np.max(np.abs(rel_loc[mask] - expect[mask])))
                bad.append("release != max(deadline, arrival) in the local "
                           f"frame (max |err| = {worst:.3e})")
            if s.release_floor > 0.0 and fin_rel.any() \
                    and float(rel[fin_rel].min()) < s.release_floor - _EPS:
                bad.append(
                    f"release below release_floor={s.release_floor!r}: "
                    f"min release {float(rel[fin_rel].min())!r}")
            # watermark monotonicity: per receiver, release order is
            # deadline order among admitted entries (local frame)
            for r in range(a_loc.shape[1]):
                ok = adm[:, r] & np.isfinite(rel_loc[:, r])
                if capped.any() and r == s.leader:
                    ok &= ~capped
                if ok.sum() < 2:
                    continue
                order = np.lexsort((d[ok], rel_loc[ok, r]))
                ds = d[ok][order]
                if (np.diff(ds) < 0).any():
                    bad.append(f"receiver {r}: release order violates "
                               "deadline order "
                               f"({int((np.diff(ds) < 0).sum())} pair(s))")

        # pre-stamped deadline preservation: the dl > 0 override is applied
        # LAST in every tier, so the finished deadline must be the fixed
        # global value EXACTLY (bitwise) -- this is what makes a MultiOp's
        # sub-entries sequence at the same slot in every involved group
        if s.pre_deadline is not None:
            fixed = s.pre_deadline > 0.0
            wrong = fixed & (d != s.pre_deadline)
            if wrong.any():
                bad.append(
                    f"{int(wrong.sum())} pre-stamped entr(ies) stamped off "
                    "their fixed global deadline (max |err| = "
                    f"{float(np.max(np.abs(d[wrong] - s.pre_deadline[wrong]))):.3e})")

        if s.committed is not None and s.commit_time is not None:
            if (s.committed != np.isfinite(s.commit_time)).any():
                bad.append("committed mask != finite(commit_time)")
            if s.fast is not None and (s.fast & ~s.committed).any():
                bad.append("fast-path mark on uncommitted entry")

        self.epochs_checked += 1
        if bad:
            self.violations.extend(bad)
            raise SanitizerError(
                f"epoch invariant violation(s) [tier={self.name}, N={n}, "
                f"leader={s.leader}]: " + "; ".join(bad))


__all__ = ["SanitizerTier", "SanitizerError"]
