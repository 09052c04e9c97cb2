"""The determinism-contract linter (repro.analysis.lint): seeded-violation
fixtures for each AST pass (dtype-parity, host-sync, RNG-discipline), pragma
and suppression-file semantics, the jaxpr trace-safety layer's detectors,
CLI exit codes, and the repo's clean baseline -- the acceptance criterion
that `python -m repro.analysis.lint src/` exits 0 here and nonzero on any
seeded violation.
"""
import textwrap

import numpy as np
import pytest

from repro.analysis.lint import RULES, lint_paths, run_lint
from repro.analysis.lint.pragmas import (SuppressionFileError,
                                         collect_pragmas,
                                         parse_suppression_file)
from repro.analysis.lint.passes import lint_module


def _lint_src(source: str, path: str = "mod.py"):
    source = textwrap.dedent(source)
    return lint_module(path, source, collect_pragmas(source))


def _rules(findings, active_only: bool = True):
    return sorted(f.rule for f in findings
                  if not (active_only and f.suppressed))


# ---------------------------------------------------------------------------
# dtype-parity pass (DP001/DP002)
# ---------------------------------------------------------------------------
def test_dp001_flags_f32_cast_on_time_values():
    found = _lint_src("""
        import numpy as np

        def stamp(deadlines):
            deadlines32 = deadlines.astype(np.float32)
            return deadlines32
    """)
    assert "DP001" in _rules(found)


def test_dp002_flags_jnp_time_compute_without_x64():
    found = _lint_src("""
        import jax.numpy as jnp

        def schedule(deadlines, arrivals):
            return jnp.maximum(deadlines, arrivals[:, 0])
    """)
    assert "DP002" in _rules(found)


def test_dp002_clean_under_enable_x64():
    found = _lint_src("""
        import jax
        import jax.numpy as jnp

        def schedule(deadlines, arrivals):
            with jax.enable_x64(True):
                return jnp.maximum(deadlines, arrivals[:, 0])
    """)
    assert _rules(found) == []


def test_dp002_enable_x64_false_is_not_an_x64_entry():
    """`jax.enable_x64(False)` switches 64-bit types off (the Pallas
    wrappers trace their int32 kernels that way), so it must not count
    as the x64 scope that time compute needs."""
    found = _lint_src("""
        import jax
        import jax.numpy as jnp

        def schedule(deadlines, arrivals):
            with jax.enable_x64(False):
                return jnp.maximum(deadlines, arrivals[:, 0])
    """)
    assert "DP002" in _rules(found)


def test_dp002_x64_reaches_intra_module_callees():
    """Safety propagates through the call graph, including function
    REFERENCES passed as arguments (jax.vmap(f)) -- the pattern
    `dom_release_schedule` uses after its x64 fix."""
    found = _lint_src("""
        import jax
        import jax.numpy as jnp

        def _one_receiver(deadlines):
            return jnp.sort(deadlines)

        def schedule(deadlines):
            with jax.enable_x64(True):
                return jax.vmap(_one_receiver)(deadlines)
    """)
    assert _rules(found) == []


def test_dp_f32_time_keys_flagged_even_in_kernel_code():
    """The span-relative-f32 pragma class is gone: the Pallas kernels use
    exact int32 key words now, so an f32 cast on time values is an active
    DP001/DP002 finding no matter where it appears."""
    found = _lint_src("""
        import jax.numpy as jnp

        def _kernel_keys(deadlines, span):
            rel = jnp.float32(deadlines - deadlines[0])
            return jnp.minimum(rel, span)
    """)
    assert "DP001" in _rules(found)
    assert "DP002" in _rules(found)


# ---------------------------------------------------------------------------
# host-sync pass (HS001-HS004)
# ---------------------------------------------------------------------------
def test_hs001_flags_item():
    found = _lint_src("""
        def pull(release_jnp):
            return release_jnp.item()
    """)
    assert "HS001" in _rules(found)


def test_hs002_flags_float_on_device_value():
    found = _lint_src("""
        import jax.numpy as jnp

        def pull(vals):
            out = jnp.max(vals)
            return float(out)
    """)
    assert _rules(found) == ["HS002"]


def test_hs003_flags_np_asarray_on_device_value():
    found = _lint_src("""
        import numpy as np

        def pull(vals):
            out = dom_admit_traced(vals)
            return np.asarray(out)
    """)
    assert _rules(found) == ["HS003"]


def test_hs003_clean_on_host_values():
    found = _lint_src("""
        import numpy as np

        def shape(vals):
            return np.asarray(vals)
    """)
    assert _rules(found) == []


def test_hs004_flags_python_branch_on_traced_value():
    found = _lint_src("""
        import jax

        @jax.jit
        def step(deadlines):
            if deadlines[0] > 0:
                return deadlines
            return -deadlines
    """)
    assert "HS004" in _rules(found)


def test_hs004_allows_is_none_dispatch():
    """`x is None` is a trace-time Python test (static arg dispatch), not a
    branch on a traced value -- the fused step's fault-variant pattern."""
    found = _lint_src("""
        import jax

        @jax.jit
        def step(deadlines, dies_at=None):
            if dies_at is None:
                return deadlines
            return deadlines + dies_at
    """)
    assert "HS004" not in _rules(found)


def test_hs_inventory_includes_suppressed_syncs():
    """The machine-readable round-trip inventory (ROADMAP item 2) keeps
    JUSTIFIED syncs: the device-resident refactor still has to absorb
    them."""
    src = textwrap.dedent("""
        def pull(release_jnp):
            # lint: allow[HS001] boundary pull at the epoch seam
            return release_jnp.item()
    """)
    import pathlib
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        p = pathlib.Path(d) / "mod.py"
        p.write_text(src)
        report = lint_paths([str(p)])
    assert report.exit_code == 0
    inv = report.inventory()
    assert len(inv) == 1 and inv[0]["rule"] == "HS001"
    assert inv[0]["suppressed"] is True


def test_scan_budget_counts_per_epoch_syncs_on_the_fast_path(tmp_path):
    """The --scan-budget gate: a host sync inside a scan-path function is a
    per-epoch regression (even when pragma-justified), UNLESS justified as
    the amortized per-window boundary pull."""
    mod = tmp_path / "mod.py"
    mod.write_text(textwrap.dedent("""
        import numpy as np

        def run_epoch_window(tier, ops):
            scan = tier.epoch_scan(1, use_kcls=False)
            out = scan(ops)
            # lint: allow[HS003] the ONE per-window pull of K epochs
            ys = np.asarray(out)
            # lint: allow[HS002] per-epoch bound pull sneaking back in
            bound = float(out)
            return ys, bound
    """))
    report = lint_paths([str(mod)])
    assert report.exit_code == 0                    # pragmas silence the lint
    over = report.scan_path_syncs()
    assert [f.rule for f in over] == ["HS002"]      # ...not the budget gate
    assert run_lint([str(mod), "--no-trace", "--scan-budget", "0"]) == 1
    # the per-window pull alone stays inside the 0 budget once the
    # regression is justified away too -- symmetry with the repo baseline
    mod.write_text(textwrap.dedent("""
        import numpy as np

        def run_epoch_window(tier, ops):
            scan = tier.epoch_scan(1, use_kcls=False)
            out = scan(ops)
            # lint: allow[HS003] the ONE per-window pull of K epochs
            ys = np.asarray(out)
            return ys
    """))
    assert run_lint([str(mod), "--no-trace", "--scan-budget", "0"]) == 0


def test_repo_scan_fast_path_has_zero_per_epoch_syncs():
    """Acceptance: 0 per-epoch data-plane host round trips on the K-scan
    fast path (the single per-window pull is excluded by its
    justification)."""
    report = lint_paths(["src"], suppression_file="lint-suppressions.txt")
    assert report.scan_path_syncs() == []
    # ...and the gate is not vacuous: the per-window pull IS in the
    # inventory, attributed to the scan path
    scan_hs = [f for f in report.inventory()
               if "run_epoch_window" in f["symbol"]]
    assert len(scan_hs) == 1
    assert "per-window" in scan_hs[0]["justification"]


# ---------------------------------------------------------------------------
# RNG-discipline pass (RNG001/RNG002)
# ---------------------------------------------------------------------------
def test_rng001_flags_global_numpy_rng():
    found = _lint_src("""
        import numpy as np

        def jitter(n):
            return np.random.normal(0.0, 1.0, n)
    """)
    assert "RNG001" in _rules(found)


def test_rng001_allows_owned_generators():
    found = _lint_src("""
        import numpy as np

        def jitter(n, seed):
            rng = np.random.default_rng(seed)
            return rng.normal(0.0, 1.0, n)
    """)
    assert _rules(found) == []


def test_rng002_flags_prng_key_reuse():
    found = _lint_src("""
        import jax

        def sample(shape):
            key = jax.random.PRNGKey(0)
            a = jax.random.normal(key, shape)
            b = jax.random.normal(key, shape)
            return a, b
    """)
    assert _rules(found) == ["RNG002"]


def test_rng002_allows_split_keys():
    found = _lint_src("""
        import jax

        def sample(shape):
            key = jax.random.PRNGKey(0)
            ka, kb = jax.random.split(key)
            a = jax.random.normal(ka, shape)
            b = jax.random.normal(kb, shape)
            return a, b
    """)
    assert _rules(found) == []


# ---------------------------------------------------------------------------
# pragmas + suppression file
# ---------------------------------------------------------------------------
def test_allow_pragma_covers_own_and_next_line():
    found = _lint_src("""
        def pull(release_jnp):
            # lint: allow[HS001] epoch-boundary scalar
            a = release_jnp.item()
            b = release_jnp.item()
            return a, b
    """)
    active = [f for f in found if not f.suppressed]
    assert _rules(found) == ["HS001"]               # only the uncovered line
    assert len(active) == 1


def test_suppression_file_requires_justification(tmp_path):
    bad = tmp_path / "supp.txt"
    bad.write_text("HS001 src/mod.py:pull\n")
    with pytest.raises(SuppressionFileError, match="justification"):
        parse_suppression_file(bad)
    report = lint_paths([str(tmp_path)], suppression_file=str(bad))
    assert report.exit_code == 2                    # config error


def test_suppression_file_matches_and_reports_unused(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(textwrap.dedent("""
        def pull(release_jnp):
            return release_jnp.item()
    """))
    supp = tmp_path / "supp.txt"
    supp.write_text(
        "HS001 mod.py:pull -- documented boundary sync\n"
        "RNG001 mod.py -- never matches anything\n")
    report = lint_paths([str(mod)], suppression_file=str(supp))
    assert report.exit_code == 0
    assert [f.justification for f in report.findings] \
        == ["documented boundary sync"]
    assert report.unused_suppressions == ["RNG001 mod.py"]


# ---------------------------------------------------------------------------
# jaxpr trace-safety layer (TS001-TS003)
# ---------------------------------------------------------------------------
def test_trace_detector_catches_f32_compute():
    import jax
    import jax.numpy as jnp

    from repro.analysis.lint.trace_safety import non_f64_float_ops

    jaxpr = jax.make_jaxpr(lambda x: x * 2.0 + 1.0)(jnp.float32(3.0))
    bad = non_f64_float_ops(jaxpr)
    assert bad and all(d == "float32" for _, d in bad)


def test_trace_detector_catches_host_callbacks():
    import jax

    from repro.analysis.lint.trace_safety import callback_prims

    def f(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2.0, jax.ShapeDtypeStruct((), x.dtype), x)

    jaxpr = jax.make_jaxpr(f)(np.float64(1.0))
    assert callback_prims(jaxpr)


def test_fused_step_would_fail_without_x64():
    """Teeth: the SAME detector flags the fused step when traced without
    enable_x64 -- so TS001 genuinely guards the x64 requirement rather than
    vacuously passing."""
    import jax

    from repro.analysis.lint.trace_safety import (_fused_step_args,
                                                  non_f64_float_ops)
    from repro.core.engine import JitTier

    step = JitTier().epoch_step(1, use_kcls=False)
    jaxpr = jax.make_jaxpr(step)(**_fused_step_args(8, 3))   # no enable_x64
    assert non_f64_float_ops(jaxpr)


def test_trace_safety_baseline_clean():
    """TS001/TS002 on the real fused step + kernel wrappers, TS003 on the
    catalog: the shipped programs honor the contract."""
    from repro.analysis.lint.trace_safety import trace_findings

    assert trace_findings() == []


def test_compile_stability_flags_oversized_catalog():
    from dataclasses import replace

    from repro.analysis.lint.trace_safety import (COMPILE_LIMIT,
                                                  check_compile_stability)
    from repro.sim.scenario import get_scenario

    base = get_scenario("intra-zone")
    blown = [replace(base, name=f"blow-{f}", f=f,
                     overrides={**base.overrides,
                                "commutative": f % 2 == 0})
             for f in range(1, 2 * COMPILE_LIMIT)]
    found = check_compile_stability(blown)
    assert len(found) == 1 and found[0].rule == "TS003"
    assert "compile count" in found[0].message


def test_compile_stability_counts_scan_k_buckets():
    """The K-epochs-per-dispatch axis is part of the compile-count model:
    a scenario that enables the scan adds one program per reachable
    SCAN_K_BUCKETS entry, and blowing the product past the limit via K
    alone is flagged."""
    from dataclasses import replace

    from repro.analysis.lint.trace_safety import (COMPILE_LIMIT,
                                                  check_compile_stability)
    from repro.core.engine import SCAN_K_BUCKETS
    from repro.sim.scenario import get_scenario

    base = get_scenario("intra-zone")
    k_on = replace(base, name="k-on",
                   overrides={**base.overrides,
                              "epochs_per_dispatch": max(SCAN_K_BUCKETS)})
    # one scenario, all K buckets reachable: still well inside the limit
    assert check_compile_stability([k_on]) == []

    # spec keys alone fit under the limit, but x (1 + len(SCAN_K_BUCKETS))
    # K buckets they blow it -- the finding names all three axes
    n_spec = COMPILE_LIMIT // (len(SCAN_K_BUCKETS) + 1)
    many = [replace(k_on, name=f"k-blow-{f}", f=f)
            for f in range(1, n_spec + 1)]
    found = check_compile_stability(many)
    assert len(found) == 1 and found[0].rule == "TS003"
    assert "K buckets" in found[0].message
    assert found[0].extra["k_buckets"] == [1, *sorted(SCAN_K_BUCKETS)]


# ---------------------------------------------------------------------------
# CLI + repo baseline (the acceptance criteria)
# ---------------------------------------------------------------------------
def test_cli_exit_codes(tmp_path, capsys):
    clean = tmp_path / "clean.py"
    clean.write_text("def f(x):\n    return x + 1\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import numpy as np\n\n"
                     "def jitter(n):\n"
                     "    return np.random.normal(0.0, 1.0, n)\n")
    assert run_lint([str(clean), "--no-trace"]) == 0
    assert run_lint([str(dirty), "--no-trace"]) == 1
    out = capsys.readouterr().out
    assert "RNG001" in out
    assert run_lint(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    assert all(rule in listed for rule in RULES)


def test_repo_baseline_is_clean():
    """`python -m repro.analysis.lint src/` exits 0 on the repo: every
    finding fixed or justified-suppressed (AST layer; the trace layer is
    covered by test_trace_safety_baseline_clean)."""
    report = lint_paths(["src"], suppression_file="lint-suppressions.txt")
    assert report.errors == []
    assert report.active == [], report.format()
    assert report.unused_suppressions == []
    assert any(f.suppressed for f in report.findings)   # baseline is honest
