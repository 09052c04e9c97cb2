"""Benchmark driver: one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # quick mode (~minutes)
  PYTHONPATH=src python -m benchmarks.run --full     # longer sweeps
  PYTHONPATH=src python -m benchmarks.run --only fig8,roofline

Prints ``name,wall_s,rows`` CSV lines at the end (whole-benchmark wall time
in seconds -- per-op timings live inside each benchmark's own rows), plus
per-figure tables, and dumps results/benchmarks.json.  The `dom_scale`
benchmark additionally writes results/BENCH_dom_scale.json for perf
trajectory tracking.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def bench_roofline() -> list[dict]:
    """Roofline table from the dry-run artifacts (if present)."""
    path = "results/dryrun/dryrun_results.json"
    if not os.path.exists(path):
        print("  (no dry-run artifacts yet; run python -m repro.launch.dryrun --all)")
        return []
    from repro.analysis.roofline import analyze, to_markdown

    rows = analyze(path, multi_pod=None)
    print(to_markdown(rows))
    return [{k: v for k, v in r.__dict__.items()} for r in rows]


def bench_kernels(quick=True) -> list[dict]:
    """Micro-bench the jnp reference paths per kernel (CPU wall time; the
    Pallas kernels target TPU and are validated in interpret mode)."""
    import jax
    import jax.numpy as jnp

    rows = []
    from repro.kernels import ref
    from repro.models.attention import flash_attention

    S = 1024 if quick else 4096
    q = jnp.ones((1, S, 8, 64), jnp.bfloat16)
    k = jnp.ones((1, S, 2, 64), jnp.bfloat16)
    fn = jax.jit(lambda q, k: flash_attention(q, k, k))
    fn(q, k).block_until_ready()
    t0 = time.time()
    for _ in range(3):
        fn(q, k).block_until_ready()
    us = (time.time() - t0) / 3 * 1e6
    rows.append({"name": "attention_jnp", "us_per_call": us,
                 "derived": f"S={S} GQA8/2 d64"})

    x = jnp.ones((1, S, 8, 64), jnp.float32)
    dt = jnp.ones((1, S, 8), jnp.float32) * 0.1
    A = -jnp.ones((8,))
    B = jnp.ones((1, S, 64), jnp.float32)
    fn2 = jax.jit(lambda x, dt, A, B: ref.ssd_scan_ref(x, dt, A, B, B))
    fn2(x, dt, A, B).block_until_ready()
    t0 = time.time()
    for _ in range(3):
        fn2(x, dt, A, B).block_until_ready()
    rows.append({"name": "ssd_scan_ref", "us_per_call": (time.time() - t0) / 3 * 1e6,
                 "derived": f"S={S} H8 P64 N64"})

    d = jnp.arange(4096, dtype=jnp.uint32)
    fn3 = jax.jit(lambda d: ref.inchash_ref(d, d, d))
    fn3(d)[0].block_until_ready()
    t0 = time.time()
    for _ in range(10):
        fn3(d)[0].block_until_ready()
    rows.append({"name": "inchash_ref", "us_per_call": (time.time() - t0) / 10 * 1e6,
                 "derived": "n=4096"})
    for r in rows:
        print(f"  {r['name']:20s} {r['us_per_call']:10.1f} us/call  ({r['derived']})")
    return rows


def _bench_dom_scale(quick=True) -> list[dict]:
    from benchmarks.dom_scale import dom_scale

    return dom_scale(quick)


ALL = {}


def main() -> None:
    from benchmarks import figs

    ALL.update({
        "fig1_2": figs.fig1_2_reordering,
        "fig3": figs.fig3_dom,
        "fig8": figs.fig8_latency_throughput,
        "xcheck": figs.backend_crosscheck,
        "fig9": figs.fig9_ablation,
        "fig10": figs.fig10_percentile,
        "fig11": figs.fig11_scalability,
        "fig12": figs.fig12_proxy,
        "fig13": figs.fig13_wan,
        "fig14_15": figs.fig14_15_recovery,
        "fig16_17": figs.fig16_17_disk,
        "apps": figs.app_kv_exchange,
        "appendix_c": figs.appendix_c_workloads,
        "appendix_d": figs.appendix_d_clock,
        "appendix_g": figs.appendix_g_primitives,
        "tiers": figs.tier_sweep,
        "scenarios": figs.scenario_sweep,
        "dom_scale": _bench_dom_scale,
        "kernels": lambda quick: bench_kernels(quick),
        "roofline": lambda quick: bench_roofline(),
    })

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="explicit quick mode (the default; CI uses this "
                         "spelling for its scenario smoke)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--tier", default=None, choices=["numpy", "jit", "pallas"],
                    help="compute tier for the vectorized backend (staged DOM "
                         "engine); default keeps each benchmark's own choice "
                         "and the tier sweep runs all three")
    args = ap.parse_args()
    if args.quick and args.full:
        ap.error("--quick and --full are mutually exclusive")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    figs.DEFAULT_TIER = args.tier
    quick = not args.full
    names = list(ALL) if not args.only else args.only.split(",")

    all_rows: dict = {}
    timing: list = []
    for name in names:
        if name not in ALL:
            print(f"unknown benchmark {name}; have {list(ALL)}")
            continue
        print(f"\n===== {name} =====")
        t0 = time.time()
        try:
            rows = ALL[name](quick)
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc()
            rows = [{"error": str(e)}]
        wall = time.time() - t0
        timing.append((name, wall))
        all_rows[name] = rows
        print(f"  [{name}: {wall:.1f}s wall]")

    # Vectorized-backend rows carry their own "tier" key from summary();
    # _meta records the run-wide selection for reproducibility.
    all_rows["_meta"] = {"tier": args.tier or "default", "full": args.full}
    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.json", "w") as f:
        json.dump(all_rows, f, indent=1, default=str)

    # Label honestly: this is whole-benchmark wall time, not a per-call cost
    # (per-op timings are inside each benchmark's rows).
    print("\nname,wall_s,rows")
    for name, wall in timing:
        print(f"{name},{wall:.2f},{len(all_rows.get(name) or [])}")


if __name__ == "__main__":
    main()
