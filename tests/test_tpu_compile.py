"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

Each test hands the TPU compiler one program of the served path at the
chip smoke's batch bucket (N=2048 requests, R=3 replicas) and fails on
whatever the compiler refuses: Mosaic's tiling and dtype rules for the DOM
kernels, XLA:TPU's float64 emulation for the fused epoch step.  Nothing
runs, so these say nothing about results or times on the chip.

The topology is described inside a module fixture, never at import time,
so every test worker collects the same tests.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

N, R = 2048, 3
POOL_WINDOW = 1000          # DomParams.window: the OWD ring pool is W*R


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # keep libtpu's compiler logs out of the temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of the cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(args: dict, sharding) -> dict:
    return {k: jax.ShapeDtypeStruct(np.shape(v), jnp.asarray(v).dtype,
                                    sharding=sharding)
            for k, v in args.items()}


def test_dom_admit_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.dom_admit import dom_admit_pallas

    with jax.enable_x64(True):
        compiled = dom_admit_pallas.lower(
            jax.ShapeDtypeStruct((N,), jnp.float64, sharding=one_chip),
            jax.ShapeDtypeStruct((R, N), jnp.float64, sharding=one_chip),
            interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dom_release_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.dom_release import dom_release_pallas

    with jax.enable_x64(True):
        compiled = dom_release_pallas.lower(
            jax.ShapeDtypeStruct((N,), jnp.float64, sharding=one_chip),
            jax.ShapeDtypeStruct((N,), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.float64, sharding=one_chip),
            interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_jit_step_compiles_for_v5e_within_a_minute(one_chip):
    """The float64 epoch program: admission used to compile for minutes
    at this bucket (a vmapped float64 sort); now seconds."""
    from repro.analysis.lint.trace_safety import _fused_step_args
    from repro.core.engine import JitTier

    step = JitTier().epoch_step(1, use_kcls=False)
    args = _shapes(_fused_step_args(N, R, window=POOL_WINDOW), one_chip)
    t0 = time.perf_counter()
    with jax.enable_x64(True):
        step.lower(**args).compile()
    assert time.perf_counter() - t0 < 60.0


def test_fused_pallas_step_calls_the_kernels_on_v5e(one_chip, monkeypatch):
    """The pallas tier's epoch program compiles with both kernels inside.
    Off the chip the tier would interpret them; compile them instead."""
    import repro.kernels.ops as ops
    from repro.analysis.lint.trace_safety import _fused_step_args
    from repro.core.engine import PallasTier

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    step = PallasTier().epoch_step(1, use_kcls=False)
    args = _shapes(_fused_step_args(N, R, window=POOL_WINDOW), one_chip)
    with jax.enable_x64(True):
        text = step.lower(**args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


def test_fused_jit_scan_compiles_for_v5e_within_a_minute(one_chip):
    """The K=16 epoch scan with key classes at the WAN deployment's pool
    (DomParams.window 200): the program `run_epoch_window` dispatches."""
    from repro.analysis.lint.trace_safety import _fused_scan_args
    from repro.core.engine import JitTier

    scan = JitTier().epoch_scan(1, use_kcls=True)
    args = _shapes(_fused_scan_args(16, N, R, window=200), one_chip)
    t0 = time.perf_counter()
    with jax.enable_x64(True):
        scan.lower(**args).compile()
    assert time.perf_counter() - t0 < 60.0
