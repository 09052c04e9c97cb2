"""jit'd wrappers selecting kernel vs. pure-jnp path.

On the TPU the Pallas kernels run compiled.  On the CPU they run in the
Pallas interpreter (the test suite's mode); `interpret_mode` makes that
choice for every wrapper, and refuses any other backend.

Time keys: the DOM kernels compare event times as exact two-word int32
keys (repro.kernels.timekeys), so the pallas path needs no span shift, no
sentinel remapping, and matches the float64 tiers bit for bit -- callers
pass absolute float64 times straight through.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.dom_admit import dom_admit_pallas
from repro.kernels.dom_release import dom_release_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.inchash import inchash_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted: on the CPU yes, on the TPU no.

    The kernels are written for the TPU; the CPU runs them through the
    Pallas interpreter.  Any other backend raises instead of interpreting
    in silence.
    """
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run on the TPU, or interpreted "
                           f"on the CPU; the backend is {backend!r}")
    return backend == "cpu"


def attention(q, k, v, *, causal=True, window=None, use_pallas=None):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      interpret=interpret_mode())
    from repro.models.attention import flash_attention

    return flash_attention(q, k, v, causal=causal, window=window)


def ssd_scan(x, dt, A, B, C, *, chunk=128, use_pallas=None):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return ssd_scan_pallas(x, dt, A, B, C, chunk=chunk,
                               interpret=interpret_mode())
    return _ref.ssd_scan_ref(x, dt, A, B, C)


def dom_admit_traced(deadlines, arrivals, *, use_pallas=True):
    """Traceable early-buffer admission: [N] x [N, R] -> [N, R] bool.

    The jnp mirror of the host-level `dom_admit`: runs the fused
    `dom_admit_pallas` bitonic-watermark kernel on exact int32 key words,
    one grid program per receiver.  Composable inside jit -- the engine's
    fused epoch step for the pallas tier calls this directly (under
    enable_x64, so the kernel sees float64 keys and admission is exact).
    """
    if use_pallas:
        return dom_admit_pallas(deadlines, arrivals.T,
                                interpret=interpret_mode()).T
    from repro.core.vectorized import dom_admit_watermark_jnp

    return dom_admit_watermark_jnp(deadlines, arrivals)


def dom_admit(deadlines, arrivals, *, use_pallas=None):
    """Early-buffer admission via the fused watermark kernel (host entry).

    Off-kernel the float64 numpy watermark path is the reference; with
    `use_pallas` the bitonic event sort + prefix-max kernel runs admission
    on-device (interpreted on the CPU) over exact int32 time keys --
    bit-identical to the numpy watermark, ties included.
    """
    import numpy as np

    if use_pallas is None:
        use_pallas = _on_tpu()
    d = np.asarray(deadlines, np.float64)
    a = np.asarray(arrivals, np.float64)
    if not use_pallas:
        from repro.core.vectorized import dom_admit_watermark_np

        return dom_admit_watermark_np(d, a)
    with jax.enable_x64(True):
        adm = dom_admit_pallas(jnp.asarray(d), jnp.asarray(a.T),
                               interpret=interpret_mode())
    return np.asarray(adm).T  # lint: allow[HS003] host-entry wrapper: one pull of the kernel result


def dom_release(deadlines, admitted, clock_now, *, use_pallas=None):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return dom_release_pallas(deadlines, admitted, clock_now,
                                  interpret=interpret_mode())
    return dom_release_ref_order(deadlines, admitted, clock_now)


def dom_release_ref_order(deadlines, admitted, clock_now):
    """Oracle for dom_release: masked stable argsort by deadline.

    Conversion happens under `enable_x64` so float64 inputs keep float64
    comparison precision regardless of the caller's x64 context (jit-free,
    plain jnp ops; float32 inputs stay float32).
    """
    with jax.enable_x64(True):
        deadlines = jnp.asarray(deadlines)
        released = jnp.asarray(admitted, bool) & (deadlines <= clock_now)
        keys = jnp.where(released, deadlines, jnp.inf)
        order = jnp.argsort(keys, stable=True).astype(jnp.int32)
        n_rel = jnp.sum(released.astype(jnp.int32))
        seq = jnp.arange(deadlines.shape[0])
        return jnp.where(seq < n_rel, order, -1), n_rel


def dom_deadline_order(deadlines, *, use_pallas=None):
    """Full deadline sort of a message batch via the dom_release kernel.

    This is the pallas compute tier's ordering primitive (repro.core.engine):
    with every message admitted and the clock at +inf, the early-buffer drain
    degenerates to the plain deadline sort the commit classifier needs.
    Exact int32 key words with the message index as the final sort key make
    the result EXACTLY ``np.argsort(deadlines, kind="stable")``: ties break
    by message id, non-finite deadlines (dropped stamps) sort at the tail
    (ahead of the kernel's own pow2-padding lanes), and the output is always
    a permutation of [0, n). Returns int64 message indices, deadline-sorted.
    """
    import numpy as np

    d = np.asarray(deadlines, np.float64)
    n = d.size
    if n == 0:
        return np.zeros(0, np.int64)
    # pad to a pow2 bucket with never-admitted lanes (they sort behind every
    # real one), so epochs of every size share O(log n) compiled programs
    n_b = 1 << (n - 1).bit_length()
    admitted = np.zeros(n_b, np.int8)
    admitted[:n] = 1
    with jax.enable_x64(True):
        order, _ = dom_release(
            jnp.asarray(np.pad(d, (0, n_b - n), constant_values=np.inf)),
            jnp.asarray(admitted), jnp.asarray(np.inf),
            use_pallas=use_pallas)
    return np.asarray(order[:n], dtype=np.int64)  # lint: allow[HS003] host-entry wrapper: one pull of the kernel result


def dom_deadline_order_traced(deadlines, *, use_pallas=True):
    """Traceable mirror of `dom_deadline_order` for the fused epoch step.

    Same exact-key contract, expressed in jnp so it composes inside the
    jitted epoch program; off the pallas path it falls back to a plain
    stable argsort.  Both paths produce the identical permutation.
    """
    d = deadlines
    if not use_pallas:
        return jnp.argsort(d, stable=True)
    order, _ = dom_release_pallas(d, jnp.ones(d.shape[0], jnp.int8),
                                  jnp.full((), jnp.inf, d.dtype),
                                  interpret=interpret_mode())
    return order


def inchash(deadline_ns, client_id, request_id, *, use_pallas=None):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return inchash_pallas(deadline_ns, client_id, request_id,
                              interpret=interpret_mode())
    return _ref.inchash_ref(deadline_ns, client_id, request_id)


__all__ = ["attention", "ssd_scan", "dom_release", "dom_release_ref_order",
           "dom_deadline_order", "dom_deadline_order_traced",
           "dom_admit", "dom_admit_traced", "inchash"]
