"""The HBM work of the DOM kernels, and a kernel's share of the memory
roofline, kept with the benchmark so that every PR counts it alike.

The work is the problem's, not one implementation's: the bytes a kernel
has to read and write at the least, whatever its algorithm, padding or
tiling.  Times are float64 (8 bytes), verdicts one byte, the release
order int32.  Only the memory roofline is read: bench/peaks.json has no
published peak for the vector unit's comparisons, and minimal bytes over
measured time cannot exceed the HBM peak, so a share cannot pass 100%.
"""
from __future__ import annotations

import re
from typing import Optional

TIME_BYTES = 8
VERDICT_BYTES = 1
ORDER_BYTES = 4


def dom_admit_bytes(n: int, R: int) -> int:
    """Admission of ``n`` messages at ``R`` receivers: the deadlines and
    the [n, R] arrivals in, the [n, R] verdicts out."""
    return TIME_BYTES * (n + n * R) + VERDICT_BYTES * n * R


def dom_release_bytes(n: int) -> int:
    """Release ordering of ``n`` messages: the deadlines and verdicts in,
    the int32 order out."""
    return TIME_BYTES * n + VERDICT_BYTES * n + ORDER_BYTES * n


def hbm_share(n_bytes: float, seconds: float, peaks: dict) -> float:
    """Percent of the device's HBM bandwidth that ``n_bytes`` moved in
    ``seconds`` reach (``peaks``: the device's entry in bench/peaks.json)."""
    return 100.0 * n_bytes / seconds / peaks["hbm_bytes_per_s"]


def op_base(name: str) -> str:
    """An operation's own name in a trace's HLO text, without its ``%`` and
    numeric suffix: ``%dom_admit_pallas.12 = s32[3,32,128] custom-call(...)``
    gives ``dom_admit_pallas``.  Operand names later in the text, which may
    name a kernel whose output the operation reads, are not its own."""
    own = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", own)


def kernel_seconds(ctx, kernel: str) -> Optional[float]:
    """Device seconds of the operations named ``kernel`` (`op_base`) in
    ``ctx.device["ops"]``, or None where the trace has none.  A Pallas
    kernel's operation is named after the function that calls
    ``pallas_call`` (``dom_admit_pallas``, ``dom_release_pallas``)."""
    if ctx.device is None:
        return None
    times = [s for name, s in ctx.device["ops"].items()
             if op_base(name) == kernel]
    return sum(times) if times else None
