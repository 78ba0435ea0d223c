"""Bandwidth roofline of the AQP kernels.

The selection/aggregation family streams its operand planes once and
does O(1) FLOPs per byte, so the only meaningful bound is bytes
streamed / bandwidth per backend — ``benchmarks/kernels_bench.py``
emits ``achieved_GB_s`` / ``roofline_fraction`` rows against it into
the ``BENCH_*.json`` artifacts, and CI smoke asserts the jnp grouped
path stays above its floor.
"""
from __future__ import annotations

from typing import Dict

HBM = 819e9

# AQP-kernel roofline: the selection/aggregation kernels do O(1) FLOPs
# per streamed byte, so their bound is pure bandwidth — HBM on the TPU
# ("pallas"), and a conservative single-socket effective stream
# bandwidth for the XLA:CPU oracle and the f64 host mirror on this
# container class. achieved/bound is the kernel's roofline fraction.
CPU_BW = 25e9
AQP_BW = {"pallas": HBM, "jnp": CPU_BW, "np": CPU_BW}


def aqp_kernel_roofline(n_bytes: float, seconds: float,
                        backend: str) -> Dict:
    """Bandwidth-roofline verdict for one AQP kernel measurement.

    ``n_bytes`` is the kernel's minimum streamed traffic (each operand
    plane read once), ``seconds`` the measured wall time per call,
    ``backend`` one of ``AQP_BW``'s keys. Returns ``achieved_GB_s``,
    the backend's ``bound_GB_s``, and ``roofline_fraction`` =
    achieved/bound — the quantity ``benchmarks/kernels_bench.py`` emits
    per backend and CI smoke asserts on.
    """
    bound = AQP_BW[backend]
    achieved = (n_bytes / seconds) if seconds > 0 else float("nan")
    return {"backend": backend,
            "achieved_GB_s": achieved / 1e9,
            "bound_GB_s": bound / 1e9,
            "roofline_fraction": achieved / bound}
