"""Roofline share of ``grouped_agg_pallas`` (kernels/segment_agg.py), the
kernel behind every public op on the chip: the least time its observed
calls need (``opcost.bytes_needed`` over the chip's memory bandwidth,
from ``bench/peaks.json``) over the kernel's summed device time in the
trace. Only calls that ran the compiled kernel count; with no such call
or no kernel time in the trace there is nothing to read."""
import json
from pathlib import Path

from bench import opcost

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peak_bandwidth(device_kind: str) -> float:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in {PEAKS}")
    return float(table[device_kind]["hbm_bytes_per_s"])


def read(run):
    seconds = (run.trace or {}).get("kernel_s", {}).get("grouped_agg", 0.0)
    need = sum(opcost.bytes_needed(shape) for op, shape, backend
               in run.calls
               if backend == "pallas" and opcost.KERNEL.get(op) == "grouped_agg")
    if seconds <= 0.0 or need == 0:
        return None
    return 100.0 * need / peak_bandwidth(run.device_kind) / seconds
