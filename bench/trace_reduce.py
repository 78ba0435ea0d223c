"""From a profiler trace to the device's busy time, kernel times and the
host's share of the idle gaps.

``extract`` reads the ``.xplane.pb`` file that ``jax.profiler`` writes
into plain lists: every operation on each device's ops line, and the
benchmark's own host spans. ``reduce`` works on those lists only, so a
small recorded trace checks it (``bench/tests``):

- the window is the host span named ``window``; everything is clipped
  to it;
- a device is busy where any of its operations runs: the union of their
  intervals. ``busy_s`` is the mean over the devices that ran anything;
- a kernel's time is the summed duration of its operations (their HLO
  instruction matched by ``kernels.json``) over all devices;
- operations are named by their HLO instruction, without its number:
  ``%grouped_agg_pallas.1 = f32[...] custom-call(...)`` is
  ``grouped_agg_pallas``;
- each idle gap of a device is split by the innermost benchmark span
  open on the host thread at the time (``no_span`` where none is) and
  summed per span name: what the host was doing while the chip waited.
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict, Optional

HERE = Path(__file__).resolve().parent
WINDOW = "window"


def kernel_patterns() -> Dict[str, dict]:
    return json.loads((HERE / "kernels.json").read_text())["kernels"]


def extract(xplane_path, host_span) -> dict:
    """``{"device": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns, thread], ...]}``; ``host_span(name)``
    says which host events are benchmark spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane_path))
    ops_line = json.loads((HERE / "kernels.json").read_text())["ops_line"]
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = []
            for line in plane.lines:
                if line.name != ops_line:
                    continue
                for e in line.events:
                    evs.append([e.name, float(e.start_ns),
                                float(e.duration_ns)])
            if evs:
                device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if host_span(e.name):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns), line.name])
    return {"device": device, "host": host}


def _union(intervals):
    """Sorted disjoint union of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def innermost(spans, t0, t1):
    """Partition ``[t0, t1)`` into ``(start, end, name)`` pieces, each
    named by the innermost of the properly nested ``spans``
    (``(start, end, name)``) open there, or ``no_span``."""
    segs, stack = [], []
    cur = t0

    def advance(to):
        nonlocal cur
        while stack and stack[-1][0] <= to:
            end, name = stack.pop()
            if end > cur:
                segs.append((cur, end, name))
                cur = end
        if to > cur:
            segs.append((cur, to, stack[-1][1] if stack else "no_span"))
            cur = to

    for s, e, name in sorted(spans, key=lambda v: (v[0], -v[1])):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        advance(s)
        stack.append((e, name))
    advance(t1)
    return segs


def _split(gaps, segs, into):
    """Add the length of each gap's overlap with each named piece."""
    j = 0
    for gs, ge in gaps:
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            s, e, name = segs[k]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                into[name] = into.get(name, 0.0) + ov
            k += 1


def match_kernel(name: str, patterns) -> Optional[str]:
    for kernel, p in patterns.items():
        if re.search(p, name):
            return kernel
    return None


def short_name(name: str) -> str:
    """The HLO instruction's name without its ``%`` and number."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def reduce(ex: dict, patterns=None, top: int = 10) -> Optional[dict]:
    patterns = kernel_patterns() if patterns is None else patterns
    wins = [h for h in ex["host"] if h[0] == WINDOW]
    if not wins:
        return None
    _, w0, wdur, thread = wins[0]
    w1 = w0 + wdur
    spans = [(s, s + d, n) for n, s, d, t in ex["host"]
             if t == thread and n != WINDOW]
    segs = innermost(spans, w0, w1)
    busy, kernel_ns, op_ns, idle = [], {}, {}, {}
    for evs in ex["device"].values():
        ivs = []
        for name, s, d in evs:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 <= s0:
                continue
            ivs.append((s0, e0))
            op = short_name(name)
            op_ns[op] = op_ns.get(op, 0.0) + (e0 - s0)
            k = match_kernel(name, patterns)
            if k is not None:
                kernel_ns[k] = kernel_ns.get(k, 0.0) + (e0 - s0)
        if not ivs:
            continue
        u = _union(ivs)
        busy.append(sum(e - s for s, e in u))
        gaps, cur = [], w0
        for s, e in u:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < w1:
            gaps.append((cur, w1))
        _split(gaps, segs, idle)
    n_dev = max(len(busy), 1)
    by_len = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "window_s": wdur / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "devices": len(busy),
        "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
        "device_ops": [[n, v / 1e9] for n, v in by_len(op_ns)],
        "idle_gaps": [[n, v / n_dev / 1e9] for n, v in by_len(idle)],
    }

