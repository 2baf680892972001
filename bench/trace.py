"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData``. On a TPU each chip is a plane
named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per
operation the chip ran, named by the operation's HLO text
(``%lasp2_chunk_fwd.1 = (...) custom-call(...)``), with start and length
in nanoseconds on the host's clock. The benchmark's own host spans
(``jax.profiler.TraceAnnotation``) are events of the ``/host:CPU`` plane.
The two clocks agree to about a millisecond on a v5e host.

Busy time is the union of a device's op intervals inside the traced
window; the idle share is one less busy over the window. Each idle gap is
labelled with the innermost benchmark span open at its midpoint. A
control-flow op (a layer scan's ``while``) spans the ops it runs, so it
counts in busy time but not in the time by op.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

# Control flow whose event spans the ops it runs: counted in busy time,
# not in the time by op.
CONTAINERS = ("while", "conditional", "call")
_OP_NAME = re.compile(r"^%?([A-Za-z0-9_\-.]+?)(?:\.\d+)?(?: =|$)")


def op_name(hlo_text: str) -> str:
    """``%lasp2_chunk_fwd.1 = (...)`` -> ``lasp2_chunk_fwd``."""
    m = _OP_NAME.match(hlo_text.strip())
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def load(path: str, spans=("window",)):
    """Parse a trace into plain data: per device its op events
    ``(name, start_ns, end_ns)``, and the host spans named in ``spans``
    plus those under the window."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append((op_name(e.name), e.start_ns, e.end_ns))
            devices[plane.name] = ops
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        host.append((e.name, e.start_ns, e.end_ns))
    return {"devices": devices, "host": host}


def reduce(data: dict, *, window_span: str = "window", top: int = 10):
    """Busy and idle time, op time by name, and the longest idle gaps.

    Returns a dict with ``window_s``, ``busy_s`` (mean over devices),
    ``idle_share`` (of the busiest-idle device, 0..1), ``op_s``
    (name -> seconds summed over devices and divided by their number),
    ``op_calls`` (name -> calls per device), ``device_ops`` and
    ``idle_gaps`` (the ``breakdown`` lists), or ``None`` when the trace
    holds no device op inside the window."""
    wins = [(s, e) for n, s, e in data["host"] if n == window_span]
    if not wins or not data["devices"]:
        return None
    w0, w1 = wins[0]
    spans = [(n, s, e) for n, s, e in data["host"]
             if n != window_span and e > w0 and s < w1]
    n_dev = len(data["devices"])
    op_s, op_calls = defaultdict(float), defaultdict(float)
    busy, idle_share, gaps = [], 0.0, []
    for ops in data["devices"].values():
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                  if e > w0 and s < w1]
        for n, s, e in inside:
            if n in CONTAINERS:
                continue
            op_s[n] += (e - s) * 1e-9 / n_dev
            op_calls[n] += 1.0 / n_dev
        merged = _union([(s, e) for _, s, e in inside])
        b = sum(e - s for s, e in merged)
        busy.append(b * 1e-9)
        idle_share = max(idle_share, 1.0 - b / (w1 - w0))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((ge - gs, _label(spans, (gs + ge) / 2)))
    if not any(busy):
        return None
    gaps.sort(reverse=True)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n_dev,
        "idle_share": idle_share,
        "op_s": dict(op_s),
        "op_calls": dict(op_calls),
        "device_ops": sorted(([n, t] for n, t in op_s.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[lab, g * 1e-9] for g, lab in gaps[:top]],
    }


def _label(spans, t):
    """The innermost (latest-starting) span open at time ``t``."""
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or s > best[1]):
            best = (n, s)
    return best[0] if best else "none"
