"""Reduction of a profiler trace to device busy time, idle gaps and the
longest device operations.

A trace is first flattened to plain events ``(plane, line, name,
start_ns, dur_ns)`` (:func:`flatten`); :func:`reduce` works on that
list alone, so it can be checked on a small recorded excerpt without a
chip.  Host spans written by the harness (``jax.profiler.TraceAnnotation``
named ``bench.<layer>``) share the device's clock in the trace, and label
each idle gap with what the host was doing in it.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float]  # plane, line, name, start_ns, dur_ns

#: device planes of the accelerator (not its host-side sub-planes)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
#: one event per program execution; the device is busy inside each
MODULES = "XLA Modules"
#: one event per operation: millions per second inside a device loop,
#: so only the first ``MAX_OPS`` are read, for the longest-operation list
OPS = "XLA Ops"
MAX_OPS = 200_000
SPAN_PREFIX = "bench."
TOP = 10
#: On a v5e the profiler's buffer fills after about 8.7 s of this device
#: loop and drops every later device event.  A loop span that ends more
#: than this long after the device's last event marks such a trace: its
#: window then ends at that last event.
CUT_NS = 1e8


class Session:
    """A profiler session whose trace stays in memory (a long device
    loop traces hundreds of megabytes of operations per second)."""

    def __init__(self):
        import jax
        from jax._src.lib import _profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self._session = _profiler.ProfilerSession(opts)

    def stop(self) -> List[Event]:
        import jax

        data = jax.profiler.ProfileData.from_serialized_xspace(self._session.stop())
        return flatten(data)


def flatten(data) -> List[Event]:
    """Harness spans, device module executions and the first device
    operations of a ``jax.profiler.ProfileData``."""
    out: List[Event] = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name in (MODULES, OPS):
                for i, ev in enumerate(line.events):
                    if line.name == OPS and i >= MAX_OPS:
                        break
                    # an op's name is its HLO text: keep the op id
                    out.append((plane.name, line.name, ev.name.split(" = ")[0],
                                float(ev.start_ns), float(ev.duration_ns)))
            elif not device:
                out.extend((plane.name, line.name, ev.name, float(ev.start_ns),
                            float(ev.duration_ns))
                           for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    return out


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def reduce(events: Sequence[Event]) -> Optional[Dict[str, object]]:
    """``busy_s`` (time in which the device ran a program, within the
    traced window, averaged over the devices), ``window_s`` (first to
    last harness span, or to the device's last event where the buffer
    filled first: then ``cut``), the longest device operations and the
    longest idle gaps, each labelled with the harness span the host was
    in.  ``None`` when the trace holds no harness span or no device
    event."""
    spans = [(s, s + d, n[len(SPAN_PREFIX):]) for p, l, n, s, d in events
             if n.startswith(SPAN_PREFIX) and not DEVICE_PLANE.match(p)]
    dev: Dict[str, Dict[str, List[Tuple[float, float, str]]]] = {}
    for p, l, n, s, d in events:
        if DEVICE_PLANE.match(p):
            dev.setdefault(p, {}).setdefault(l, []).append((s, s + d, n))
    if not spans or not dev:
        return None
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    dev_end = max(e for lines in dev.values() for evs in lines.values() for _, e, _ in evs)
    cut = any(n == "loop" and e > dev_end + CUT_NS for _, e, n in spans)
    if cut:
        hi = dev_end
    busy, gaps, ops = [], [], {}
    for plane, lines in sorted(dev.items()):
        for s, e, n in lines.get(OPS) or lines.get(MODULES, []):
            if e > lo and s < hi:
                ops[n] = ops.get(n, 0.0) + (min(e, hi) - max(s, lo))
        runs = lines.get(MODULES) or lines.get(OPS, [])
        u = _union(_clip([(s, e) for s, e, _ in runs], lo, hi))
        busy.append(sum(e - s for s, e in u))
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = 0.5 * (s + e)
                label = next((n for a, b, n in spans if a <= mid < b), "other")
                gaps.append((e - s, label))
    gaps.sort(reverse=True)
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "cut": cut,
        "device_ops": [[n, t * 1e-9] for n, t in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label, t * 1e-9] for t, label in gaps[:TOP]],
    }
