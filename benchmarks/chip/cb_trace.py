"""Reduction of a profiler trace to the device's busy time, the kernels'
device time and the breakdown of a run.

A trace is read into :class:`Trace`: per device, the op-level events
(name, start, end in ns), and the host spans the benchmark wrote with
``jax.profiler.TraceAnnotation`` ("measured window", "request in flight").
All times are on the profiler's one clock.

- busy: the union of a device's op intervals inside the window; the idle
  share is ``100 * (1 - busy / window)``, averaged over the devices used;
- op name: an event of the device's op line is named by its HLO
  instruction (``%fused_chain_tiles.1 = (s32[...]...) custom-call(...)``);
  :func:`op_name` keeps the instruction's name without its number
  (``fused_chain_tiles``, ``copy``), which is also the name a
  ``pallas_call`` gives its kernel;
- kernel time: the summed device durations of the ops of one such name;
- top ops: device time by op name;
- idle gaps: the gaps between busy intervals, each labelled by whether a
  request was in flight at its middle.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "measured window"
REQUEST_SPAN = "request in flight"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
_INSTRUCTION = re.compile(r"%?([A-Za-z_][\w-]*?)(?:\.\d+)*\s*=")


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)  # plane name -> [(op name, start_ns, end_ns)]
    host: list = field(default_factory=list)  # [(span name, start_ns, end_ns)]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str, host_spans=(WINDOW_SPAN, REQUEST_SPAN)) -> Trace:
    """Read a profiler trace with jax's own reader."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    evs.extend((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events)
            if evs:
                tr.devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_spans:
                        tr.host.append((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)))
    return tr


def window(tr: Trace) -> tuple:
    spans = [(s, e) for n, s, e in tr.host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def merged(intervals, lo: int, hi: int) -> list:
    """Union of intervals clipped to [lo, hi], as sorted disjoint pairs."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(tr: Trace, lo: int, hi: int) -> float:
    """Busy time averaged over the traced devices."""
    if not tr.devices:
        return 0.0
    per = [sum(e - s for s, e in merged(((s, e) for _n, s, e in evs), lo, hi)) for evs in tr.devices.values()]
    return sum(per) / len(per)


def idle_pct(tr: Trace, lo: int, hi: int) -> float:
    return 100.0 * (1.0 - busy_ns(tr, lo, hi) / (hi - lo))


def op_name(event_name: str) -> str:
    """``%copy.10 = s32[...] copy(...)`` -> ``copy``; other names as they are."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def op_ns(tr: Trace, lo: int, hi: int) -> dict:
    """Device time by op name, summed over devices, clipped to the window."""
    out: dict = {}
    for evs in tr.devices.values():
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = op_name(name)
                out[key] = out.get(key, 0) + d
    return out


def kernel_ns(tr: Trace, lo: int, hi: int, kernel: str) -> float:
    """Device time of one kernel (its ``pallas_call`` name) in the window."""
    return float(op_ns(tr, lo, hi).get(kernel, 0))


def top_ops(tr: Trace, lo: int, hi: int, k: int = 10) -> list:
    ops = sorted(op_ns(tr, lo, hi).items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ops]


def idle_gaps(tr: Trace, lo: int, hi: int, k: int = 10) -> list:
    """The ``k`` longest idle gaps of the first device, labelled by the
    benchmark's request spans."""
    if not tr.devices:
        return [["no device op at 0.000 s", (hi - lo) / 1e9]]
    first = tr.devices[sorted(tr.devices)[0]]
    busy = merged(((s, e) for _n, s, e in first), lo, hi)
    edges = [lo] + [x for b in busy for x in b] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    requests = merged(((s, e) for n, s, e in tr.host if n == REQUEST_SPAN), lo, hi)
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) // 2
        inflight = any(a <= mid < b for a, b in requests)
        label = REQUEST_SPAN if inflight else f"no {REQUEST_SPAN}"
        out.append([f"{label} at {(s - lo) / 1e9:.3f} s", (e - s) / 1e9])
    return out
