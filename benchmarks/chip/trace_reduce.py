"""Reduce a JAX profiler trace to the numbers the benchmark reports.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it, each TPU is a plane named ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per operation that ran on the device (a fusion, a
convolution, a Pallas kernel's custom call), with its start and duration on
the same clock as the host's planes. The harness marks its measured window
with a host span (``jax.profiler.TraceAnnotation``), and the traffic kinds
mark what the host does inside it.

  * busy: the union of the device's operation intervals inside the window;
    averaged over the chips;
  * an operation's or a kernel's time: the sum of its events' durations
    inside the window. Control flow (a ``while`` loop, a call) is an event
    that contains the events of its body; only events that contain no other
    count as operations, so that no time is counted twice;
  * idle gaps: the stretches of the window in which the device ran nothing,
    each named by the innermost host span that covers its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read(path: str):
    """``(devices, spans)``: per device plane the list of operation events
    ``(name, start_ns, end_ns)``, and every host event ``(name, start_ns,
    end_ns)``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            devices[plane.name] = sorted(ops, key=lambda o: (o[1], -o[2]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events)
    return devices, spans


def leaves(ops):
    """The events of ``ops`` (sorted by start) that contain no other."""
    outer = set()
    stack = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            outer.add(stack[-1])
        stack.append(i)
    return [o for i, o in enumerate(ops) if i not in outer]


def union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(devices: dict, spans: list, window: str, top: int = 10) -> dict:
    marks = [(s, e) for n, s, e in spans if n == window]
    if not marks:
        raise ValueError(f"no host span named {window!r} in the trace")
    ws, we = marks[0]
    if not devices:
        raise ValueError("no TPU plane in the trace")
    busy, op_time, gaps = [], defaultdict(float), []
    inner = [(n, s, e) for n, s, e in spans
             if n != window and e > ws and s < we and e > s]
    for plane in sorted(devices):
        clipped = [(n, max(s, ws), min(e, we)) for n, s, e in devices[plane]
                   if e > ws and s < we]
        merged = union((s, e) for _, s, e in clipped)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for n, s, e in leaves(clipped):
            op_time[(plane, n)] += (e - s) / 1e9
        if plane == sorted(devices)[0]:
            edges = [ws] + [x for iv in merged for x in iv] + [we]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((s, e))
    per_op = defaultdict(float)
    for (_, n), t in op_time.items():
        per_op[n] += t / len(devices)

    def label(s, e):
        mid = (s + e) / 2
        cover = [(se - ss, n) for n, ss, se in inner if ss <= mid <= se]
        return min(cover)[1] if cover else "no host span"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (we - ws) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "op_s": dict(per_op),
        "breakdown": {
            "device_ops": [[n[:200], t] for n, t in sorted(
                per_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[label(s, e), (e - s) / 1e9] for s, e in longest],
        },
    }


def reduce_dir(trace_dir: str, window: str) -> dict:
    return reduce(*read(find_xplane(trace_dir)), window=window)


def kernel_seconds(reduced: dict, pattern: str):
    """Device seconds of the operations whose name matches ``pattern`` (a
    regular expression), or None where none ran."""
    rx = re.compile(pattern)
    hits = [t for n, t in reduced["op_s"].items() if rx.search(n)]
    return sum(hits) if hits else None
