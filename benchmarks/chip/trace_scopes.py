"""The program's own names in a JAX profiler trace: where a compiled FL job's
device time goes, stage by stage, and what the host was doing while the
device sat idle.

    python3 benchmarks/chip/trace_scopes.py <trace dir or .xplane.pb> \
        [--window bench_window]

prints one JSON object: ``trace_reduce.reduce``'s keys (``window_s``,
``busy_s``, ``breakdown``; ``op_s`` left out) and three more, all inside the
window (the host span named ``--window``):

  * ``scopes``: for each ``fl.*`` scope of the program (``jax.named_scope``
    in ``core/fl/engine.py``), ``s``, the device seconds of the leaf
    operations under it, nested scopes included, and ``self_s``, those of
    the operations for which it is the innermost ``fl.*`` scope; averaged
    over the chips, as ``op_s`` is. Operations under no ``fl.*`` scope count
    under ``(none)``; an operation whose name two programs of the trace
    give different scopes counts under ``(conflict)``, and the names are
    listed in ``scope_conflicts``;
  * ``spans``: for each program span on the host (``fl.*``, ``serve.*``,
    ``gc.collect``; ``jax.profiler.TraceAnnotation`` in the program), its
    ``count`` (spans that overlap the window), ``s`` (their seconds inside
    it) and ``self_s`` (less the program spans nested in them);
  * ``idle_by_span``: the seconds in which the first chip ran nothing, each
    put down to a ``gc.collect`` on any thread (a collection holds the
    interpreter lock, so it stalls every thread), else to the innermost
    program span of a thread that dispatches device work (a host line with
    a ``PjitFunction`` event in the window; where two such threads are in
    program spans at once, the span that began last), else to ``(no
    program span)``. The values add up to the idle time.

An operation's scope is read from the ``tf_op`` stat of its event metadata:
the JAX name stack at the operation, such as
``jit(f)/while/body/vmap(transpose(jvp(fl.local_update)))/fl.adam/mul``.
``jax.profiler.ProfileData`` does not expose metadata stats, so
:func:`op_scopes` reads the few fields it needs straight off the protobuf
wire format; nothing here imports TensorFlow.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import trace_reduce  # noqa: E402

PROGRAM_SPAN = re.compile(r"^(fl\.|serve\.)|^gc\.collect$")
DISPATCH = "PjitFunction("
GC = "gc.collect"
NO_SCOPE, CONFLICT, NO_SPAN = "(none)", "(conflict)", "(no program span)"
WRAPPER = re.compile(r"^[\w.-]*\((.*)\)$")


# --- the protobuf wire format, as far as XSpace's metadata needs it ----------

def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one serialized message:
    an int for a varint, bytes for a fixed or length-delimited field."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _map_values(entries):
    """The values of a protobuf map's entries (field 2 of each entry)."""
    for entry in entries:
        for field, value in _fields(entry):
            if field == 2:
                yield value


def _str_stat(buf, stat_names: dict):
    """``(stat name id, value)`` of one XStat holding a string: in
    ``str_value`` (5), or in ``ref_value`` (7), which names a string kept in
    the plane's stat metadata."""
    sid, value = None, None
    for field, v in _fields(buf):
        if field == 1:
            sid = v
        elif field == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif field == 7:
            value = stat_names.get(v)
    return sid, value


def op_metadata(path: str) -> dict:
    """For each device plane of the ``.xplane.pb`` at ``path``, every event
    metadata name with the set of ``tf_op`` values it takes (one per
    program that has an operation of that name)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:                       # XSpace.planes
            continue
        name, events, stats = "", [], []
        for pf, v in _fields(plane):
            if pf == 2:                      # XPlane.name
                name = bytes(v).decode()
            elif pf == 4:                    # XPlane.event_metadata
                events.append(v)
            elif pf == 5:                    # XPlane.stat_metadata
                stats.append(v)
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for v in _map_values(stats):
            sid, sname = None, ""
            for f2, x in _fields(v):
                if f2 == 1:
                    sid = x
                elif f2 == 2:
                    sname = bytes(x).decode()
            stat_names[sid] = sname
        want = {i for i, n in stat_names.items() if n == "tf_op"}
        ops = defaultdict(set)
        for v in _map_values(events):
            ename, value = "", None
            for f2, x in _fields(v):
                if f2 == 2:
                    ename = bytes(x).decode("utf-8", "replace")
                elif f2 == 5:
                    sid, sv = _str_stat(x, stat_names)
                    if sid in want:
                        value = sv
            if value is not None:
                ops[ename].add(value)
        out[name] = dict(ops)
    return out


def scope_path(tf_op: str):
    """The ``fl.*`` scopes of a name stack, outermost first, with transform
    wrappers (``vmap(...)``, ``transpose(jvp(...))``) stripped."""
    out = []
    for part in tf_op.split("/"):
        m = WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = WRAPPER.match(part)
        if part.startswith("fl.") and part not in out:
            out.append(part)
    return tuple(out)


def op_scopes(path: str):
    """Per device plane, each operation name's ``fl.*`` scope path, and the
    names whose programs disagree on it."""
    scopes, conflicts = {}, set()
    for plane, ops in op_metadata(path).items():
        table = scopes[plane] = {}
        for name, values in ops.items():
            paths = {scope_path(v) for v in values}
            if len(paths) > 1:
                conflicts.add(name)
                table[name] = None
            else:
                table[name] = paths.pop()
    return scopes, sorted(conflicts)


# --- the reduction -----------------------------------------------------------

def read_lines(path: str):
    """``(devices, lines)``: per device plane its operation events, as
    ``trace_reduce.read`` gives them, and each host line's events ``(name,
    start_ns, end_ns)``, one list per thread."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, lines = {}, []
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            devices[plane.name] = sorted(ops, key=lambda o: (o[1], -o[2]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([(e.name, e.start_ns, e.end_ns)
                              for e in line.events])
    return devices, lines


def _pieces(spans):
    """Properly nested spans of one thread as disjoint ``(start, end, name,
    began)`` pieces, each named by the innermost span covering it, which
    began at ``began``."""
    out, stack, t = [], [], None
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            end, top, began = stack.pop()
            out.append((t, end, top, began))
            t = end
        if stack:
            out.append((t, s) + stack[-1][1:])
        stack.append((e, name, s))
        t = s
    while stack:
        end, top, began = stack.pop()
        out.append((t, end, top, began))
        t = end
    return [p for p in out if p[1] > p[0]]


def _latest(threads):
    """The pieces of several threads as one sorted disjoint ``(start, end,
    name)`` list: where two threads are in program spans at once, the span
    that began last names the time."""
    if len(threads) == 1:
        return [p[:3] for p in threads[0]]
    bounds = sorted({x for ps in threads for p in ps for x in p[:2]})
    at = [0] * len(threads)
    out = []
    for a, b in zip(bounds, bounds[1:]):
        best = None
        for i, ps in enumerate(threads):
            while at[i] < len(ps) and ps[at[i]][1] <= a:
                at[i] += 1
            if at[i] < len(ps) and ps[at[i]][0] <= a:
                if best is None or ps[at[i]][3] > best[3]:
                    best = ps[at[i]]
        if best is None:
            continue
        if out and out[-1][1] == a and out[-1][2] == best[2]:
            out[-1] = (out[-1][0], b, best[2])
        else:
            out.append((a, b, best[2]))
    return out


def _overlap(gaps, pieces):
    """The overlaps of sorted disjoint ``gaps`` with sorted disjoint
    labelled ``pieces``, as ``(start, end, label)``."""
    out, j = [], 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            s, e = max(gs, pieces[k][0]), min(ge, pieces[k][1])
            if e > s:
                out.append((s, e, pieces[k][2]))
            k += 1
    return out


def _subtract(gaps, cuts):
    """Sorted disjoint ``gaps`` less the sorted disjoint ``cuts``."""
    out, j = [], 0
    for gs, ge in gaps:
        s = gs
        while j < len(cuts) and cuts[j][1] <= s:
            j += 1
        k = j
        while k < len(cuts) and cuts[k][0] < ge:
            if cuts[k][0] > s:
                out.append((s, cuts[k][0]))
            s = max(s, cuts[k][1])
            k += 1
        if ge > s:
            out.append((s, ge))
    return out


def reduce_program(devices: dict, lines: list, scopes: dict, conflicts,
                   window: str) -> dict:
    """``scopes``, ``scope_conflicts``, ``spans`` and ``idle_by_span`` (see
    the module's docstring) of one trace."""
    marks = [(s, e) for line in lines for n, s, e in line if n == window]
    if not marks:
        raise ValueError(f"no host span named {window!r} in the trace")
    if not devices:
        raise ValueError("no TPU plane in the trace")
    ws, we = marks[0]

    def inside(s, e):
        return e > ws and s < we

    per = defaultdict(lambda: [0.0, 0.0])
    for plane, ops in devices.items():
        table = scopes.get(plane, {})
        clipped = [(n, max(s, ws), min(e, we)) for n, s, e in ops
                   if inside(s, e)]
        for n, s, e in trace_reduce.leaves(clipped):
            t = (e - s) / 1e9 / len(devices)
            path = table.get(n, ())
            if n in table and path is None:
                path = (CONFLICT,)
            for name in path or (NO_SCOPE,):
                per[name][0] += t
            per[(path or (NO_SCOPE,))[-1]][1] += t
    scope_s = {k: {"s": v[0], "self_s": v[1]} for k, v in sorted(per.items())}

    spans = defaultdict(lambda: {"count": 0, "s": 0.0, "self_s": 0.0})
    gc_cuts, dispatching = [], []
    for line in lines:
        prog = [(n, max(s, ws), min(e, we)) for n, s, e in line
                if inside(s, e) and PROGRAM_SPAN.match(n)]
        for n, s, e in prog:
            spans[n]["count"] += 1
            spans[n]["s"] += (e - s) / 1e9
        pieces = _pieces(prog)
        for s, e, n, _ in pieces:
            spans[n]["self_s"] += (e - s) / 1e9
        gc_cuts.extend((s, e) for n, s, e in prog if n == GC)
        if pieces and any(n.startswith(DISPATCH) and inside(s, e)
                          for n, s, e in line):
            dispatching.append(pieces)

    first = devices[sorted(devices)[0]]
    busy = trace_reduce.union((max(s, ws), min(e, we)) for _, s, e in first
                              if inside(s, e))
    edges = [ws] + [x for iv in busy for x in iv] + [we]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    idle = defaultdict(float)
    gc_union = [tuple(iv) for iv in trace_reduce.union(gc_cuts)]
    for s, e, _ in _overlap(gaps, [(s, e, GC) for s, e in gc_union]):
        idle[GC] += (e - s) / 1e9
    rest = _subtract(gaps, gc_union)
    named = _overlap(rest, _latest(dispatching)) if dispatching else []
    for s, e, n in named:
        idle[n] += (e - s) / 1e9
    idle[NO_SPAN] += (sum(e - s for s, e in rest)
                      - sum(e - s for s, e, _ in named)) / 1e9
    return {"scopes": scope_s, "scope_conflicts": list(conflicts),
            "spans": dict(sorted(spans.items())),
            "idle_by_span": dict(sorted(idle.items()))}


def reduce_path(path: str, window: str) -> dict:
    """``trace_reduce.reduce``'s keys and :func:`reduce_program`'s, for the
    ``.xplane.pb`` at ``path``."""
    devices, lines = read_lines(path)
    flat = [ev for line in lines for ev in line]
    out = trace_reduce.reduce(devices, flat, window=window)
    scopes, conflicts = op_scopes(path)
    out.update(reduce_program(devices, lines, scopes, conflicts, window))
    return out


def reduce_dir(trace_dir: str, window: str) -> dict:
    return reduce_path(trace_reduce.find_xplane(trace_dir), window)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb file")
    ap.add_argument("--window", default="bench_window")
    args = ap.parse_args(argv)
    path = (args.trace if os.path.isfile(args.trace)
            else trace_reduce.find_xplane(args.trace))
    out = reduce_path(path, args.window)
    out.pop("op_s")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
