"""CPU tests of ``trace_scopes``: the protobuf reader of event metadata, the
stripping of transform wrappers, and the reduction to ``scopes``, ``spans``
and ``idle_by_span``, on a hand-built trace and on traces recorded on a TPU
v5e.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402
import trace_scopes  # noqa: E402

SMALL = os.path.join(HERE, "data", "small.xplane.pb")
# Recorded on a TPU v5e with run.py's profiler options, inside one
# ``bench_window``: a 2-round ``run_fl(driver="while")`` job (LoGTST at
# d_model 16, 4 clients, fused mix), then 12 and 5 requests to a routed
# server of two clusters (max_batch 4) with a forced ``gc.collect()``
# between them. The HLO protos and every device metadata stat but
# ``tf_op`` were dropped from the file to keep it small.
PROGRAM = os.path.join(HERE, "data", "program.xplane.pb")
MS = 1_000_000          # ns
STAGES = ("fl.round_down", "fl.local_update", "fl.window_gather", "fl.adam",
          "fl.round_up", "fl.eval")


def test_reader_returns_tf_op_of_a_fusion():
    ops = trace_scopes.op_metadata(SMALL)["/device:TPU:0"]
    name = next(n for n in ops if n.startswith("%copy_bitcast_fusion.2 "))
    assert ops[name] == {"jit(<lambda>)/jit(_psgf_mix_batch)/reshape:"}


def test_existing_keys_unchanged():
    old = trace_reduce.reduce_dir(os.path.dirname(SMALL), "bench_window")
    new = trace_scopes.reduce_dir(os.path.dirname(SMALL), "bench_window")
    assert {k: new[k] for k in old} == old
    # no fl.* scope in that program: every leaf counts under (none)
    total = sum(old["op_s"].values())
    assert list(new["scopes"]) == ["(none)"]
    assert new["scopes"]["(none)"]["s"] == pytest.approx(total)


@pytest.mark.parametrize("tf_op,path", [
    ("jit(f)/while/body/vmap(transpose(jvp(fl.local_update)))/fl.adam/mul",
     ("fl.local_update", "fl.adam")),
    ("jit(_run_while_impl)/while/body/closed_call/fl.round_down/"
     "vmap(jit(_uniform))/vmap()/while/body/add", ("fl.round_down",)),
    ("jit(<lambda>)/jit(_psgf_mix_batch)/reshape:", ()),
    ("jit(f)/fl.eval/fl.eval/dot_general", ("fl.eval",)),
])
def test_scope_path_strips_wrappers(tf_op, path):
    assert trace_scopes.scope_path(tf_op) == path


def test_reduction_by_hand():
    """A window of 100 ms; times in ms, counted by hand below."""
    ops = [("w", 5, 60), ("a", 10, 20), ("b", 30, 35), ("c", 40, 50),
           ("d", 52, 55), ("a", 95, 110)]
    devices = {"/device:TPU:0": [(n, s * MS, e * MS) for n, s, e in ops]}
    scopes = {"/device:TPU:0": {"a": ("fl.local_update", "fl.adam"),
                                "b": ("fl.round_down",), "c": (),
                                "d": None}}
    main = [("bench_window", 0, 100), ("serve.submit", 0, 3),
            ("gc.collect", 80, 85)]
    worker = [("serve.queue_wait", 0, 4), ("serve.group", 4, 75),
              ("serve.step", 5, 8), ("PjitFunction(f)", 6, 7),
              ("serve.copy_back", 58, 64), ("serve.resolve", 64, 75),
              ("serve.queue_wait", 75, 120)]
    lines = [[(n, s * MS, e * MS) for n, s, e in line]
             for line in (main, worker)]
    out = trace_scopes.reduce_program(devices, lines, scopes, ["d"],
                                      "bench_window")
    sec = lambda ms: pytest.approx(ms / 1e3)  # noqa: E731
    # leaves: a 10 + 5 (clipped), b 5, c 10, d 3; the while w is no leaf
    assert out["scopes"] == {
        "(conflict)": {"s": sec(3), "self_s": sec(3)},
        "(none)": {"s": sec(10), "self_s": sec(10)},
        "fl.adam": {"s": sec(15), "self_s": sec(15)},
        "fl.local_update": {"s": sec(15), "self_s": sec(0)},
        "fl.round_down": {"s": sec(5), "self_s": sec(5)}}
    assert out["scope_conflicts"] == ["d"]
    span = lambda n, s, self_s: {"count": n, "s": sec(s),  # noqa: E731
                                 "self_s": sec(self_s)}
    assert out["spans"] == {
        "gc.collect": span(1, 5, 5),
        "serve.copy_back": span(1, 6, 6),
        "serve.group": span(1, 71, 71 - 3 - 6 - 11),
        "serve.queue_wait": span(2, 4 + 25, 4 + 25),
        "serve.resolve": span(1, 11, 11),
        "serve.step": span(1, 3, 3),
        "serve.submit": span(1, 3, 3)}
    # busy [5, 60] and [95, 100]: idle [0, 5] and [60, 95], 40 ms; the
    # worker dispatched, the main thread's collection takes [80, 85]
    assert out["idle_by_span"] == {
        "(no program span)": sec(0),
        "gc.collect": sec(5),
        "serve.copy_back": sec(4),
        "serve.group": sec(1),
        "serve.queue_wait": sec(4 + 5 + 10),
        "serve.resolve": sec(11)}


# ---- a trace recorded on the chip -------------------------------------------


@pytest.fixture(scope="module")
def program():
    return trace_scopes.reduce_path(PROGRAM, "bench_window")


def test_chip_trace_scopes(program):
    sc = program["scopes"]
    assert set(sc) == set(STAGES) | {"(none)"}
    assert program["scope_conflicts"] == []
    us = {k: round(v["s"] * 1e6, 3) for k, v in sc.items()}
    assert us == {"(none)": 144.672, "fl.adam": 1.867, "fl.eval": 24.556,
                  "fl.local_update": 187.086, "fl.round_down": 23.574,
                  "fl.round_up": 7.522, "fl.window_gather": 48.953}
    # the gather and Adam nest in the local update; every leaf counts once
    # under its innermost scope
    lu = sc["fl.local_update"]
    assert lu["s"] == pytest.approx(lu["self_s"] + us["fl.window_gather"] / 1e6
                                    + us["fl.adam"] / 1e6)
    assert sum(v["self_s"] for v in sc.values()) \
        == pytest.approx(sum(program["op_s"].values()))


def test_chip_trace_spans(program):
    sp = program["spans"]
    # 17 submits, 6 groups (the server counted 6 batches in the window), two
    # collections (the forced one, 55.4 ms, and one the allocator started);
    # the worker's waits that were open when tracing began or ended are
    # not recorded, so 2 of them
    assert {k: v["count"] for k, v in sp.items()} == {
        "fl.dispatch": 1, "fl.finalize": 1, "fl.readback": 1,
        "gc.collect": 2, "serve.assemble": 6, "serve.coalesce": 3,
        "serve.copy_back": 6, "serve.group": 6, "serve.queue_wait": 2,
        "serve.resolve": 6, "serve.step": 6, "serve.submit": 17}
    assert sp["gc.collect"]["s"] == pytest.approx(0.055610814)
    assert sp["serve.group"]["s"] == pytest.approx(0.014579156)
    steps = sum(sp[n]["s"] for n in ("serve.assemble", "serve.step",
                                     "serve.copy_back", "serve.resolve"))
    assert sp["serve.group"]["self_s"] == pytest.approx(
        sp["serve.group"]["s"] - steps)


def test_chip_trace_idle_by_span(program):
    idle = program["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(
        program["window_s"] - program["busy_s"])
    # both collections fell in device-idle time
    assert idle["gc.collect"] == pytest.approx(0.055610814)
    # idle time the host spent in the job's readback and in copy-backs
    assert idle["fl.readback"] == pytest.approx(0.002933199)
    assert idle["serve.copy_back"] == pytest.approx(0.007775152)
    # the harness's sleeps, and the worker's unrecorded last wait
    assert idle["(no program span)"] == pytest.approx(0.055800718)
