"""CPU tests of the benchmark: operation counts, the trace reduction, and
whole runs of every cell cut to a size a test run holds, with the check for
a chip skipped.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

Each cell runs with its own limits: sound, it must come out correct; with a
fault planted under the timed path (``faults.py``) it must not. Cells whose
files are kept but which ``BENCHMARK.json`` leaves out run too. The control
(the program one precision step below its configuration) must come out not
correct at the cell's own size: that test needs the chip, since elsewhere
JAX computes every matrix product in full float32.
"""
from __future__ import annotations

import copy
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import faults  # noqa: E402
import flops  # noqa: E402
import readings  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

BENCHMARK = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
LEFT_OUT = sorted(f[:-len(".json")] for f in os.listdir(
    os.path.join(BENCH, "workloads")) if f[:-len(".json")] not in CELLS)
SEED = 2**33 + 17          # wider than 32 bits, as run seeds may be


def files_of(cell):
    """The cell's entry, files and end-to-end and per-layer metrics; a
    left-out cell reports what the benchmark's cells of its kind report."""
    if cell in CELLS:
        return run.cell_files(cell, BENCHMARK) + run.metrics_of(cell,
                                                                BENCHMARK)
    workload = run.load_json(os.path.join(BENCH, "workloads",
                                          cell + ".json"))
    config = run.load_json(os.path.join(BENCH, "configs",
                                        workload["config"] + ".json"))
    mix = run.load_json(os.path.join(BENCH, "traffic",
                                     workload["traffic"] + ".json"))
    like = next(c for c in CELLS if kind_of(c) == mix["kind"])
    entry = dict(name=cell, chips=1, config=workload["config"],
                 traffic=workload["traffic"])
    return (entry, workload, config, mix) + run.metrics_of(like, BENCHMARK)


def kind_of(cell):
    return files_of(cell)[3]["kind"]


ALL = CELLS + LEFT_OUT
FL = [c for c in ALL if kind_of(c) == "fl_jobs"]
SERVE = [c for c in ALL if kind_of(c) != "fl_jobs"]


def small(cell):
    """The cell's files with its data, rounds and load cut for a CPU run;
    the configuration keeps its widths except PatchTST's look-back and
    horizon, and the limits are the cell's own."""
    entry, workload, config, mix, e2e, layer = copy.deepcopy(files_of(cell))
    if mix["kind"] == "fl_jobs":
        mix.update(clients=4, rounds=4, eval_every=2, patience=5)
        if config["look_back"] > 128:
            config.update(look_back=128, horizon=8)
            mix.update(steps=400)
    else:
        mix.update(max_batch=8, in_flight=32, check_requests=256)
    return entry, workload, config, mix, e2e, layer


def run_small(cell, seed=SEED):
    return run.run_cell(cell, seed, 1.0, False, None, "cpu", {},
                        workload_override=small(cell), log=lambda m: None)


# ---------------------------------------------------------------------------
# operation counts
# ---------------------------------------------------------------------------


def test_forward_flops_match_hand_counts():
    c = run.load_json(os.path.join(BENCH, "configs", "logtst-paper.json"))
    # 15 tokens: embed 2*15*16*128, 3 feed-forwards 3*2*2*15*128*256, one
    # attention's projections 4*2*15*128*128 and core 2*2*15*15*128, head
    # 2*15*128*2
    assert flops.forward_flops(c) == 61_440 + 5_898_240 + 1_966_080 \
        + 115_200 + 7_680 == 8_048_640
    p = run.load_json(os.path.join(BENCH, "configs", "patchtst64.json"))
    # 63 tokens, three attention blocks, horizon 96
    assert flops.forward_flops(p) == 258_048 + 3 * 8_257_536 \
        + 3 * 8_257_536 + 3 * 2_032_128 + 1_548_288 == 57_447_936
    assert flops.train_flops(c) == 3 * 8_048_640


def test_mix_bytes_and_attention_core():
    assert flops.mix_bytes(2, 1000) == 2 * 1000 * 8.125 + 4000
    p = run.load_json(os.path.join(BENCH, "configs", "patchtst64.json"))
    assert flops.attention_core_flops(p, 1) == 4 * 63 * 63 * 128


def test_reference_parameter_counts_match_configs():
    from repro.core.forecast import num_params

    sys.path.insert(0, os.path.join(BENCH, "traffic"))
    import fl_jobs

    for name in ("logtst-paper", "patchtst64"):
        c = run.load_json(os.path.join(BENCH, "configs", name + ".json"))
        ref = run.load_module(os.path.join(BENCH, "references",
                                           c["reference"] + ".py"), "r")
        assert ref.param_count(c) == c["params"]
        assert num_params(fl_jobs.model_config(c)) == c["params"]


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def test_reduce_hand_made_trace():
    ms = 1_000_000
    devices = {"/device:TPU:0": [("while.1", 0 * ms, 6 * ms),
                                 ("fusion.1", 0 * ms, 2 * ms),
                                 ("fusion.2", 1 * ms, 3 * ms),
                                 ("psgf_mix", 5 * ms, 6 * ms),
                                 ("late", 9 * ms, 12 * ms)]}
    spans = [("bench_window", -1 * ms, 10 * ms), ("fl_job", 0, 6 * ms),
             ("submit", 3 * ms, 5 * ms)]
    r = trace_reduce.reduce(devices, spans, "bench_window")
    assert r["window_s"] == pytest.approx(11e-3)
    # busy: [0, 6] + [9, 10] (clipped) = 7 ms; the while loop holds the
    # three ops inside it and is not an op of its own
    assert r["busy_s"] == pytest.approx(7e-3)
    assert "while.1" not in r["op_s"]
    assert r["op_s"]["late"] == pytest.approx(1e-3)
    assert r["op_s"]["fusion.1"] == pytest.approx(2e-3)
    gaps = sorted((round(t * 1e3, 6), n) for n, t in
                  r["breakdown"]["idle_gaps"])
    # [-1, 0] no span, [6, 9] no span
    assert gaps == [(1.0, "no host span"), (3.0, "no host span")]
    assert trace_reduce.kernel_seconds(r, "psgf") == pytest.approx(1e-3)
    assert trace_reduce.kernel_seconds(r, "flash") is None


def test_reduce_recorded_trace():
    """A trace recorded on a TPU v5e: three rounds of one fused psgf_mix call
    and one 512x512 matmul, inside a window span."""
    path = os.path.join(HERE, "data", "small.xplane.pb")
    devices, spans = trace_reduce.read(path)
    assert list(devices) == ["/device:TPU:0"]
    r = trace_reduce.reduce(devices, spans, "bench_window")
    assert 0 < r["busy_s"] < r["window_s"]
    kernel = run.load_module(os.path.join(BENCH, "metrics",
                                          "psgf_mix_roofline_pct.py"),
                             "m").KERNEL
    ops = [o for o in devices["/device:TPU:0"] if kernel.search(o[0])]
    # the three calls of the mix kernel, and not the ops that read its output
    assert len(ops) == 3
    assert trace_reduce.kernel_seconds(r, kernel.pattern) > 0


# ---------------------------------------------------------------------------
# whole runs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", ALL)
def test_cell_is_correct(cell):
    res = run_small(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    names = {m["name"] for m in files_of(cell)[4]}
    assert set(res["metrics"]) == names
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell,fault",
                         [(c, f) for c in FL for f in faults.TRAINING]
                         + [(c, f) for c in SERVE for f in faults.SERVING])
def test_fault_is_caught(cell, fault):
    with faults.planted(fault):
        res = run_small(cell)
    assert not res["correct"], res["checks"]


def on_tpu():
    import jax

    return jax.devices()[0].platform == "tpu"


@pytest.mark.skipif(not on_tpu(), reason="the control needs the chip")
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_caught(cell):
    """At the cell's own size: the program as configured comes out correct,
    the control (its matrix products one precision step lower) not."""
    row = readings.readings(cell, [SEED], 3.0, log=lambda m: None)[0]
    assert row["program_correct"] and not row["control_correct"], row
