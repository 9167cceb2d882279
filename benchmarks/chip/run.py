"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by name, so a cell, a configuration, a traffic mix or a
per-layer metric is added with files alone:

  * ``BENCHMARK.json`` (repository root) lists the cells and which metrics
    each reports;
  * ``workloads/<cell>.json`` names the cell's configuration and traffic mix
    and holds the limits of its correctness comparison;
  * ``configs/<config>.json`` holds the model as it is run and names its
    plain reference, ``references/<reference>.py``;
  * ``traffic/<mix>.json`` holds a traffic mix's parameters and names its
    kind, ``traffic/<kind>.py``, the general generator and driver of that
    kind of traffic;
  * ``metrics/<metric>.py`` reads one per-layer metric (names after the first
    ``.`` select a cell's variant of a shared reader: ``a.b`` is read by
    ``metrics/a.py``).

A run sets the cell up (data and weights from ``--seed``, every program it
will use compiled and warmed), measures for ``--seconds``, then compares
what the timed path produced with the plain reference. With ``--trace 0`` it
reports the cell's end-to-end metrics; with ``--trace 1`` the window runs
under the profiler and the run reports the per-layer metrics read from the
trace and the program's counters. The last line of standard output is one
JSON object; the last lines of standard error are the numbers compared,
each beside its limit.

The run needs the accelerator the cell asks for: it exits with a non-zero
code and prints no result when JAX finds no TPU or too few chips.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(BENCH, ".traces")


class BenchError(Exception):
    """A run that cannot produce a result: exit non-zero, print none."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchError(f"no such file: {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed32(seed: int, purpose: str) -> int:
    """A 32-bit seed for one purpose, drawn from the run's seed (which may
    exceed 32 bits)."""
    h = hashlib.sha256(f"{seed}/{purpose}".encode()).digest()
    return int.from_bytes(h[:4], "little")


class CompileClock:
    """Seconds in XLA's backend compiler and the number of programs lowered,
    from JAX's own monitoring events (a program loaded from the persistent
    cache is lowered but not compiled)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.lowered = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1


class Context:
    """What a traffic kind gets from the harness: the cell's files, the seed
    and its keys, host spans, and the plain reference."""

    def __init__(self, name, workload, config, mix, seed, seconds,
                 tracing=False, log=print):
        self.name = name
        self.seconds = seconds
        self.log = log
        self.workload = workload
        self.config = config
        self.mix = mix
        self.seed = seed
        self.data_seed = seed32(seed, "data")
        self.tracing = tracing
        self.reference = load_module(
            os.path.join(BENCH, "references", config["reference"] + ".py"),
            "bench_reference_" + config["reference"])

    def key(self, purpose: str):
        import jax

        return jax.random.PRNGKey(seed32(self.seed, purpose))

    def rng(self, purpose: str):
        import numpy as np

        return np.random.default_rng(seed32(self.seed, purpose))

    def span(self, name: str):
        """A host span in the profiler's trace (only while tracing)."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


def cell_files(name: str, bench: dict):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    entry = cells[name]
    workload = load_json(os.path.join(BENCH, "workloads", name + ".json"))
    if (workload["config"], workload["traffic"]) != (entry["config"],
                                                     entry["traffic"]):
        raise BenchError(f"workloads/{name}.json disagrees with "
                         f"BENCHMARK.json on its config or traffic")
    config = load_json(os.path.join(BENCH, "configs",
                                    entry["config"] + ".json"))
    mix = load_json(os.path.join(BENCH, "traffic", entry["traffic"] + ".json"))
    return entry, workload, config, mix


def metrics_of(name: str, bench: dict):
    """The end-to-end and per-layer metric entries this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    moves = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in moves)]
    return e2e, layer


def apply_precision(config: dict):
    """Compute the program's matrix products as the configuration states
    (``matmul_precision``; JAX's default on a TPU is one bfloat16 pass). Set
    process-wide, so that the program's threads see it too."""
    import jax

    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])


def compare(numbers: dict, limits: dict):
    """Each number compared against its limit: correct when every number is
    finite and at most its limit."""
    missing = sorted(set(limits) - set(numbers))
    if missing:
        raise BenchError(f"the comparison gave no {missing}")
    checks = {k: {"value": float(numbers[k]), "limit": float(limits[k])}
              for k in sorted(limits)}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             bench: dict, device_kind: str, peaks: dict,
             workload_override=None, log=print):
    """Set up, measure and check one cell; return the result object.
    ``workload_override`` replaces the cell's files (tests run tiny cells
    through here)."""
    import jax

    if workload_override is None:
        entry, workload, config, mix = cell_files(name, bench)
        e2e, layer = metrics_of(name, bench)
    else:
        entry, workload, config, mix, e2e, layer = workload_override
    apply_precision(config)
    ctx = Context(name, workload, config, mix, seed, seconds, tracing=trace,
                  log=log)
    kind = load_module(os.path.join(BENCH, "traffic", mix["kind"] + ".py"),
                       "bench_traffic_" + mix["kind"])
    clock = CompileClock()
    cell = kind.Cell(ctx)
    cell.setup()
    setup_s = time.perf_counter() - T_START
    log(f"setup {setup_s:.3f} s, backend compile {clock.compile_s:.3f} s, "
        f"{clock.lowered} programs lowered")

    trace_dir = os.path.join(TRACE_DIR, name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    lowered0 = clock.lowered
    with ctx.span("bench_window"):
        rec = cell.window(seconds)
    in_window = clock.lowered - lowered0
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        trace_reduce = load_module(os.path.join(BENCH, "trace_reduce.py"),
                                   "bench_trace_reduce")
        reduced = trace_reduce.reduce_dir(trace_dir, window="bench_window")
        shutil.rmtree(trace_dir, ignore_errors=True)
    if in_window:
        raise BenchError(f"{in_window} programs were lowered inside the "
                         f"measured window: set-up missed a shape")
    devices = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:entry["chips"]])
    cell.release()

    numbers = cell.check()
    correct, checks = compare(numbers, workload["limits"])
    for k, v in sorted(numbers.items()):
        if k not in checks:
            log(f"reading {k} {v:.6e} (not compared)")

    metrics = {}
    if not trace:
        values = dict(rec["e2e"], setup_s=setup_s)
        for m in e2e:
            if m["name"] not in values:
                raise BenchError(f"the {mix['kind']} traffic gives no "
                                 f"{m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        rc = ReadContext(ctx, rec, reduced, peaks[device_kind])
        for m in layer:
            base = m["name"].split(".")[0]
            reader = load_module(os.path.join(BENCH, "metrics", base + ".py"),
                                 "bench_metric_" + base)
            value = reader.read(rc)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": entry["chips"], "memory_peak_bytes": peak}
    result = {"correct": bool(correct and rec["failed"] == 0),
              "attempted": int(rec["attempted"]), "failed": int(rec["failed"]),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = checks
    return result


class ReadContext:
    """What a per-layer metric's reader sees: the cell's configuration and
    traffic, the window's record (counts and the program's counters), the
    reduced trace and the chip's peaks."""

    def __init__(self, ctx, record, trace, peaks):
        self.config = ctx.config
        self.mix = ctx.mix
        self.record = record
        self.counts = record.get("counts", {})
        self.trace = trace
        self.peaks = peaks
        self.flops = load_module(os.path.join(BENCH, "flops.py"),
                                 "bench_flops")
        self.trace_reduce = load_module(os.path.join(BENCH, "trace_reduce.py"),
                                        "bench_trace_reduce")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        entry = cell_files(args.workload, bench)[0]
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise BenchError("the system under test (src/repro) is not in "
                             "this checkout")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX's first device is "
                             f"{devices[0].platform!r}")
        if len(devices) < entry["chips"]:
            raise BenchError(f"the cell needs {entry['chips']} chips, JAX "
                             f"sees {len(devices)}")
        peaks = load_json(os.path.join(BENCH, "peaks.json"))
        kind = devices[0].device_kind
        if kind not in peaks:
            raise BenchError(f"no peaks for device kind {kind!r} in "
                             f"peaks.json")
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), bench, kind, peaks, log=log)
    except BenchError as e:
        log(f"run.py: {e}")
        return 2
    for k, c in result["checks"].items():
        log(f"check {k} {c['value']:.6e} limit {c['limit']:.6e} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
