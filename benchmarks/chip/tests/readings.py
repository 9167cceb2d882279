"""Readings that the limits of a cell's correctness comparison are set from.

    python3 benchmarks/chip/tests/readings.py --workload <cell> \
        --seeds <n> [<n> ...] [--seconds <s>] [--faults <name> ...]

For each seed, in one process: the cell is set up as a run sets it up (for
a serving cell, a short window at the cell's own load follows), then the
numbers compared are read for the program as the configuration states it,
and for the control: the same program with its matrix products one step
below the configuration's precision (``high``, three bfloat16 passes, where
the configuration states ``highest``), set up and run again from the same
seed. Each set of numbers goes through the harness's own comparison with
the cell's limits, which gives its ``correct``. With ``--faults`` each
fault in turn is planted in the program (see ``faults.py``) and only the
program's numbers are read. One JSON line per seed. The control needs the
chip: elsewhere JAX computes every precision in full float32.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
import run  # noqa: E402

STEP_BELOW = {"highest": "high"}


def numbers(kind, name, workload, config, mix, seed, seconds, log):
    """Set the cell up at ``config``'s precision and read its numbers."""
    import jax

    run.apply_precision(config)
    jax.clear_caches()
    cell = kind.Cell(run.Context(name, workload, config, mix, seed, seconds,
                                 log=log))
    cell.setup()
    if getattr(cell, "CHECKS_WINDOW", False):
        cell.window(seconds)
    cell.release()
    return cell.check()


def readings(name, seeds, seconds, fault=None, control=True, override=None,
             log=print):
    import faults

    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    if override is None:
        bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
        _, workload, config, mix = run.cell_files(name, bench)
    else:
        workload, config, mix = override
    kind = run.load_module(os.path.join(run.BENCH, "traffic",
                                        mix["kind"] + ".py"),
                           "bench_traffic_" + mix["kind"])
    sides = {"program": config}
    if control and fault is None:
        sides["control"] = dict(config, matmul_precision=STEP_BELOW[
            config["matmul_precision"]])
    out = []
    with faults.planted(fault):
        for seed in seeds:
            t0 = time.perf_counter()
            row = {"seed": seed, "fault": fault}
            for side, cfg in sides.items():
                row[side] = numbers(kind, name, workload, cfg, mix, seed,
                                    seconds, log)
                row[side + "_correct"] = run.compare(row[side],
                                                     workload["limits"])[0]
            row["seconds"] = time.perf_counter() - t0
            log(json.dumps(row))
            out.append(row)
    run.apply_precision(config)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", run.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if not args.cpu and jax.devices()[0].platform != "tpu":
        print("readings.py: no TPU", file=sys.stderr)
        return 2
    for fault in args.faults or [None]:
        readings(args.workload, args.seeds, args.seconds, fault=fault,
                 control=not args.no_control,
                 log=lambda m: print(m, flush=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
