"""End-to-end driver (deliverable b): the paper's full system, expressed
through the Forecaster/ExperimentSpec API.

Pipeline (paper §III.B): synthetic UK-EV-like data -> station cleaning ->
DTW K-means clustering -> per-cluster federated training of LoGTST under
Online-Fed / PSO-Fed / PSGF-Fed -> RMSE + cumulative communication report
(Tables II/III analogue). With ``--ckpt-dir`` every trained global model is
written in ``load_forecaster`` format, ready for
``python -m repro.launch.serve_forecast``.

  PYTHONPATH=src python examples/federated_ev.py [--rounds 200] [--clusters 3]
  PYTHONPATH=src python examples/federated_ev.py --small --rounds 20   # CI smoke
"""
import argparse

import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.core.tasks import ExperimentSpec, get_task, run_experiment, task_forecaster


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=None,
                    help="max FL rounds (default: 150, or 30 with --small)")
    ap.add_argument("--clusters", type=int, default=3)
    ap.add_argument("--clients", type=int, default=58)
    ap.add_argument("--small", action="store_true",
                    help="quick preset: small model + fewer rounds for a fast demo")
    ap.add_argument("--ckpt-dir", default=None,
                    help="write per-(policy, cluster) global-model checkpoints")
    ap.add_argument("--streaming", action="store_true",
                    help="stream windows from the raw (K, T) series on device "
                         "(FLConfig.streaming_windows) instead of "
                         "materializing (K, n_win, L+T) tensors — "
                         "bit-identical results, ~(L+T)x less data memory")
    ap.add_argument("--participation", default=None,
                    help="per-round participant cohort: an int cohort size "
                         "(must fit the smallest cluster) or a float fraction "
                         "in (0, 1] (FLConfig.participation); only the "
                         "sampled cohort trains/communicates each round")
    args = ap.parse_args()
    enable_compile_cache()
    if args.participation is not None:
        # "0.25" -> fraction of each cluster, "4" -> fixed cohort size
        args.participation = (float(args.participation)
                              if "." in args.participation
                              else int(args.participation))
    rounds = args.rounds if args.rounds is not None else (30 if args.small else 150)

    # quick preset swaps in look_back 64 + the d_model-32 model; data geometry
    # (num_days 420, --clients stations) matches the paper-sized task
    task = get_task("ev", quick=args.small, clusters=args.clusters,
                    num_clients=args.clients, num_days=420,
                    min_cluster_clients=4)
    series = task.series()
    print(f"1) generated EV-like data for {args.clients} charging stations")
    labels = task.cluster_labels(series)
    print(f"2) DTW K-means -> cluster sizes: {np.bincount(labels).tolist()}")

    model = task_forecaster(task, "logtst", quick=args.small)
    print(f"3) model: {model.name}, {model.num_params():,} params")

    grid = (
        ("online", {}),
        ("pso", dict(share_ratio=0.3)),
        ("psgf", dict(share_ratio=0.3, forward_ratio=0.2)),
    )
    print(f"4) federated training per cluster, {rounds} max rounds")
    # scan driver: patience is checked at eval_every-round boundaries
    spec = ExperimentSpec(task=task, model=model, grid=grid, select_ratio=0.5,
                          local_steps=4, batch_size=32, max_rounds=rounds,
                          patience=10, eval_every=25,
                          streaming_windows=args.streaming,
                          participation=args.participation)
    res = run_experiment(
        spec, checkpoint_dir=args.ckpt_dir, series=series, labels=labels,
        on_row=lambda r: print(
            f"   {r['policy'].split('-')[0]:7s} cluster {r['cluster']}: "
            f"rounds {r['rounds']:4d} rmse {r['rmse']:.4f} "
            f"comm {r['comm_params']:.2e}"))

    report = []
    for policy, _ in grid:
        rows = [r for r in res["rows"] if r["policy"].split("-")[0] == policy]
        report.append((policy, float(np.mean([r["rmse"] for r in rows])),
                       sum(r["comm_params"] for r in rows)))

    print("\n== summary (Tables II/III analogue) ==")
    print(f"{'policy':10s} {'RMSE':>8s} {'#Params (Comm.)':>16s}")
    for policy, rmse, comm in report:
        print(f"{policy:10s} {rmse:8.4f} {comm:16.3e}")
    online = next(r for r in report if r[0] == "online")
    psgf = next(r for r in report if r[0] == "psgf")
    print(f"\nPSGF-Fed comm reduction vs Online-Fed: "
          f"{(1 - psgf[2] / online[2]):.0%} at RMSE delta "
          f"{psgf[1] - online[1]:+.4f}")


if __name__ == "__main__":
    main()
