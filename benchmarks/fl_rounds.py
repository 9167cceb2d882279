"""FL round-driver benchmark: the engine's three drivers head-to-head
(repro/core/fl/engine.py).

Driver selection (``run_fl(driver=...)``), by how much of the run compiles
into one dispatch:

  * ``loop``  — one dispatch + two host syncs per round (the seed design);
  * ``scan``  — ``eval_every`` rounds per dispatch, donated carry, host-side
    convergence/patience + RMSE eval at every chunk boundary;
  * ``while`` — the FULL run as ONE dispatch: a ``lax.while_loop`` over scan
    chunks carries ``(best_loss, stall, stop)`` on-device and the per-chunk
    RMSE is computed in-graph, so the host reads results back exactly once.

Three measurements seed the perf trajectory of the round hot path:

  * ``driver`` — rounds/sec of each driver on a dispatch-bound micro-model
    (50 rounds, ``eval_every=5`` so scan pays 10 host round-trips that the
    while driver folds on-device). All drivers are verified to produce the
    SAME final RMSE (within 1e-5; round-by-round identical math,
    bitwise-equal on the pinned CPU toolchain). Each driver also reports its
    measured host<->device transfer counts (``jax.transfer_guard("log")``
    captured at the fd level — the guard logs from C++), the direct evidence
    for the dispatch-count story. On the CPU backend device-to-host reads are
    zero-copy and never logged (count 0 is expected); the host-to-device
    count — scalars/operands shipped per dispatch — is the per-driver
    round-trip proxy (~17x fewer for while than scan/loop).
  * ``scaling`` — wall time of a chunked-vmap round at num_clients=512
    (``FLConfig.client_chunk``), the regime the scan/while drivers + chunking
    are for (paper uses 58 clients; related FL-for-EV work studies thousands).
  * ``streaming`` — materialized ``(K, n_win, L+T)`` windows vs the raw
    ``(K, T)`` streaming pipeline (``FLConfig.streaming_windows``) on the
    while driver: training-data device bytes (the H2D payload on a real
    accelerator), live device-buffer bytes after the run
    (``jax.live_arrays()``), host-transfer counts and rounds/sec. Streaming
    must keep the while driver's one-dispatch property (h2d pinned at 22 on
    the micro-bench) and rounds/sec within 10% while cutting training-data
    memory ~``(L+T)``x — measured at the CI micro config AND at
    num_clients=512 with the full preset's look_back=128 (``--quick`` runs
    only the micro config). RMSE must match BITWISE between the layouts.

  * ``participation`` — per-round cohort sampling (``FLConfig.
    participation``): the while driver's 22-host-transfer pin must hold with
    sampling compiled into the round, and a same-K A/B (full participation vs
    a K/16 cohort, identical config otherwise) must show the ~K/S round-cost
    drop — >= 5x rounds/sec is asserted in full mode — plus the matching
    comm-byte reduction (bytes accrue only for sampled clients).
  * ``host_store`` — ``run_fl(driver="host")`` at ``num_clients=100_000``,
    ``participation=256``: the client fleet (params + Adam moments + raw
    series) lives in a host-resident numpy ``ClientStore`` and only each
    round's cohort touches the device. Records rounds/sec, host-store /
    peak-RSS / live-device bytes, and the exact comm accounting (asserted
    <= rounds * 2 * S * D params — cohort-only, never O(K)).

  * ``comm_bits`` — wire-format A/B at matched rounds (fp32 / bf16 /
    int8+per-leaf-scale, ``FLConfig.comm_bits``); asserts int8 bytes
    <= 0.55x bf16 with final RMSE within 2% of fp32. Runs in quick mode too.

  * ``multihost`` (``--multihost``) — single- vs 2-process
    ``jax.distributed`` host-driver at the ``host_store`` config
    (``num_clients=100_000``, ``participation=256``): rounds/sec and
    per-process peak RSS on each side, with the 2-process run asserted
    BITWISE identical to the single-process run (losses, comm, RMSE, final
    weights) and the host store asserted to split exactly across processes.
    This harness is CPU-only: its children run ``JAX_PLATFORMS=cpu`` (gloo
    collectives), and the parent never touches JAX — a chip belongs to one
    process, so the parent takes ``env`` from the children's reports.

  PYTHONPATH=src python -m benchmarks.fl_rounds [--quick | --multihost]

``--quick`` (the CI smoke) still covers ALL THREE drivers, the streaming
micro A/B and the participation micro pin + a small same-K A/B; it trims
repetitions and skips the 512-client, 4096-client and 100k-client runs.

Results -> experiments/fl_rounds/results.json.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fl.engine import FLConfig, run_fl
from repro.core.forecaster import get_forecaster
from repro.core.tasks import get_task

from benchmarks.common import record_env, save_json

DRIVERS = ("loop", "scan", "while")

_MICRO = dict(look_back=8, horizon=1, d_model=8, num_heads=2, d_ff=8,
              patch_len=4, stride=4, mixers=("id",))


def _data(num_clients: int, look_back: int, horizon: int, num_days: int = 40,
          streaming: bool = False):
    task = get_task("nn5", seed=0, num_clients=num_clients, num_days=num_days,
                    look_back=look_back, horizon=horizon)
    tr, va, te, _ = task.client_data(task.series(), streaming=streaming)
    return jnp.asarray(tr), jnp.asarray(te)


def count_transfers(fn):
    """Run ``fn()`` under ``jax.transfer_guard("log")`` and count the logged
    host<->device transfers. The guard logs from C++ directly to fd 2, so the
    capture has to happen at the file-descriptor level, not via python
    logging."""
    sys.stderr.flush()
    saved = os.dup(2)
    with tempfile.TemporaryFile(mode="w+") as tmp:
        os.dup2(tmp.fileno(), 2)
        try:
            with jax.transfer_guard("log"):
                out = fn()
            jax.effects_barrier()
        finally:
            sys.stderr.flush()
            os.dup2(saved, 2)
            os.close(saved)
        tmp.seek(0)
        txt = tmp.read()
    return out, {"host_to_device": txt.count("host-to-device transfer"),
                 "device_to_host": txt.count("device-to-host transfer")}


def _time_driver(model_cfg, fl_cfg, tr, te, rounds: int, driver: str,
                 eval_every: int, reps: int = 3):
    """Best-of-reps wall time for a full run (compile excluded via warmup),
    plus the transfer counts of one instrumented run."""
    kw = dict(max_rounds=rounds, patience=rounds + 1, eval_every=eval_every,
              driver=driver)
    run = lambda: run_fl(model_cfg, fl_cfg, tr, te, jax.random.PRNGKey(0), **kw)
    run()  # warmup/compile
    hist, transfers = count_transfers(run)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        hist = run()
        best = min(best, time.perf_counter() - t0)
    return best, hist, transfers


def bench_driver(rounds: int = 50, reps: int = 3, eval_every: int = 5):
    """loop vs scan vs while on a dispatch-bound micro-model (the regime where
    the per-round/per-chunk host round-trip is the cost, not the local math).
    ``eval_every=5`` keeps the convergence-check cadence realistic: scan pays
    ``rounds / eval_every`` host syncs + eager RMSE evals that the while
    driver folds into its single dispatch."""
    model_cfg = get_forecaster(
        "idformer", look_back=8, horizon=1, d_model=8, num_heads=2, d_ff=8,
        patch_len=4, stride=4, mixers=("id",)).cfg
    fl_cfg = FLConfig(policy="psgf", num_clients=4, local_steps=1, batch_size=2)
    tr, te = _data(4, 8, 1)

    out = {}
    for driver in DRIVERS:
        secs, hist, transfers = _time_driver(model_cfg, fl_cfg, tr, te, rounds,
                                             driver, eval_every, reps)
        out[driver] = {"seconds": secs, "rounds_per_sec": rounds / secs,
                       "final_rmse": hist["final_rmse"],
                       "transfers": transfers}
        print(f"fl_rounds,{driver},{rounds / secs:.1f} rounds/s,"
              f"rmse={hist['final_rmse']:.6f},"
              f"d2h={transfers['device_to_host']},"
              f"h2d={transfers['host_to_device']}", flush=True)

    out["speedup_scan_over_loop"] = (out["scan"]["rounds_per_sec"]
                                     / out["loop"]["rounds_per_sec"])
    out["speedup_while_over_scan"] = (out["while"]["rounds_per_sec"]
                                      / out["scan"]["rounds_per_sec"])
    rmse_delta = max(abs(out[d]["final_rmse"] - out["loop"]["final_rmse"])
                     for d in DRIVERS)
    out["rmse_delta"] = rmse_delta
    print(f"fl_rounds,speedup,scan/loop={out['speedup_scan_over_loop']:.2f}x,"
          f"while/scan={out['speedup_while_over_scan']:.2f}x,"
          f"rmse_delta={rmse_delta:.2e}", flush=True)
    assert rmse_delta < 1e-5, "drivers diverged — all three must agree"
    return out


def bench_scaling(num_clients: int = 512, client_chunk: int = 64,
                  rounds: int = 3):
    """num_clients >> paper scale via chunked vmap (client_chunk bounds live
    activations; without it the vmapped LocalUpdate replicates all K)."""
    model_cfg = get_forecaster("logtst", look_back=16, horizon=2, d_model=8,
                               num_heads=2, d_ff=16, patch_len=8, stride=4).cfg
    fl_cfg = FLConfig(policy="psgf", num_clients=num_clients, local_steps=1,
                      batch_size=4, client_chunk=client_chunk)
    tr, te = _data(num_clients, 16, 2, num_days=60)
    t0 = time.perf_counter()
    hist = run_fl(model_cfg, fl_cfg, tr, te, jax.random.PRNGKey(0),
                  max_rounds=rounds, patience=rounds + 1, eval_every=rounds)
    secs = time.perf_counter() - t0
    row = {"num_clients": num_clients, "client_chunk": client_chunk,
           "rounds": rounds, "seconds": secs,
           "final_rmse": hist["final_rmse"],
           "finite": bool(np.isfinite(hist["final_rmse"]))}
    print(f"fl_rounds,scale_K{num_clients}_chunk{client_chunk},"
          f"{secs:.1f}s/{rounds}r,rmse={hist['final_rmse']:.4f}", flush=True)
    return row


def _live_device_bytes() -> int:
    """Total bytes of all live device buffers — the residency snapshot the
    streaming A/B compares (taken while the run's data + state are still
    referenced, so the training-data buffers dominate)."""
    return int(sum(a.nbytes for a in jax.live_arrays()))


def bench_streaming_case(name: str, model_cfg, fl_kw: dict, data_kw: dict,
                         rounds: int, eval_every: int, reps: int = 2):
    """ONE materialized-vs-streaming A/B on the while driver: same model,
    same FLConfig, same seed — only the data layout (and the matching
    ``streaming_windows`` flag) differs. Records training-data device bytes
    (== the H2D payload for the training data on a real accelerator; the CPU
    backend's transfer guard logs only per-dispatch operand shipments, which
    are counted separately), live device-buffer bytes after the run, transfer
    counts and best-of-reps rounds/sec. The layouts must agree on RMSE
    BITWISE — same RNG, same gathered values."""
    out = {}
    for mode in ("materialized", "streaming"):
        streaming = mode == "streaming"
        tr, te = _data(streaming=streaming, **data_kw)
        fl_cfg = FLConfig(streaming_windows=streaming, **fl_kw)
        best, hist, transfers = _time_driver(model_cfg, fl_cfg, tr, te,
                                             rounds, "while", eval_every, reps)
        out[mode] = {
            "train_shape": list(tr.shape),
            "test_shape": list(te.shape),
            "train_data_bytes": int(tr.nbytes + te.nbytes),
            "live_device_bytes": _live_device_bytes(),
            "transfers": transfers,
            "rounds_per_sec": rounds / best,
            "final_rmse": hist["final_rmse"],
        }
        print(f"fl_rounds,streaming_{name},{mode},"
              f"data={out[mode]['train_data_bytes'] / 1e6:.3f}MB,"
              f"live={out[mode]['live_device_bytes'] / 1e6:.3f}MB,"
              f"{rounds / best:.1f} rounds/s,"
              f"h2d={transfers['host_to_device']},"
              f"rmse={hist['final_rmse']:.6f}", flush=True)
        del tr, te, hist  # drop this layout's buffers before the next snapshot
    mat, st = out["materialized"], out["streaming"]
    out["train_data_reduction"] = mat["train_data_bytes"] / st["train_data_bytes"]
    out["live_bytes_reduction"] = mat["live_device_bytes"] / st["live_device_bytes"]
    out["rounds_per_sec_ratio"] = st["rounds_per_sec"] / mat["rounds_per_sec"]
    out["rmse_bitwise_equal"] = mat["final_rmse"] == st["final_rmse"]
    print(f"fl_rounds,streaming_{name},reduction="
          f"{out['train_data_reduction']:.1f}x data / "
          f"{out['live_bytes_reduction']:.1f}x live,"
          f"speed={out['rounds_per_sec_ratio']:.2f}x,"
          f"rmse_equal={out['rmse_bitwise_equal']}", flush=True)
    # bit-identity is scoped to the pinned CPU toolchain (the gather vs
    # direct-indexing HLO may fuse differently elsewhere); other backends
    # still must agree to tolerance
    if jax.default_backend() == "cpu":
        assert out["rmse_bitwise_equal"], \
            "streaming diverged from materialized — layouts must agree bitwise"
    else:
        assert abs(mat["final_rmse"] - st["final_rmse"]) < 1e-5, \
            "streaming diverged from materialized beyond tolerance"
    return out


def bench_streaming(quick: bool = True):
    """The streaming-pipeline A/B at two scales: the dispatch-bound micro
    config (the CI smoke — also guards the while driver's 22-transfer
    one-dispatch property under streaming) and, in full mode, num_clients=512
    at the full preset's look_back=128 — the regime the streaming pipeline is
    FOR (max_rounds*n_win*(L+T) floats of windows vs one (K, T) residency)."""
    micro_model = get_forecaster(
        "idformer", look_back=8, horizon=1, d_model=8, num_heads=2, d_ff=8,
        patch_len=4, stride=4, mixers=("id",)).cfg
    out = {"micro": bench_streaming_case(
        "micro", micro_model,
        fl_kw=dict(policy="psgf", num_clients=4, local_steps=1, batch_size=2),
        data_kw=dict(num_clients=4, look_back=8, horizon=1),
        rounds=50, eval_every=5, reps=2 if quick else 5)}
    for mode in ("materialized", "streaming"):
        h2d = out["micro"][mode]["transfers"]["host_to_device"]
        assert h2d <= 22, (
            f"{mode} while-driver run regressed to {h2d} host transfers "
            "(pin: 22) — the one-dispatch property broke")
    if not quick:
        model_512 = get_forecaster(
            "logtst", look_back=128, horizon=2, d_model=8, num_heads=2,
            d_ff=16, patch_len=16, stride=8).cfg
        out["clients512"] = bench_streaming_case(
            "512", model_512,
            fl_kw=dict(policy="psgf", num_clients=512, local_steps=1,
                       batch_size=4, client_chunk=64),
            data_kw=dict(num_clients=512, look_back=128, horizon=2,
                         num_days=420),
            rounds=2, eval_every=2, reps=1)
        assert out["clients512"]["train_data_reduction"] >= 10, (
            "streaming must cut 512-client training-data memory >= 10x, got "
            f"{out['clients512']['train_data_reduction']:.1f}x")
    return out


def bench_participation(quick: bool = True):
    """Per-round cohort sampling (``FLConfig.participation``), two claims:

    1. the while driver's one-dispatch property survives sampling — the
       cohort gather/scatter compiles INTO the round, so the micro-bench
       host-transfer pin (22) must hold unchanged;
    2. same-K economics: at ``participation = K/16`` the round hot path
       (LocalUpdate + gating on S instead of K clients) must deliver >= 5x
       rounds/sec at a matching comm-byte cut, with NOTHING else different —
       same model, same data, same seed, same while driver.
    """
    model_cfg = get_forecaster("idformer", **_MICRO).cfg
    out = {}

    # (1) host-transfer pin under sampling (the streaming micro config with a
    # half-fleet cohort; same 50-round / eval_every=5 cadence as the pin)
    tr, te = _data(4, 8, 1, streaming=True)
    fl_samp = FLConfig(policy="psgf", num_clients=4, local_steps=1,
                       batch_size=2, streaming_windows=True, participation=2)
    _, hist, transfers = _time_driver(model_cfg, fl_samp, tr, te, 50, "while",
                                      5, reps=1)
    out["micro_sampled"] = {"num_clients": 4, "participation": 2,
                            "transfers": transfers,
                            "final_rmse": hist["final_rmse"]}
    print(f"fl_rounds,participation_micro,K=4,S=2,"
          f"h2d={transfers['host_to_device']},"
          f"rmse={hist['final_rmse']:.6f}", flush=True)
    assert transfers["host_to_device"] <= 22, (
        f"sampled while-driver run regressed to "
        f"{transfers['host_to_device']} host transfers (pin: 22) — cohort "
        "gather/scatter must compile into the round")

    # (2) same-K A/B at a K/16 cohort
    K = 512 if quick else 4096
    S = K // 16
    rounds = 10 if quick else 20
    tr, te = _data(K, 8, 1, streaming=True)
    base = dict(policy="psgf", num_clients=K, local_steps=1, batch_size=2,
                streaming_windows=True, client_chunk=min(64, S))
    ab = {}
    for name, part in (("full", None), ("sampled", S)):
        fl_cfg = FLConfig(participation=part, **base)
        best, hist, transfers = _time_driver(model_cfg, fl_cfg, tr, te,
                                             rounds, "while", rounds,
                                             reps=1 if quick else 3)
        ab[name] = {"participation": part if part is not None else K,
                    "seconds": best, "rounds_per_sec": rounds / best,
                    "final_rmse": hist["final_rmse"],
                    "comm_params": hist["final_comm"],
                    "transfers": transfers}
        print(f"fl_rounds,participation_K{K},{name},"
              f"{rounds / best:.2f} rounds/s,"
              f"comm={hist['final_comm']:.3e},"
              f"rmse={hist['final_rmse']:.4f}", flush=True)
    ab["speedup_sampled_over_full"] = (ab["sampled"]["rounds_per_sec"]
                                       / ab["full"]["rounds_per_sec"])
    ab["comm_reduction"] = (ab["full"]["comm_params"]
                            / ab["sampled"]["comm_params"])
    out["same_K"] = {"num_clients": K, "cohort": S, "rounds": rounds, **ab}
    print(f"fl_rounds,participation_K{K},speedup="
          f"{ab['speedup_sampled_over_full']:.2f}x,"
          f"comm_reduction={ab['comm_reduction']:.2f}x", flush=True)
    if not quick:
        assert ab["speedup_sampled_over_full"] >= 5.0, (
            f"participation=K/16 must buy >= 5x rounds/sec, got "
            f"{ab['speedup_sampled_over_full']:.2f}x")
    return out


def bench_host_store(num_clients: int = 100_000, cohort: int = 256,
                     rounds: int = 30):
    """``run_fl(driver="host")`` at deployment scale: the ``(K, D)`` client
    state + raw ``(K, T)`` series live in a host-resident numpy
    ``ClientStore`` and only each round's size-``cohort`` rows are ever
    device-resident. Records rounds/sec, the host/device byte split (store
    bytes, peak process RSS, live device buffers after the run) and the
    exact comm accounting — asserted cohort-only: at most
    ``rounds * 2 * S * D`` shared params regardless of K."""
    import resource

    model = get_forecaster("idformer", **_MICRO)
    model_cfg = model.cfg
    D = model.num_params()
    task = get_task("nn5", seed=0, num_clients=num_clients, num_days=40,
                    look_back=8, horizon=1)
    tr, va, te, _ = task.client_data(task.series(), streaming=True)
    fl_cfg = FLConfig(policy="psgf", num_clients=num_clients, local_steps=1,
                      batch_size=2, streaming_windows=True,
                      participation=cohort, client_chunk=cohort)
    kw = dict(policy=None, driver="host")
    run_fl(model_cfg, fl_cfg, tr, te, jax.random.PRNGKey(0), max_rounds=1,
           patience=2, eval_every=1, **kw)        # warmup/compile
    t0 = time.perf_counter()
    hist = run_fl(model_cfg, fl_cfg, tr, te, jax.random.PRNGKey(0),
                  max_rounds=rounds, patience=rounds + 1, eval_every=rounds,
                  **kw)
    secs = time.perf_counter() - t0
    store = hist["client_store"]
    comm_bound = rounds * 2.0 * cohort * D
    row = {
        "num_clients": num_clients, "participation": cohort,
        "num_params": D, "rounds": rounds, "seconds": secs,
        "rounds_per_sec": rounds / secs,
        "host_store_bytes": store.nbytes,
        "host_store_state_bytes": store.state_nbytes,
        "host_store_series_bytes": store.series_nbytes,
        "peak_host_rss_bytes": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024,
        "live_device_bytes": _live_device_bytes(),
        "comm_params": hist["final_comm"],
        "comm_bytes": hist["final_comm"] * (fl_cfg.comm_bits / 8.0),
        "comm_cohort_bound_params": comm_bound,
        "final_rmse": hist["final_rmse"],
    }
    print(f"fl_rounds,host_store,K={num_clients},S={cohort},"
          f"{row['rounds_per_sec']:.2f} rounds/s,"
          f"store={row['host_store_bytes'] / 1e6:.1f}MB,"
          f"rss={row['peak_host_rss_bytes'] / 1e6:.1f}MB,"
          f"live_dev={row['live_device_bytes'] / 1e6:.3f}MB,"
          f"comm={row['comm_params']:.3e}", flush=True)
    assert row["comm_params"] <= comm_bound, (
        f"comm accounting leaked beyond the cohort: {row['comm_params']:.3e} "
        f"params > bound {comm_bound:.3e} (= rounds * 2 * S * D)")
    assert np.isfinite(row["final_rmse"])
    return row


def _multihost_config(num_clients: int, cohort: int):
    """The ONE config both sides of the multihost A/B run. client_chunk=16
    divides the cohort block per process (S/P = 128) AND the per-process
    client block (K/P = 50_000), the alignment conditions for bitwise
    identity of the chunked LocalUpdate and the partitioned RMSE eval
    (see docs/distributed.md)."""
    model_cfg = get_forecaster("idformer", **_MICRO).cfg
    fl_cfg = FLConfig(policy="psgf", num_clients=num_clients, local_steps=1,
                      batch_size=2, streaming_windows=True,
                      participation=cohort, client_chunk=16)
    task = get_task("nn5", seed=0, num_clients=num_clients, num_days=40,
                    look_back=8, horizon=1)
    tr, va, te, _ = task.client_data(task.series(), streaming=True)
    return model_cfg, fl_cfg, tr, te


def _multihost_child() -> dict:
    """One process of the multihost A/B (spawned by :func:`bench_multihost`;
    single-process when launched without a cluster): runs the host driver at
    the benchmark config and reports rounds/sec, per-process peak RSS and
    the bitwise fingerprint the parent compares."""
    import hashlib
    import resource

    from repro.launch.distributed import initialize_distributed

    initialize_distributed()
    K, S, rounds = (int(os.environ[k]) for k in
                    ("REPRO_FLR_MH_K", "REPRO_FLR_MH_S", "REPRO_FLR_MH_R"))
    model_cfg, fl_cfg, tr, te = _multihost_config(K, S)
    kw = dict(patience=rounds + 1, eval_every=rounds, driver="host")
    run_fl(model_cfg, fl_cfg, tr, te, jax.random.PRNGKey(0), max_rounds=1,
           **{**kw, "eval_every": 1, "patience": 2})   # warmup/compile
    t0 = time.perf_counter()
    hist = run_fl(model_cfg, fl_cfg, tr, te, jax.random.PRNGKey(0),
                  max_rounds=rounds, **kw)
    secs = time.perf_counter() - t0
    store = hist["client_store"]
    print(json.dumps({
        "process_count": jax.process_count(),
        "process_index": jax.process_index(),
        "seconds": secs,
        "rounds_per_sec": rounds / secs,
        "host_store_bytes": store.nbytes,
        "peak_host_rss_bytes": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024,
        "owned_block": [int(store.lo), int(store.hi)],
        "losses_sha": hashlib.sha256(
            np.asarray(hist["train_loss"], np.float64).tobytes()).hexdigest(),
        "w_global_sha": hashlib.sha256(
            np.asarray(hist["state"]["w_global"]).tobytes()).hexdigest(),
        "final_rmse": hist["final_rmse"],
        "comm_params": hist["final_comm"],
        "env": record_env(),
    }))
    return {}


def bench_multihost(num_clients: int = 100_000, cohort: int = 256,
                    rounds: int = 30):
    """Single- vs 2-process ``run_fl(driver="host")`` at deployment scale:
    the 2-process ``jax.distributed`` run must be BITWISE identical to the
    single-process run (per-round losses, comm, RMSE, final weights) while
    spreading the host-resident client fleet — per-process peak RSS is the
    headline number. Both sides run in FRESH child processes so the RSS
    readings are comparable (no inherited allocator state). CPU-only: the
    children run on ``JAX_PLATFORMS=cpu`` and this parent stays off JAX."""
    from repro.launch.distributed import spawn_processes

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["REPRO_FLR_MH_K"] = str(num_clients)
    env["REPRO_FLR_MH_S"] = str(cohort)
    env["REPRO_FLR_MH_R"] = str(rounds)
    argv = [sys.executable, "-m", "benchmarks.fl_rounds", "--multihost-child"]
    out = {"num_clients": num_clients, "participation": cohort,
           "rounds": rounds, "client_chunk": 16}
    reports = {}
    for n in (1, 2):
        procs = spawn_processes(n, argv, env=env, timeout=3600)
        reps = []
        for i, r in enumerate(procs):
            if r.returncode != 0:
                raise RuntimeError(
                    f"multihost child {i}/{n} failed:\n{r.stderr[-4000:]}")
            reps.append(json.loads(r.stdout.strip().splitlines()[-1]))
        reports[n] = reps
        for rep in reps:
            print(f"fl_rounds,multihost,P={n},"
                  f"proc={rep['process_index']},"
                  f"{rep['rounds_per_sec']:.2f} rounds/s,"
                  f"store={rep['host_store_bytes'] / 1e6:.1f}MB,"
                  f"rss={rep['peak_host_rss_bytes'] / 1e6:.1f}MB,"
                  f"block={rep['owned_block']},"
                  f"rmse={rep['final_rmse']:.4f}", flush=True)
    single = reports[1][0]
    out["single_process"] = single
    out["two_process"] = reports[2]
    bitwise = all(rep["losses_sha"] == single["losses_sha"]
                  and rep["w_global_sha"] == single["w_global_sha"]
                  and rep["final_rmse"] == single["final_rmse"]
                  and rep["comm_params"] == single["comm_params"]
                  for rep in reports[2])
    out["bitwise_equal"] = bitwise
    out["rounds_per_sec_ratio"] = (reports[2][0]["rounds_per_sec"]
                                   / single["rounds_per_sec"])
    out["peak_rss_reduction"] = (
        single["peak_host_rss_bytes"]
        / max(r["peak_host_rss_bytes"] for r in reports[2]))
    out["store_split"] = [r["host_store_bytes"] for r in reports[2]]
    print(f"fl_rounds,multihost,bitwise={bitwise},"
          f"speed_ratio={out['rounds_per_sec_ratio']:.2f}x,"
          f"rss_reduction={out['peak_rss_reduction']:.2f}x", flush=True)
    assert bitwise, ("2-process host-driver run diverged from the "
                     "single-process run — the partitioned round must be "
                     "bitwise identical")
    assert sum(out["store_split"]) == single["host_store_bytes"], \
        "partitioned stores must split the fleet exactly"
    return out


def bench_comm_bits(rounds: int = 15):
    """Wire-format A/B at matched rounds: ``FLConfig.comm_bits`` in
    {32, 16, 8} with the SAME model, data, seed and round budget (patience
    disabled) — only the simulated wire width differs. Per width this
    records final RMSE and the engine's own byte accounting
    (``final_comm_bytes`` = payload bytes + int8's per-leaf fp32 scale
    headers, ``final_scale_bytes``). Two bars are asserted:

      * int8 bytes <= 0.55x the bf16 row — the scale-header overhead is
        4 * L bytes per payload, so the ratio only lands under 0.55 when the
        average leaf carries >> 40 elements; the d_model=32 model here has
        ~400 params/leaf (overhead ~1%). A d_model=16 micro-model measures
        ~0.56x — scale headers are NOT free at toy widths, which is exactly
        why this A/B runs at a realistic width;
      * int8 final RMSE within 2% of the fp32 row at the same round count —
        the wire quantizer is stochastic-rounded (unbiased) per round, so the
        quantization noise averages out instead of stalling the descent (the
        deterministic nearest-rounding quantizer measures 10-25% regression
        on this exact config).
    """
    model_cfg = get_forecaster("logtst", look_back=16, horizon=2, d_model=32,
                               num_heads=4, d_ff=32, patch_len=8, stride=4).cfg
    tr, te = _data(8, 16, 2, num_days=60)
    out = {"rounds": rounds, "num_clients": 8}
    for bits in (32, 16, 8):
        fl_cfg = FLConfig(policy="psgf", num_clients=8, local_steps=1,
                          batch_size=4, comm_bits=bits)
        hist = run_fl(model_cfg, fl_cfg, tr, te, jax.random.PRNGKey(0),
                      max_rounds=rounds, patience=rounds + 1,
                      eval_every=rounds, driver="while")
        out[f"bits{bits}"] = {
            "comm_bits": bits,
            "final_rmse": hist["final_rmse"],
            "comm_params": hist["final_comm"],
            "comm_bytes": hist["final_comm_bytes"],
            "scale_bytes": hist["final_scale_bytes"],
        }
        print(f"fl_rounds,comm_bits,{bits}b,"
              f"bytes={hist['final_comm_bytes']:.3e},"
              f"scale_bytes={hist['final_scale_bytes']:.3e},"
              f"rmse={hist['final_rmse']:.6f}", flush=True)
    ratio = out["bits8"]["comm_bytes"] / out["bits16"]["comm_bytes"]
    out["bytes_ratio_int8_over_bf16"] = ratio
    out["bytes_ratio_int8_over_fp32"] = (out["bits8"]["comm_bytes"]
                                         / out["bits32"]["comm_bytes"])
    rmse32 = out["bits32"]["final_rmse"]
    reg = max(0.0, (out["bits8"]["final_rmse"] - rmse32) / rmse32)
    out["rmse_regression_int8_vs_fp32"] = reg
    print(f"fl_rounds,comm_bits,int8/bf16={ratio:.3f}x,"
          f"int8/fp32={out['bytes_ratio_int8_over_fp32']:.3f}x,"
          f"rmse_regression={reg:.4f}", flush=True)
    assert ratio <= 0.55, (
        f"int8 wire must cost <= 0.55x the bf16 bytes at matched rounds, "
        f"got {ratio:.3f}x — scale-header overhead grew")
    assert reg <= 0.02, (
        f"int8 final RMSE regressed {reg:.2%} vs fp32 at matched rounds "
        "(bar: 2%)")
    return out


def run(quick: bool = True):
    results = {"env": record_env(),
               "driver": bench_driver(rounds=50, reps=2 if quick else 5),
               "streaming": bench_streaming(quick=quick),
               "participation": bench_participation(quick=quick),
               "comm_bits": bench_comm_bits()}
    if not quick:
        results["scaling"] = bench_scaling()
        results["host_store"] = bench_host_store()
    save_json("fl_rounds", "results", results, keep_existing=True)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="driver A/B/C + streaming/participation micro A/Bs "
                         "only (CI smoke; still covers loop, scan AND "
                         "while); skips the 512-, 4096- and 100k-client runs")
    ap.add_argument("--multihost", action="store_true",
                    help="run ONLY the multihost section: single- vs "
                         "2-process host-driver at num_clients=100k "
                         "(bitwise-asserted; other committed sections are "
                         "kept via keep_existing)")
    ap.add_argument("--multihost-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.multihost_child:
        _multihost_child()
    elif args.multihost:
        multihost = bench_multihost()
        results = {"env": multihost["single_process"]["env"],
                   "multihost": multihost}
        save_json("fl_rounds", "results", results, keep_existing=True)
    else:
        run(quick=args.quick)
