"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows for micro-benches and
table rows for the paper-table benches.

  PYTHONPATH=src python -m benchmarks.run            # quick mode (~10-20 min)
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale FL grids

Heavier artifacts run as standalone scripts (their own XLA device counts):
  python -m repro.launch.dryrun --all                # deliverable (e)
  python -m benchmarks.roofline                      # deliverable (g)
  python -m benchmarks.psgf_dp_comm                  # beyond-paper comm bench
"""
from __future__ import annotations

import subprocess
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, time_call


def kernel_microbench():
    """us_per_call for each Pallas kernel vs its oracle: compiled on the chip,
    interpreted on the CPU backend (``repro.kernels.resolve_interpret``), so a
    CPU row is a timing of the interpreter, never of the kernel."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.psgf_mix.ops import psgf_mix
    from repro.kernels.psgf_mix.ref import psgf_mix_ref
    from repro.kernels.ssm_scan.ops import ssm_scan
    from repro.kernels.ssm_scan.ref import ssm_scan_ref

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    fa = jax.jit(lambda a, b, c: flash_attention(a, b, c, block_q=128,
                                                 block_k=128))
    fr = jax.jit(lambda a, b, c: attention_ref(a, b, c))
    mode = jax.default_backend()
    csv_row(f"flash_attention_{mode}", time_call(fa, q, k, v), "B1,S256,H4,hd64")
    csv_row("flash_attention_ref", time_call(fr, q, k, v), "oracle")

    D = 539_000  # LoGTST parameter-vector size
    wg = jax.random.normal(ks[3], (D,))
    wl = jax.random.normal(ks[4], (D,))
    m = jax.random.uniform(ks[0], (D,)) < 0.3
    pm = jax.jit(psgf_mix)
    pr = jax.jit(psgf_mix_ref)
    csv_row(f"psgf_mix_{mode}", time_call(pm, wg, wl, m), f"D={D}")
    csv_row("psgf_mix_ref", time_call(pr, wg, wl, m), "oracle")

    x = jax.random.normal(ks[0], (1, 128, 256))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 128, 256)))
    Bm = jax.random.normal(ks[2], (1, 128, 16))
    Cm = jax.random.normal(ks[3], (1, 128, 16))
    A = -jnp.exp(0.1 * jax.random.normal(ks[4], (256, 16)))
    sk = jax.jit(lambda *a: ssm_scan(*a, chunk=64, d_block=128))
    sr = jax.jit(ssm_scan_ref)
    csv_row(f"ssm_scan_{mode}", time_call(sk, x, dt, Bm, Cm, A), "S128,D256,N16")
    csv_row("ssm_scan_ref", time_call(sr, x, dt, Bm, Cm, A), "oracle")


def fl_round_bench():
    """us per FL round per policy (the system's inner loop, engine-dispatched).

    Driver-level rounds/sec (loop vs scan) lives in benchmarks/fl_rounds.py.
    """
    from repro.core import forecast as F
    from repro.core.fl.engine import FLConfig, fl_round, init_fl_state
    from repro.data.synthetic import nn5_synthetic
    from repro.data.windowing import client_datasets

    model_cfg = F.logtst_config(look_back=64, horizon=2, d_model=32,
                                num_heads=4, d_ff=64)
    series = nn5_synthetic(seed=0, num_clients=16, num_days=200)
    tr, va, te, _ = client_datasets(series, 64, 2)
    tr = jnp.asarray(tr)
    for policy in ("online", "pso", "psgf"):
        fl_cfg = FLConfig(policy=policy, num_clients=16, local_steps=2,
                          batch_size=16)
        state, meta = init_fl_state(model_cfg, fl_cfg, jax.random.PRNGKey(0))
        fn = lambda s: fl_round(s, tr, jax.random.PRNGKey(1), model_cfg,
                                fl_cfg, meta)[0]
        csv_row(f"fl_round_{policy}", time_call(fn, state), "K=16,D~1e5")


def main() -> None:
    full = "--full" in sys.argv
    print("== kernel micro-benchmarks (name,us_per_call,derived) ==")
    kernel_microbench()
    print("== FL round micro-benchmarks ==")
    fl_round_bench()
    print("== FL round-driver benchmark (loop vs scan) ==")
    from benchmarks import fl_rounds
    fl_rounds.run(quick=not full)
    print("== Table I (centralized forecasting) ==")
    from benchmarks import table1
    table1.run(quick=not full)
    print("== Tables II/III (FL policies) ==")
    from benchmarks import table23
    table23.run("nn5", quick=not full)
    table23.run("ev", quick=not full)
    print("== Fig. 6 (comm-loss trade-off) ==")
    from benchmarks import fig6
    fig6.run("nn5")
    fig6.run("ev")
    print("== PSGF-DP cross-pod collective bytes (subprocess: 8 devices) ==")
    r = subprocess.run([sys.executable, "-m", "benchmarks.psgf_dp_comm"],
                       capture_output=True, text=True)
    print(r.stdout[-2000:])
    if r.returncode != 0:
        print(r.stderr[-2000:])
    print("benchmarks.run: DONE")


if __name__ == "__main__":
    main()
