"""Traffic kind ``fl_jobs``: back-to-back federated training jobs.

Each job is one call of the program's ``run_fl``: ``rounds`` PSGF rounds
over every client of the mix's data set, from the benchmark's weights and a
job key of its own, with the global model's RMSE over all test windows every
``eval_every`` rounds. Set-up runs job 0 through the same call (it compiles
the one job program), and the window runs jobs 1, 2, ... until ``--seconds``
have passed; the job in flight then is finished and counted.

``fl_rounds_per_s`` is all rounds of all jobs completed in the window over
the time from the window's start to the end of the last job.

Correctness: the plain reference runs job 0 again, round by round, from the
same weights, data and key chain, its matrix products in float32 (the
configuration's ``matmul_precision``, ``highest``). Compared:

  * ``loss_gap``: the largest relative gap of the mean local loss over the
    first three rounds;
  * ``comm_gap``: the largest gap of the cumulative count of parameters sent
    down and up, over every round (exact);
  * ``rmse_gap``: the largest relative gap of the eval RMSE;
  * ``dw_gap``: for each parameter leaf, the gap between the norms of the
    global model's change over the job, program against reference, over the
    larger of the reference's norm for that leaf and the median leaf's; the
    worst leaf. Leaves whose first-step gradient in the reference is under
    a thousandth of the median leaf's move by round-off alone and are left
    out.
"""
from __future__ import annotations

import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import synthetic  # noqa: E402

MODEL_KEYS = ("look_back", "horizon", "patch_len", "stride", "d_model",
              "num_heads", "d_ff", "revin", "use_flash_attn")
FL_KEYS = ("select_ratio", "share_ratio", "forward_ratio", "local_steps",
           "batch_size", "lr", "adam_b1", "adam_b2", "adam_eps")


def model_config(c: dict):
    """The program's model configuration for a benchmark configuration."""
    from repro.core.forecast import ForecastConfig

    return ForecastConfig(mixers=tuple(c["mixers"]),
                          **{k: c[k] for k in MODEL_KEYS})


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.c = ctx.config
        self.p = ctx.mix
        self.first = None

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core.fl.engine import FLConfig

        c, p = self.c, self.p
        series = synthetic.GENERATORS[p["dataset"]](
            self.ctx.data_seed, p["clients"], p["steps"])
        self.train, self.test = synthetic.split_normalized(
            series, c["look_back"], c["horizon"])
        self.K = self.train.shape[0]
        ref = self.ctx.reference
        self.params = jax.jit(partial(ref.init_params, c))(
            self.ctx.key("weights"))
        self.dim = ref.param_count(c)
        self.model_cfg = model_config(c)
        self.fl = FLConfig(policy=p["policy"], num_clients=self.K,
                           streaming_windows=True,
                           use_pallas_mix=p["use_pallas_mix"],
                           **{k: p[k] for k in FL_KEYS})
        self.train_d = jnp.asarray(self.train)
        self.test_d = jnp.asarray(self.test)
        self.first = self._outputs(self._job(0))

    def _job(self, j: int):
        import jax

        from repro.core.fl.engine import run_fl

        p = self.p
        key = jax.random.fold_in(self.ctx.key("jobs"), j)
        with self.ctx.span("fl_job"):
            return run_fl(self.model_cfg, self.fl, self.train_d, self.test_d,
                          key, max_rounds=p["rounds"], patience=p["patience"],
                          eval_every=p["eval_every"], driver=p["driver"],
                          init_params=self.params)

    def _outputs(self, h) -> dict:
        return {"loss": np.asarray(h["train_loss"], np.float64),
                "comm": np.asarray(h["comm"], np.float64),
                "rmse": np.asarray([r for _, r in h["rmse"]], np.float64),
                "w": np.asarray(h["state"]["w_global"], np.float32),
                "rounds": int(h["rounds_run"])}

    def _sound(self, h) -> bool:
        return (h["rounds_run"] == self.p["rounds"]
                and bool(np.all(np.isfinite(h["train_loss"])))
                and bool(np.isfinite(h["final_rmse"])))

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        jobs = rounds = failed = 0
        while True:
            h = self._job(jobs + 1)
            jobs += 1
            rounds += int(h["rounds_run"])
            failed += not self._sound(h)
            del h
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        p = self.p
        return {"e2e": {"fl_rounds_per_s": rounds / elapsed},
                "attempted": jobs, "failed": failed,
                "counts": {"rounds": rounds, "jobs": jobs,
                           "evals": jobs * (p["rounds"] // p["eval_every"]),
                           "clients": self.K, "dim": self.dim,
                           "train_rows_per_round":
                               self.K * p["local_steps"] * p["batch_size"],
                           "eval_rows": self.K * (
                               self.test.shape[1] - self.c["look_back"]
                               - self.c["horizon"] + 1),
                           "window_s": elapsed}}

    def release(self):
        del self.train_d, self.test_d

    def reference_outputs(self) -> dict:
        import jax

        p = self.p
        key = jax.random.fold_in(self.ctx.key("jobs"), 0)
        return self.ctx.reference.fl_job(
            self.c, {k: p[k] for k in FL_KEYS}, self.params, self.train,
            self.test, key, rounds=p.get("ref_rounds", p["rounds"]),
            eval_every=p["eval_every"])

    def check(self) -> dict:
        """The numbers compared, for the program's job 0 against the
        reference."""
        return compare(self.first, self.reference_outputs())


def _rel(a, b):
    return np.abs(np.asarray(a) - np.asarray(b)) / np.maximum(
        np.abs(np.asarray(b)), 1e-30)


def compare(out: dict, ref: dict) -> dict:
    n = len(ref["loss"])
    if out["rounds"] < n or len(out["rmse"]) < len(ref["rmse"]):
        return {"loss_gap": np.inf, "comm_gap": np.inf, "rmse_gap": np.inf,
                "dw_gap": np.inf}
    nums = {"loss_gap": float(np.max(_rel(out["loss"][:3], ref["loss"][:3]))),
            "comm_gap": float(np.max(np.abs(out["comm"][:n] - ref["comm"]))),
            "rmse_gap": float(np.max(_rel(out["rmse"][:len(ref["rmse"])],
                                          ref["rmse"])))}
    if n == out["rounds"]:
        offs = ref["leaf_offsets"]
        g1 = ref["g1_leaf_norms"]
        counted = g1 >= 1e-3 * np.median(g1)
        dp = out["w"].astype(np.float64) - ref["w0"]
        dr = ref["w"].astype(np.float64) - ref["w0"]
        np_ = np.array([np.linalg.norm(dp[a:b]) for a, b in
                        zip(offs[:-1], offs[1:])])
        nr = np.array([np.linalg.norm(dr[a:b]) for a, b in
                       zip(offs[:-1], offs[1:])])
        scale = np.maximum(nr, np.median(nr[counted]))
        gaps = np.abs(np_ - nr) / scale
        nums["dw_gap"] = float(np.max(gaps[counted]))
        worst = int(np.argmax(np.where(counted, gaps, -1)))
        nums["dw_gap_leaf"] = float(worst)
    if not all(np.isfinite(v) for v in nums.values()):
        nums = {k: (v if np.isfinite(v) else np.inf) for k, v in nums.items()}
    return nums
