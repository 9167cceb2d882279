"""Smoke run of the paper-width train -> serve path on a TPU.

One process drives the system through the entry points a user calls, at the
paper's LoGTST width (look-back 128, horizon 2, patch 16 / stride 8, d_model
128, 16 heads of head_dim 8, d_ff 256: 273,284 parameters) with random
initial weights from a fixed seed:

  1. device  — JAX's first device must be a TPU; there is no CPU fallback;
  2. train   — ``run_experiment`` on the EV full preset (58 synthetic
               stations, DTW clusters=2), PSGF share 0.3 / forward 0.2, the
               while driver, streaming windows and a few rounds, writing
               checkpoints and the routing manifest to a temp dir;
  3. kernels — each Pallas kernel of the main path, compiled for the chip
               (``tpu_custom_call`` in the program), against its reference:
               the fused downlink mix in one round's downlink stage,
               ``psgf_mix_batch`` at a cluster's K and D = 273,284, and one
               forward with flash attention against the dense one;
  4. serve   — ``ForecastServer.from_manifest(denormalize=True)`` answers
               queued 1-row and 3-channel station requests in raw units,
               then one authed ``POST /v1/forecast`` goes through a
               ``ForecastGateway``; answers must agree with a float32
               forward of the restored params at highest matmul precision.

``--chips 4`` runs only what exists across chips: the client-sharded while
driver and batch-sharded serving on 4 chips, each against the same run on
one chip.

Any fault raises and exits non-zero. The last line of stdout is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PAPER_PARAMS = 273_284
ROUNDS = 10
EVAL_EVERY = 5
PSGF = ("psgf", {"share_ratio": 0.3, "forward_ratio": 0.2})

# Tolerances. The mix and its count are exact: the gates are 0/1, so every
# output element is one input element and the count is a sum of integers.
# Everything else runs at the TPU's default matmul precision, where float32
# operands enter the MXU as bfloat16 (8-bit mantissa, relative rounding
# 2^-9 ~ 2e-3 per operand) and accumulate in float32:
#   flash vs dense forward (random weights): max |diff| <= FLASH_TOL *
#     max |dense|;
#   served vs the highest-precision reference, in units of the station's sd
#     (the normalized units the model predicts in): on a TPU v5e the served
#     answers sat 3.2e-2 sd from the reference, and 1.5e-2 sd from the same
#     plain forward at default precision (other batch shapes compile to
#     other roundings), so max |diff| <= SERVE_TOL * sd — well under the
#     model's own error (RMSE ~1.25 sd after the smoke's rounds);
#   4 chips vs 1 chip: the same arithmetic partitioned differently, apart
#     from the order of the cross-client sums, so per-round losses, RMSE and
#     forecasts agree to SHARD_RTOL.
FLASH_TOL = 3e-2
SERVE_TOL = 1e-1
SHARD_RTOL = 5e-3


def log(msg: str):
    print(msg, flush=True)


def has_kernel(compiled) -> bool:
    """A compiled program that runs a Pallas kernel on the chip carries it as
    a ``tpu_custom_call``; an interpreted kernel or a jnp stand-in does
    not."""
    return "tpu_custom_call" in compiled.as_text()


class CompileClock:
    """Seconds spent in the XLA backend compiler, from JAX's own event."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def paper_setup(**task_overrides):
    from repro.core.tasks import get_task, task_forecaster

    task = get_task("ev", quick=False, **task_overrides)
    model = task_forecaster(task, "logtst", quick=False)
    if model.num_params() != PAPER_PARAMS:
        raise AssertionError(f"LoGTST has {model.num_params()} params, "
                             f"expected {PAPER_PARAMS}")
    return task, model


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def train_phase(task, model, series, labels, workdir, clock, device):
    import numpy as np

    from repro.core.tasks import ExperimentSpec, run_experiment

    spec = ExperimentSpec(task=task, model=model, grid=(PSGF,),
                          driver="while", streaming_windows=True,
                          max_rounds=ROUNDS, eval_every=EVAL_EVERY,
                          patience=ROUNDS + 1)
    c0, t0 = clock.seconds, time.perf_counter()
    res = run_experiment(spec, checkpoint_dir=workdir, series=series,
                         labels=labels)
    wall = time.perf_counter() - t0
    for r in res["rows"]:
        losses = np.asarray(r["train_loss"])
        log(f"train: cluster {r['cluster']} clients {r['clients']} rounds "
            f"{r['rounds']} loss {losses[0]:.6f} -> {losses[-1]:.6f} rmse "
            f"{r['rmse']:.6f} comm_params {r['comm_params']:.6e} "
            f"train_s {r['train_s']}")
        if r["rounds"] != ROUNDS or len(losses) != ROUNDS:
            raise AssertionError(f"cluster {r['cluster']} ran {r['rounds']} "
                                 f"rounds, expected {ROUNDS}")
        if not (np.all(np.isfinite(losses)) and np.isfinite(r["rmse"])):
            raise AssertionError(f"cluster {r['cluster']}: non-finite loss "
                                 f"or RMSE: {losses.tolist()} {r['rmse']}")
    log(f"train: {len(res['rows'])} cluster runs in {wall:.3f} s, backend "
        f"compile {clock.seconds - c0:.3f} s, peak_bytes_in_use "
        f"{peak_bytes(device)}")
    if len(res["rows"]) != task.clusters:
        raise AssertionError(f"trained {len(res['rows'])} clusters, "
                             f"expected {task.clusters}")
    return res


def kernel_phase(task, model, series, labels):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import forecast
    from repro.core.fl import engine as E
    from repro.core.fl import policies as pol
    from repro.kernels.psgf_mix.ops import psgf_mix_batch
    from repro.kernels.psgf_mix.ref import psgf_mix_batch_ref

    cluster = int(np.argmax(np.bincount(labels)))
    idx = np.nonzero(labels == cluster)[0]
    tr, _, te, _ = task.client_data(series, idx, streaming=True)
    K = tr.shape[0]
    cfg = model.cfg

    # one downlink stage, fused kernel against the jnp mix; clients are
    # moved off the global model so the gates select different values
    fl = E.FLConfig(policy=PSGF[0], num_clients=K, streaming_windows=True,
                    **PSGF[1])
    key = jax.random.PRNGKey(0)
    state, meta = E.init_fl_state(cfg, fl, key)
    noise = jax.random.normal(jax.random.PRNGKey(1), state["w_clients"].shape)
    state["w_clients"] = state["w_clients"] + 1e-2 * noise
    policy = pol.from_config(fl)
    rk = jax.random.PRNGKey(2)
    down = {}
    for fused in (False, True):
        f_cfg = dataclasses.replace(fl, use_pallas_mix=fused)
        compiled = jax.jit(E._round_down, static_argnums=(2, 3, 4)).lower(
            state, rk, f_cfg, meta, policy).compile()
        if has_kernel(compiled) != fused:
            raise AssertionError(f"downlink use_pallas_mix={fused}: kernel "
                                 f"in program = {has_kernel(compiled)}")
        down[fused] = jax.device_get(compiled(state, rk))
    for name in down[False]:
        if not np.array_equal(down[False][name], down[True][name]):
            raise AssertionError(f"fused downlink differs in {name!r}")
    log(f"kernels: psgf_mix downlink stage K={K} D={meta.total}: "
        f"bitwise equal to the jnp mix, comm_down {down[True]['comm_down']}")

    # psgf_mix_batch alone at a cluster's K and the paper's D
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    D = PAPER_PARAMS
    wg = jax.random.normal(ks[0], (D,))
    wc = jax.random.normal(ks[1], (K, D))
    m = jax.random.uniform(ks[2], (K, D)) < 0.3
    compiled = jax.jit(psgf_mix_batch).lower(wg, wc, m).compile()
    if not has_kernel(compiled):
        raise AssertionError("psgf_mix_batch compiled without its kernel")
    out, cnt = compiled(wg, wc, m)
    ref, rcnt = jax.jit(psgf_mix_batch_ref)(wg, wc, m)
    if not (np.array_equal(np.asarray(out), np.asarray(ref))
            and float(cnt) == float(rcnt)):
        raise AssertionError(f"psgf_mix_batch != reference (count {cnt} vs "
                             f"{rcnt})")
    log(f"kernels: psgf_mix_batch K={K} D={D}: bitwise equal to the "
        f"reference, count {float(cnt):.0f}")

    # one paper-width forward, flash attention against dense
    params = forecast.init_params(cfg, jax.random.PRNGKey(4))
    L = cfg.look_back
    starts = np.arange(te.shape[1] - L + 1)
    x = jnp.asarray(te[:, starts[:, None] + np.arange(L)].reshape(-1, L))
    preds = {}
    for flash in (False, True):
        f_cfg = dataclasses.replace(cfg, use_flash_attn=flash)
        compiled = jax.jit(lambda p, x, c=f_cfg: forecast.forward(c, p, x)) \
            .lower(params, x).compile()
        if has_kernel(compiled) != flash:
            raise AssertionError(f"forward use_flash_attn={flash}: kernel in "
                                 f"program = {has_kernel(compiled)}")
        preds[flash] = np.asarray(compiled(params, x))
    err = float(np.max(np.abs(preds[True] - preds[False])))
    scale = float(np.max(np.abs(preds[False])))
    log(f"kernels: flash vs dense forward over {x.shape[0]} windows: max "
        f"|diff| {err:.6e}, max |dense| {scale:.6e}, tolerance "
        f"{FLASH_TOL} x max |dense|")
    if not (np.all(np.isfinite(preds[True])) and err <= FLASH_TOL * scale):
        raise AssertionError("flash attention forward disagrees with dense")


def plain_forecast(fc, params, x):
    """The plain float32 forward at highest matmul precision: no buckets,
    no padding, no queue."""
    import jax
    import numpy as np

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(fc.forward_multivariate)(params, x))


def serve_phase(task, series, workdir, device, requests: int = 48):
    import numpy as np

    from repro.core.forecaster import load_forecaster
    from repro.core.tasks import read_routing_manifest, run_name
    from repro.launch.gateway import ForecastGateway, request_json
    from repro.launch.serve_forecast import ForecastServer

    server = ForecastServer.from_manifest(workdir, denormalize=True)
    _, manifest = read_routing_manifest(workdir)
    mu = np.asarray(manifest["norm"]["mu"], np.float32)
    sd = np.asarray(manifest["norm"]["sd"], np.float32)
    restored = {c: load_forecaster(os.path.join(workdir, sub))
                for c, sub in manifest["policies"][run_name(*PSGF)].items()}
    stations = server.routable_stations()
    L = task.look_back
    t0 = time.perf_counter()
    for channels in (1, 3):
        server.warmup(channels)
    log(f"serve: {len(server.engines)} cluster engines, {len(stations)} "
        f"routable stations, warmup {time.perf_counter() - t0:.3f} s")

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(requests):
        s = stations[i % len(stations)]
        M = 1 if i % 2 == 0 else 3
        offs = rng.integers(0, series.shape[1] - L, size=M)
        reqs.append((s, np.stack([series[s, o:o + L] for o in offs])
                     .astype(np.float32)))

    def assert_close(answers, what):
        """Served forecasts against the float32 reference, in units of the
        station's sd (the normalized units the model predicts in)."""
        errs = []
        for s, x, y in answers:
            fc, params, _ = restored[str(manifest["station_cluster"][s])]
            ref = plain_forecast(fc, params, ((x - mu[s]) / sd[s])[None])[0]
            errs.append(float(np.max(np.abs(
                np.asarray(y) - (ref * sd[s] + mu[s])))) / float(sd[s]))
        log(f"serve: {what}: max |diff| {max(errs):.6e} sd to the "
            f"highest-precision reference (tolerance {SERVE_TOL} sd)")
        if not max(errs) <= SERVE_TOL:
            raise AssertionError(f"{what}: served forecasts disagree with "
                                 f"the float32 reference")

    server.start()
    t0 = time.perf_counter()
    futs = [server.submit(x, station=s) for s, x in reqs]
    ys = [f.result(timeout=120) for f in futs]
    secs = time.perf_counter() - t0
    log(f"serve: {len(ys)} queued requests (1-row and 3-channel) answered in "
        f"{secs:.3f} s, {server.stats['batches']} batches incl. warmup")
    assert_close([(s, x, y) for (s, x), y in zip(reqs, ys)],
                 "queued requests")

    token = "chip-smoke"
    gw = ForecastGateway(server, port=0, auth_token=token)
    host, port = gw.start()
    try:
        s, x = reqs[1]
        status, _, body = request_json(host, port, "POST", "/v1/forecast",
                                       {"x": x.tolist(), "station": int(s)},
                                       token=token, timeout=120)
        if status != 200:
            raise AssertionError(f"POST /v1/forecast -> {status}: {body}")
        log(f"serve: POST /v1/forecast -> 200 for station {s}")
        assert_close([(s, x, body["y"])], "gateway request")
    finally:
        gw.stop(close_server=True)
    log(f"serve: peak_bytes_in_use {peak_bytes(device)}")


def four_chip_phase(devices):
    """The client-sharded while driver and batch-sharded serving on all the
    chips, each against the same work on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.pytree_utils import tree_unflatten_from_vector
    from repro.core.fl.engine import FLConfig, run_fl
    from repro.launch.serve_forecast import ForecastServer

    n = len(devices)
    task, model = paper_setup()
    series = task.series()
    tr, _, te, _ = task.client_data(series, np.arange(32), streaming=True)
    K = tr.shape[0]
    if K % n:
        raise AssertionError(f"{K} stations do not split over {n} chips")
    fl = FLConfig(policy=PSGF[0], num_clients=K, streaming_windows=True,
                  **PSGF[1])
    kw = dict(max_rounds=ROUNDS, eval_every=EVAL_EVERY, patience=ROUNDS + 1,
              driver="while")
    tr, te = jnp.asarray(tr), jnp.asarray(te)
    runs = {}
    for shard in (False, True):
        t0 = time.perf_counter()
        runs[shard] = run_fl(model.cfg, fl, tr, te, jax.random.PRNGKey(0),
                             shard_clients=shard, **kw)
        log(f"4-chip: while driver K={K} shard_clients={shard}: "
            f"{time.perf_counter() - t0:.3f} s incl. compile")
    one, many = runs[False], runs[True]
    spread = len(many["state"]["w_clients"].sharding.device_set)
    if spread != n or len(one["state"]["w_clients"].sharding.device_set) != 1:
        raise AssertionError(f"client state on {spread} devices, "
                             f"expected {n}")
    l1, ln = np.asarray(one["train_loss"]), np.asarray(many["train_loss"])
    loss_err = float(np.max(np.abs(ln - l1) / np.abs(l1)))
    rmse_err = abs(many["final_rmse"] - one["final_rmse"]) / one["final_rmse"]
    log(f"4-chip: client state on {spread} devices; losses {l1[0]:.6f} -> "
        f"{l1[-1]:.6f} (1 chip) vs {ln[0]:.6f} -> {ln[-1]:.6f} ({n} chips), "
        f"max rel diff {loss_err:.6e}; rmse {one['final_rmse']:.6f} vs "
        f"{many['final_rmse']:.6f}, rel diff {rmse_err:.6e}; tolerance "
        f"{SHARD_RTOL}")
    if len(ln) != ROUNDS or not (loss_err <= SHARD_RTOL
                                 and rmse_err <= SHARD_RTOL):
        raise AssertionError("sharded while driver disagrees with one chip")

    params = tree_unflatten_from_vector(many["state"]["w_global"],
                                        many["meta"])
    params = jax.device_get(params)
    L = task.look_back
    x = np.stack([series[:, o:o + L] for o in (0, 100, 200)], axis=1)
    x = np.asarray(x[:32], np.float32)      # (32, 3, L): one full bucket
    outs = {}
    for shard in (False, True):
        server = ForecastServer(model, params, shard_batch=shard)
        outs[shard] = server.predict(x)
        if shard:
            buf = server.engines[None]._out[(32, 3)]
            spread = len(buf.sharding.device_set)
        server.close()
    err = float(np.max(np.abs(outs[True] - outs[False])))
    scale = float(np.max(np.abs(outs[False])))
    log(f"4-chip: shard_batch serving of a (32, 3, {L}) bucket on {spread} "
        f"devices: max |diff| {err:.6e} vs one chip, max |y| {scale:.6e}")
    if spread != n or not err <= SHARD_RTOL * scale:
        raise AssertionError("batch-sharded serving disagrees with one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-training and sharded-"
                         "serving comparisons, on four chips")
    args = ap.parse_args(argv)

    from repro.common.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"device: platform {d0.platform}, kind {d0.device_kind}, count "
        f"{len(devices)}; compile cache {cache}")
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX's first device is "
                         f"{d0.platform!r}); there is no CPU fallback")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} devices")

    t_all = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(devices)
    else:
        clock = CompileClock()
        task, model = paper_setup(clusters=2)
        log(f"model: {model.name}, {model.num_params()} params, "
            f"{dataclasses.asdict(model.cfg)}")
        series = task.series()
        labels = task.cluster_labels(series)
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir:
            train_phase(task, model, series, labels, workdir, clock, d0)
            kernel_phase(task, model, series, labels)
            serve_phase(task, series, workdir, d0)
        log(f"backend compile total {clock.seconds:.3f} s")
    log(f"all phases passed in {time.perf_counter() - t_all:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
