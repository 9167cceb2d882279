"""Tests for the Forecaster/ExperimentSpec/serving API surface.

Guards: registry round-trip, facade bit-identity to the free functions in
``repro.core.forecast``, task presets, ``run_experiment`` equivalence to a
hand-assembled ``run_fl`` call, serve bucketing pad/unpad correctness, and the
checkpoint save -> restore -> serve round-trip.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import forecast as F
from repro.core.fl.engine import FLConfig, run_fl
from repro.core.forecaster import (Forecaster, forecaster_names, get_forecaster,
                                   load_forecaster, save_forecaster)
from repro.core.tasks import (ExperimentSpec, get_task, run_experiment,
                              task_forecaster, task_names)
from repro.launch.serve_forecast import ForecastServer, batch_buckets, serve_requests


TINY = dict(look_back=16, horizon=2, d_model=16, num_heads=2, d_ff=16,
            patch_len=8, stride=4)


def _tiny(name="logtst"):
    return get_forecaster(name, **TINY)


# ---- registry ---------------------------------------------------------------


@pytest.mark.parametrize("name", ["logtst", "patchtst", "mlpformer", "idformer"])
def test_registry_roundtrip(name):
    fc = get_forecaster(name, **TINY)
    # the derived cfg.name resolves back to an identical config
    assert get_forecaster(fc.cfg.name, **TINY).cfg == fc.cfg
    assert get_forecaster(fc.cfg).cfg == fc.cfg  # config passthrough
    assert name in forecaster_names()


def test_registry_default_names_roundtrip():
    for name in forecaster_names():
        fc = get_forecaster(name)
        assert get_forecaster(fc.cfg.name).cfg == fc.cfg


def test_registry_unknown_and_mixer_override():
    with pytest.raises(KeyError):
        get_forecaster("tcn")
    fc = get_forecaster("idformer", mixers=("id",), **TINY)
    assert fc.cfg.mixers == ("id",)
    # a mixer override must keep the registered fn's OTHER defaults
    assert fc.cfg.d_model == TINY["d_model"]
    from repro.core.forecaster import register_forecaster
    register_forecaster(
        "_custom_test", lambda **kw: F.ForecastConfig(
            **{"d_model": 64, "num_heads": 4, "mixers": ("mlp",), **kw}))
    try:
        fc2 = get_forecaster("_custom_test", mixers=("id", "id"))
        assert fc2.cfg.mixers == ("id", "id") and fc2.cfg.d_model == 64
    finally:
        from repro.core import forecaster as _fmod
        _fmod._REGISTRY.pop("_custom_test", None)


# ---- facade bit-identity ----------------------------------------------------


def test_facade_bit_identical_to_free_functions(rng_key):
    fc = _tiny()
    params = fc.init_params(rng_key)
    ref_params = F.init_params(fc.cfg, rng_key)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    x = jax.random.normal(rng_key, (4, fc.cfg.look_back))
    y = jax.random.normal(rng_key, (4, fc.cfg.horizon))
    np.testing.assert_array_equal(np.asarray(fc.forward(params, x)),
                                  np.asarray(F.forward(fc.cfg, params, x)))
    xm = x.reshape(2, 2, fc.cfg.look_back)
    np.testing.assert_array_equal(
        np.asarray(fc.forward_multivariate(params, xm)),
        np.asarray(F.forward_multivariate(fc.cfg, params, xm)))
    assert float(fc.loss_fn(params, x, y)) == float(F.mse_loss(fc.cfg, params, x, y))
    assert fc.num_params() == F.num_params(fc.cfg)


def test_abstract_params_and_axes_match_concrete(rng_key):
    fc = _tiny("patchtst")
    params = fc.init_params(rng_key)
    ab = fc.abstract_params()
    axes = fc.param_axes()
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_a = jax.tree_util.tree_flatten_with_path(ab)[0]
    assert len(flat_p) == len(flat_a)
    for (pa, leaf), (aa, st) in zip(flat_p, flat_a):
        assert pa == aa and leaf.shape == st.shape and leaf.dtype == st.dtype
    # axes tree mirrors the param tree with one logical name per dim
    for leaf, ax in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(axes, is_leaf=lambda t: isinstance(t, tuple))):
        assert len(ax) == leaf.ndim
    assert fc.num_params() == sum(int(np.prod(l.shape))
                                  for l in jax.tree_util.tree_leaves(params))


# ---- tasks + experiments ----------------------------------------------------


def test_task_presets_and_overrides():
    assert set(task_names()) >= {"ev", "nn5", "household"}
    ev_q, ev_f = get_task("ev", quick=True), get_task("ev", quick=False)
    assert (ev_q.look_back, ev_q.horizon) == (64, 2)
    assert (ev_f.look_back, ev_f.horizon) == (128, 2)
    assert ev_f.num_clients == 58  # the paper's Dundee station count
    assert get_task("nn5").horizon == 4
    t = get_task("ev", clusters=3, num_clients=12)
    assert t.clusters == 3 and t.num_clients == 12
    with pytest.raises(KeyError):
        get_task("ett")


def test_household_workload_properties():
    t = get_task("household", quick=True)
    s = t.series()
    assert s.shape == (t.num_clients, t.num_days)
    assert (s >= 0).all() and np.isfinite(s).all()
    # vacation spans: every household has some near-idle days but is not dead
    frac_low = (s < 0.3 * s.mean(axis=1, keepdims=True)).mean(axis=1)
    assert (frac_low > 0).mean() > 0.5 and (s.mean(axis=1) > 1.0).all()
    tr, va, te, info = t.client_data(s)
    assert tr.shape[2] == t.look_back + t.horizon and np.isfinite(tr).all()


def test_task_cluster_labels_pooled_and_clustered():
    t = get_task("ev", quick=True, num_clients=8, num_days=120)
    s = t.series()
    assert (t.cluster_labels(s) == 0).all()  # pooled
    tc = dataclasses.replace(t, clusters=2)
    labels = tc.cluster_labels(s)
    assert labels.shape == (8,) and set(labels) <= {0, 1}


def test_run_experiment_matches_hand_assembled_run_fl():
    """The spec path must feed run_fl EXACTLY what the hand-rolled drivers
    did: same windows, same FLConfig, same key -> bit-identical history."""
    task = get_task("nn5", quick=True, num_clients=4, num_days=60,
                    look_back=16, horizon=2)
    model = get_forecaster("logtst", **TINY)
    spec = ExperimentSpec(task=task, model=model, grid=(("psgf", {}),),
                          local_steps=1, batch_size=4, max_rounds=3,
                          patience=5, eval_every=3)
    res = run_experiment(spec)
    row = res["rows"][0]

    tr, va, te, _ = task.client_data(task.series())
    fl_cfg = FLConfig(policy="psgf", num_clients=tr.shape[0], select_ratio=0.5,
                      local_steps=1, batch_size=4)
    hist = run_fl(model.cfg, fl_cfg, jnp.asarray(tr), jnp.asarray(te),
                  jax.random.PRNGKey(0), max_rounds=3, patience=5, eval_every=3)
    assert row["rmse"] == hist["final_rmse"]
    assert row["comm_params"] == hist["final_comm"]
    assert row["rounds"] == hist["rounds_run"]
    assert row["train_loss"] == hist["train_loss"]
    assert row["comm_bytes"] == hist["final_comm"] * 4.0


def test_run_experiment_clustered_rows():
    task = get_task("ev", quick=True, num_clients=10, num_days=120,
                    look_back=16, horizon=2, clusters=2)
    model = get_forecaster("idformer", **TINY)
    spec = ExperimentSpec(task=task, model=model,
                          grid=(("online", {}), ("pso", {"share_ratio": 0.5})),
                          local_steps=1, batch_size=4, max_rounds=2,
                          patience=5, eval_every=2)
    res = run_experiment(spec)
    assert sum(res["cluster_sizes"]) == 10
    clusters_seen = {r["cluster"] for r in res["rows"]}
    assert clusters_seen <= {0, 1}
    for r in res["rows"]:
        assert np.isfinite(r["rmse"]) and r["rounds"] == 2
        assert r["policy"] in ("online", "pso-s50")


# ---- checkpoint round-trip --------------------------------------------------


def test_save_load_forecaster_roundtrip(rng_key, tmp_path):
    fc = _tiny("mlpformer")
    params = fc.init_params(rng_key)
    d = str(tmp_path / "ckpt")
    save_forecaster(d, fc, params, step=3, extra={"note": "hi"})
    fc2, params2, extra = load_forecaster(d)
    assert fc2.cfg == fc.cfg and extra["note"] == "hi"
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_fl_writes_servable_checkpoint(tmp_path):
    task = get_task("nn5", quick=True, num_clients=4, num_days=60,
                    look_back=16, horizon=2)
    model = get_forecaster("logtst", **TINY)
    tr, va, te, _ = task.client_data(task.series())
    fl_cfg = FLConfig(policy="psgf", num_clients=tr.shape[0], local_steps=1,
                      batch_size=4)
    d = str(tmp_path / "fl_ckpt")
    hist = run_fl(model.cfg, fl_cfg, jnp.asarray(tr), jnp.asarray(te),
                  jax.random.PRNGKey(0), max_rounds=2, patience=5,
                  eval_every=2, checkpoint_dir=d)
    assert os.path.isdir(hist["checkpoint"])
    fc, params, extra = load_forecaster(d)
    assert fc.cfg == model.cfg
    assert extra["final_rmse"] == hist["final_rmse"]
    # restored global == in-memory global, bit for bit
    from repro.common.pytree_utils import tree_unflatten_from_vector
    ref = tree_unflatten_from_vector(hist["state"]["w_global"], hist["meta"])
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- serving ----------------------------------------------------------------


def test_batch_buckets():
    assert batch_buckets(8) == (1, 2, 4, 8)
    assert batch_buckets(12) == (1, 2, 4, 8, 12)
    assert batch_buckets(1) == (1,)


def test_server_bucketing_pads_and_unpads(rng_key):
    fc = _tiny()
    params = fc.init_params(rng_key)
    server = ForecastServer(fc, params, max_batch=8)
    rng = np.random.default_rng(0)
    for b in (1, 2, 3, 5, 8, 11):  # ragged, including > max_batch
        x = rng.standard_normal((b, 2, fc.cfg.look_back)).astype(np.float32)
        y = server.predict(x)
        assert y.shape == (b, 2, fc.cfg.horizon)
        # tight vs the same padded shape (jitted step vs eager forward may
        # reassociate at the ulp level)...
        bucket = server.bucket_for(min(b, server.max_batch))
        xp = np.zeros((bucket, 2, fc.cfg.look_back), np.float32)
        xp[: min(b, 8)] = x[:8]
        ref = np.asarray(fc.forward_multivariate(params, jnp.asarray(xp)))
        np.testing.assert_allclose(y[:min(b, 8)], ref[:min(b, 8)],
                                   rtol=1e-5, atol=1e-6)
        # ...and vs the unpadded forward (different XLA batch shape)
        ref_exact = np.asarray(fc.forward_multivariate(params, jnp.asarray(x)))
        np.testing.assert_allclose(y, ref_exact, rtol=1e-4, atol=1e-5)
    assert server.stats["padded_slots"] > 0


def test_server_single_request_and_queue(rng_key):
    fc = _tiny()
    params = fc.init_params(rng_key)
    server = ForecastServer(fc, params, max_batch=4, max_wait_ms=1.0)
    x = np.ones((2, fc.cfg.look_back), np.float32)
    y = server.predict(x)  # (M, L) single-request shape
    assert y.shape == (2, fc.cfg.horizon)
    rep = serve_requests(server, requests=9, channels=2)
    assert rep["forecasts_per_sec"] > 0 and rep["requests"] == 9


def test_queue_heterogeneous_shapes_one_microbatch(rng_key):
    """Coalesced requests with different channel counts (M) used to crash the
    whole micro-batch — np.stack over the ragged batch raised and failed
    EVERY waiter's Future. The worker now groups by shape and runs one bucket
    per group, so mixed-M requests in one coalescing window all resolve."""
    fc = _tiny()
    params = fc.init_params(rng_key)
    # long wait so all submissions land in ONE coalescing window
    server = ForecastServer(fc, params, max_batch=8, max_wait_ms=200.0)
    server.warmup(channels=2)
    server.warmup(channels=3)
    server.start()
    try:
        rng = np.random.default_rng(0)
        xs = [rng.standard_normal((m, fc.cfg.look_back)).astype(np.float32)
              for m in (2, 3, 2, 3, 2)]
        futs = [server.submit(x) for x in xs]
        ys = [f.result(timeout=60) for f in futs]
    finally:
        server.stop()
    for x, y in zip(xs, ys):
        assert y.shape == (x.shape[0], fc.cfg.horizon)
        ref = np.asarray(fc.forward_multivariate(params, jnp.asarray(x[None])))[0]
        np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)


def test_submit_rejects_only_the_malformed_request(rng_key):
    """A bad request (wrong look-back / rank) fails ITS OWN future without
    poisoning the batch it would have been coalesced into."""
    fc = _tiny()
    params = fc.init_params(rng_key)
    server = ForecastServer(fc, params, max_batch=8, max_wait_ms=200.0)
    server.warmup(channels=2)
    server.start()
    try:
        good = np.ones((2, fc.cfg.look_back), np.float32)
        bad_len = np.ones((2, fc.cfg.look_back + 3), np.float32)
        bad_rank = np.ones((fc.cfg.look_back,), np.float32)
        f1 = server.submit(good)
        f2 = server.submit(bad_len)
        f3 = server.submit(bad_rank)
        f4 = server.submit(good)
        f5 = server.submit([[1.0, 2.0], [1.0]])  # ragged: asarray itself fails
        assert f1.result(timeout=60).shape == (2, fc.cfg.horizon)
        assert f4.result(timeout=60).shape == (2, fc.cfg.horizon)
        for bad_fut in (f2, f3):
            with pytest.raises(ValueError, match="look_back"):
                bad_fut.result(timeout=60)
        with pytest.raises(Exception):
            f5.result(timeout=60)
    finally:
        server.stop()


def test_checkpoint_restore_serve_roundtrip(rng_key, tmp_path):
    """FL -> checkpoint -> restore -> served forecasts match the training-side
    model (same batch shape; jit-vs-eager ulp tolerance)."""
    fc = _tiny()
    params = fc.init_params(rng_key)
    d = str(tmp_path / "ckpt")
    save_forecaster(d, fc, params)
    fc2, params2, _ = load_forecaster(d)
    server = ForecastServer(fc2, params2, max_batch=4)
    x = np.random.default_rng(1).standard_normal((4, 3, fc.cfg.look_back)).astype(np.float32)
    served = server.predict(x)
    ref = np.asarray(fc.forward_multivariate(params, jnp.asarray(x)))
    np.testing.assert_allclose(served, ref, rtol=1e-5, atol=1e-6)
