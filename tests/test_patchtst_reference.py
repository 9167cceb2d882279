"""The forecaster against the benchmark's plain reference, on the CPU.

``benchmarks/chip/references/patch_transformer.py`` is the straightforward
``jax.numpy`` forecaster and PSGF job that decides a chip cell's ``correct``;
it imports nothing of the program. Here a small PatchTST (three attention
blocks, look-back 64, horizon 24, d_model 32, 4 heads), and the LoGTST and
IDFormer mixer patterns at the same size, are checked against it on seeded
random weights: the forward, the loss and every gradient leaf of
``forecast.mse_loss``, and a 3-round ``run_fl(driver="while")`` job against
the reference's ``fl_job`` through the benchmark's own comparison and the
PatchTST/64 cell's limits.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.pytree_utils import tree_flatten_to_vector
from repro.core import forecast
from repro.core.fl import engine as E

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "chip")


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references/patch_transformer.py", "bench_ref_patch_transformer")
fl_jobs = _load("traffic/fl_jobs.py", "bench_traffic_fl_jobs")
synthetic = _load("traffic/synthetic.py", "bench_traffic_synthetic")

with open(os.path.join(BENCH, "workloads", "fl.patchtst64.wx21.json")) as f:
    LIMITS = json.load(f)["limits"]
with open(os.path.join(BENCH, "traffic", "weather21_jobs.json")) as f:
    WEATHER = json.load(f)

MIXERS = {"patchtst": ["attn", "attn", "attn"],
          "logtst": ["id", "id", "attn"],
          "idformer": ["id", "id", "id"]}
SMALL = dict(look_back=64, horizon=24, patch_len=16, stride=8, d_model=32,
             num_heads=4, d_ff=64, revin=True, use_flash_attn=False)


def small_config(preset):
    return dict(SMALL, mixers=MIXERS[preset])


def random_params(c, seed=0):
    """The reference's initial weights with every leaf moved by seeded
    noise, so that no bias, norm or RevIN leaf sits at its trivial value."""
    params = ref.init_params(c, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        l + 0.1 * jax.random.normal(k, l.shape, l.dtype)
        for l, k in zip(leaves, keys)])


def batch(c, n=32, seed=0):
    series = synthetic.weather_like(seed, 4, 600)
    train, _ = synthetic.split_normalized(series, c["look_back"],
                                          c["horizon"])
    w = c["look_back"] + c["horizon"]
    rows = np.arange(n) % train.shape[0]
    starts = (np.arange(n) * 11) % (train.shape[1] - w + 1)
    win = np.stack([train[r, s:s + w] for r, s in zip(rows, starts)])
    return jnp.asarray(win[:, :c["look_back"]]), jnp.asarray(
        win[:, c["look_back"]:])


def is_key_bias(path):
    return jax.tree_util.keystr(path).endswith("['bk']")


@pytest.fixture(autouse=True)
def highest():
    """The precision the configurations state (a no-op on the CPU, where
    every float32 product is full float32)."""
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("preset", sorted(MIXERS))
def test_forward_matches_reference(preset):
    c = small_config(preset)
    params = random_params(c)
    x, _ = batch(c)
    got = forecast.forward(fl_jobs.model_config(c), params, x)
    want = ref.forward(c, params, x)
    # float32 on both sides, the same equations in a different order
    # (jax.nn.softmax, jnp.var, rsqrt against the reference's written-out
    # forms): a few ulps per operation over ~40 operations in depth, and
    # outputs of order one; measured ~3e-7 relative
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("preset", sorted(MIXERS))
def test_loss_and_gradients_match_reference(preset):
    c = small_config(preset)
    cfg = fl_jobs.model_config(c)
    params = random_params(c)
    x, y = batch(c)
    loss, grads = jax.value_and_grad(
        lambda p: forecast.mse_loss(cfg, p, x, y))(params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: jnp.mean(jnp.square(ref.forward(c, p, x) - y)))(params)
    # the mean of 32 x 24 squared errors of order one: float32 round-off
    # of the forward above, ~1e-7 relative
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    want = jax.tree_util.tree_leaves(ref_grads)
    norms = [float(jnp.linalg.norm(w)) for w in want]
    scale = float(np.median(norms))
    for (path, g), w, n in zip(got, want, norms):
        if is_key_bias(path):
            continue
        # each leaf's gap over its own norm (or the median leaf's, for a
        # leaf whose gradient is small): the backward pass adds as many
        # operations again as the forward, ~1e-6 measured
        gap = float(jnp.linalg.norm(g - w)) / max(n, scale)
        assert gap < 1e-4, (jax.tree_util.keystr(path), gap)


@pytest.mark.parametrize("preset", ["patchtst", "logtst"])
def test_key_biases_have_no_effect(preset):
    """A key bias adds the same amount to every score of a query's row, which
    the softmax takes away: its exact gradient is zero, and what either side
    computes for it is round-off. So it is compared by its effect: moved by
    a random vector, the outputs of the program and of the reference do not
    move, and both gradients are round-off beside the other leaves'."""
    c = small_config(preset)
    cfg = fl_jobs.model_config(c)
    params = random_params(c)
    x, y = batch(c)
    moved = jax.tree_util.tree_map_with_path(
        lambda p, l: l + (jax.random.normal(jax.random.PRNGKey(9), l.shape)
                          if is_key_bias(p) else 0.0), params)
    base = ref.forward(c, params, x)
    for fwd in (lambda p: forecast.forward(cfg, p, x),
                lambda p: ref.forward(c, p, x)):
        np.testing.assert_allclose(fwd(moved), base, rtol=1e-5, atol=1e-5)
    for loss in (lambda p: forecast.mse_loss(cfg, p, x, y),
                 lambda p: jnp.mean(jnp.square(ref.forward(c, p, x) - y))):
        flat = jax.tree_util.tree_flatten_with_path(jax.grad(loss)(params))[0]
        norms = {jax.tree_util.keystr(p): float(jnp.linalg.norm(g))
                 for p, g in flat}
        median = float(np.median(list(norms.values())))
        bk = [v for k, v in norms.items() if k.endswith("['bk']")]
        assert bk and max(bk) < 1e-4 * median, (bk, median)


@pytest.mark.parametrize("preset", sorted(MIXERS))
def test_while_job_matches_reference_job(preset):
    """Three PSGF rounds through the program's normal training path, the
    benchmark's ``fl_jobs`` set-up cut to 4 weather stations, against the
    reference's job from the same weights, data and key, judged by the
    benchmark's comparison with the PatchTST/64 cell's limits."""
    c = small_config(preset)
    fl = {k: WEATHER[k] for k in fl_jobs.FL_KEYS}
    series = synthetic.weather_like(3, 4, 600)
    train, test = synthetic.split_normalized(series, c["look_back"],
                                             c["horizon"])
    params = random_params(c, seed=5)
    key = jax.random.PRNGKey(11)
    fl_cfg = E.FLConfig(policy="psgf", num_clients=4, streaming_windows=True,
                        use_pallas_mix=True, **fl)
    h = E.run_fl(fl_jobs.model_config(c), fl_cfg, jnp.asarray(train),
                 jnp.asarray(test), key, max_rounds=3, patience=51,
                 eval_every=1, driver="while", init_params=params)
    out = {"loss": np.asarray(h["train_loss"], np.float64),
           "comm": np.asarray(h["comm"], np.float64),
           "rmse": np.asarray([r for _, r in h["rmse"]], np.float64),
           "w": np.asarray(h["state"]["w_global"], np.float32),
           "rounds": int(h["rounds_run"])}
    want = ref.fl_job(c, fl, params, train, test, key, rounds=3,
                      eval_every=1)
    nums = fl_jobs.compare(out, want)
    assert np.all(np.isfinite(out["loss"])), out["loss"]
    assert nums["comm_gap"] == 0.0, nums
    for k, limit in LIMITS.items():
        assert nums[k] <= limit, (k, nums)


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_eval_forward_holds_a_bounded_score_block():
    """PatchTST/64's eval over all 5,943 test windows of 21 stations: the flat
    forward would hold (5943, 16, 63, 63) float32 scores, 1.51 GB per
    attention layer, beside the round in the same ``while`` program. The
    eval runs over blocks of clients instead, each within
    ``EVAL_SCORE_BYTES``."""
    with open(os.path.join(BENCH, "configs", "patchtst64.json")) as f:
        c = json.load(f)
    cfg = fl_jobs.model_config(c)
    params = jax.eval_shape(lambda k: ref.init_params(c, k),
                            jax.random.PRNGKey(0))
    vec = jax.ShapeDtypeStruct((ref.param_count(c),), jnp.float32)
    _, meta = tree_flatten_to_vector(
        jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), params))
    test = jax.ShapeDtypeStruct((21, 890), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda w, d: E._rmse_device(cfg, w, meta, d))(vec, test)
    biggest = max(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                  for eqn in _eqns(jaxpr.jaxpr) for v in eqn.outvars
                  if hasattr(v.aval, "shape"))
    assert E._eval_chunk(cfg, 21, 283, None) == 3
    assert biggest <= E.EVAL_SCORE_BYTES, biggest
