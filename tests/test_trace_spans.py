"""The program's own names for the profiler, and the server's counters:

  * a compiled ``run_fl(driver="while")`` job carries every ``fl.*`` stage
    scope and the forecaster's ``forecast.*`` scopes in its operations'
    metadata (what a device trace's ``tf_op`` shows), and the scopes leave
    the job's results bitwise as they are without them;
  * served requests under ``jax.profiler`` yield every ``serve.*`` span, the
    per-group steps nested in ``serve.group``, one ``serve.group`` per
    dispatched batch, and a ``gc.collect`` span per collection;
  * a collection bumps the server's two gc counters while it is started,
    and no longer once it is stopped or closed;
  * ``stats`` and ``cluster_stats`` are read-only views of the registry's
    counters, exact under concurrent callers, with or without exposition.
"""
import gc
import glob
import re
import threading
from concurrent.futures import wait

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import forecast as F
from repro.core.fl import engine as E
from repro.core.fl import policies as pol
from repro.core.forecaster import get_forecaster
from repro.data.synthetic import nn5_synthetic
from repro.data.windowing import client_series_datasets
from repro.launch.metrics import parse_exposition, sum_samples
from repro.launch.serve_forecast import ForecastServer

STAGES = ("fl.round_down", "fl.local_update", "fl.window_gather", "fl.adam",
          "fl.round_up", "fl.eval")
MODEL_SCOPES = ("forecast.attn", "forecast.mlp", "forecast.head")
STEPS = ("serve.assemble", "serve.step", "serve.copy_back", "serve.resolve")
SERVE_SPANS = ("serve.submit", "serve.queue_wait", "serve.coalesce",
               "serve.group") + STEPS
TINY = dict(look_back=16, horizon=2, d_model=16, num_heads=2, d_ff=16,
            patch_len=8, stride=4)


# ---- device scopes ----------------------------------------------------------


def _tiny_job():
    """A tiny LoGTST ``while`` job's model, FL configuration and data."""
    model_cfg = F.logtst_config(look_back=32, horizon=2, d_model=16,
                                num_heads=2, d_ff=32, patch_len=8, stride=4)
    fl_cfg = E.FLConfig(policy="psgf", num_clients=4, local_steps=2,
                        batch_size=8, streaming_windows=True)
    tr, _, te, _ = client_series_datasets(
        nn5_synthetic(seed=0, num_clients=4, num_days=200), 32, 2)
    return model_cfg, fl_cfg, jnp.asarray(tr), jnp.asarray(te)


@pytest.fixture(scope="module")
def while_job_op_names():
    """Every ``op_name`` in the compiled HLO of a tiny ``while`` job."""
    model_cfg, fl_cfg, tr, te = _tiny_job()
    state, meta = E.init_fl_state(model_cfg, fl_cfg, jax.random.PRNGKey(0))
    hlo = E._run_while_jit.lower(
        state, jax.random.PRNGKey(1), tr, te,
        model_cfg, fl_cfg, meta, pol.from_config(fl_cfg), 4, 2, 5,
    ).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.mark.parametrize("scope", STAGES)
def test_while_job_names_stage_scope(while_job_op_names, scope):
    assert any(scope in n.split("/") or f"({scope})" in n
               for n in while_job_op_names), scope


@pytest.mark.parametrize("scope", MODEL_SCOPES)
def test_while_job_names_forecaster_scope(while_job_op_names, scope):
    """The forecaster's attention, block feed-forward and head, inside the
    local update (forward and backward) and the eval."""
    hits = [n for n in while_job_op_names
            if scope in n.split("/") or f"({scope})" in n]
    assert any("fl.local_update" in n for n in hits), scope
    assert any("fl.eval" in n for n in hits), scope


def test_scopes_leave_the_job_bitwise_unchanged(monkeypatch):
    """Scopes are metadata only: a job traced with every ``named_scope``
    made a no-op gives the same losses, RMSEs and weights, bit for bit."""
    import contextlib

    model_cfg, fl_cfg, tr, te = _tiny_job()

    def job():
        jax.clear_caches()
        h = E.run_fl(model_cfg, fl_cfg, tr, te, jax.random.PRNGKey(3),
                     max_rounds=4, patience=5, eval_every=2, driver="while")
        return (np.asarray(h["train_loss"]), np.asarray(h["rmse"]),
                np.asarray(h["state"]["w_global"]))

    scoped = job()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = job()
    jax.clear_caches()
    for a, b in zip(scoped, bare):
        np.testing.assert_array_equal(a, b)


def test_window_gather_and_adam_nest_in_local_update(while_job_op_names):
    for inner in ("fl.window_gather", "fl.adam"):
        hits = [n for n in while_job_op_names if f"/{inner}/" in n]
        assert hits and all("fl.local_update" in n for n in hits), inner


# ---- host spans -------------------------------------------------------------


def _server(rng_key, **kw):
    fc = get_forecaster("logtst", **TINY)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_ms", 1.0)
    return ForecastServer(fc, fc.init_params(rng_key), **kw)


def _host_lines(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    pd = ProfileData.from_file(path)
    return [[(e.name, e.start_ns, e.end_ns) for e in line.events]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines]


@pytest.fixture(scope="module")
def served_trace(tmp_path_factory, rng_key):
    """Host lines of a trace of 10 requests served (and one forced
    collection), with the batches dispatched meanwhile."""
    server = _server(rng_key)
    server.warmup(channels=1)
    server.start()
    wait([server.submit(np.ones((1, 16), np.float32))])
    before = server.stats["batches"]
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    xs = np.random.default_rng(0).standard_normal((10, 1, 16))
    wait([server.submit(x.astype(np.float32)) for x in xs])
    gc.collect()
    wait([server.submit(np.ones((1, 16), np.float32))])
    jax.profiler.stop_trace()
    batches = server.stats["batches"] - before
    server.close()
    return _host_lines(trace_dir), batches


def test_serving_yields_every_span(served_trace):
    lines, _ = served_trace
    names = {n for line in lines for n, _, _ in line}
    for span in SERVE_SPANS + ("gc.collect",):
        assert span in names, span


def test_group_steps_nest_in_their_group(served_trace):
    lines, _ = served_trace
    seen = set()
    for line in lines:
        groups = [(s, e) for n, s, e in line if n == "serve.group"]
        for n, s, e in line:
            if n in STEPS:
                seen.add(n)
                assert any(gs <= s and e <= ge for gs, ge in groups), n
    assert seen == set(STEPS)


def test_one_group_span_per_dispatched_batch(served_trace):
    lines, batches = served_trace
    groups = sum(n == "serve.group" for line in lines for n, _, _ in line)
    assert batches > 0 and groups == batches


# ---- gc counters ------------------------------------------------------------


def _gc_counts(server):
    s = parse_exposition(server.metrics_text())
    return (sum_samples(s, "forecast_gc_collections_total", generation="2"),
            sum_samples(s, "forecast_gc_pause_seconds_total", generation="2"))


def test_forced_collection_bumps_both_gc_counters(rng_key):
    server = _server(rng_key)
    server.start()
    runs0, pause0 = _gc_counts(server)
    gc.collect()
    runs1, pause1 = _gc_counts(server)
    # at least the forced one (the allocator may start one of its own)
    assert runs1 >= runs0 + 1 and pause1 > pause0
    server.close()
    gc.collect()
    assert _gc_counts(server) == (runs1, pause1)


def test_gc_counters_count_only_while_started(rng_key):
    server = _server(rng_key)
    gc.collect()
    assert _gc_counts(server) == (0, 0)
    server.close()


def test_stop_unhooks_the_collector_and_start_hooks_it_again(rng_key):
    server = _server(rng_key)
    server.start()
    server.stop()
    stopped = _gc_counts(server)
    gc.collect()
    assert _gc_counts(server) == stopped
    server.start()
    gc.collect()
    assert _gc_counts(server)[0] >= stopped[0] + 1
    server.close()


# ---- stats are views of the registry ----------------------------------------


def test_stats_are_read_only_views_of_the_registry(rng_key):
    server = _server(rng_key)
    server.warmup(channels=1)
    s = parse_exposition(server.metrics_text())
    assert server.stats["batches"] == sum_samples(s, "forecast_batches_total")
    assert server.stats["series_served"] \
        == sum_samples(s, "forecast_series_served_total")
    with pytest.raises(TypeError):
        server.stats["batches"] = 0
    with pytest.raises(TypeError):
        server.cluster_stats[None] = {}
    server.close()


@pytest.mark.parametrize("metrics", [True, False])
def test_concurrent_tallies_are_exact(rng_key, metrics):
    """Worker and predict callers on other threads count every series once:
    the tallies are the registry's locked counters, not a second unlocked
    dict."""
    server = _server(rng_key, metrics=metrics)
    server.warmup(channels=1)
    base = dict(server.stats)
    server.start()
    x = np.ones((1, 16), np.float32)
    THREADS, PER = 6, 20

    def caller():
        for _ in range(PER):
            server.predict(x[None])
            server.submit(x).result(timeout=30)

    threads = [threading.Thread(target=caller) for _ in range(THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    server.stop()
    n = THREADS * PER
    assert server.stats["requests"] - base["requests"] == n
    assert server.stats["series_served"] - base["series_served"] == 2 * n
    assert server.cluster_stats[None]["requests"] == n
    if metrics:
        s = parse_exposition(server.metrics_text())
        assert sum_samples(s, "forecast_series_served_total") \
            == server.stats["series_served"]
    else:
        assert server.metrics is None and server.metrics_text() == ""
    server.close()


def test_hidden_registry_keeps_no_latency_histogram(rng_key):
    """With ``metrics=False`` nothing reads the latency histogram, so a
    submit records none."""
    server = _server(rng_key, metrics=False)
    server.start()
    fut = server.submit(np.ones((1, 16), np.float32))
    fut.result(timeout=30)
    server.close()
    assert "forecast_latency_seconds_count" not in server._registry.expose()
    assert server.stats["requests"] == 1
