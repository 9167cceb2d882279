"""Tests for the streaming window pipeline (PR 5 tentpole).

The FL engine can now train and evaluate straight off raw ``(K, T)`` series
slices (``FLConfig.streaming_windows``) instead of the materialized
``(K, n_win, L+T)`` window tensor. The contract is BIT-IDENTITY: same seed ->
same per-round states, comm counters and final RMSE as the materialized
layout, across every policy and all three drivers, at ~``(L+T)``x less
training-data memory. Covers:

  * ``split_series`` raw slices window-for-window equal to
    ``split_windows(make_windows(...))``;
  * ``client_series`` / ``client_series_datasets`` == ``client_datasets``
    modulo materialization (same cleaning, normalization, split boundaries);
  * ``clean_clients`` short-series regression (the ``-T // 4`` tail slice
    degenerated to the WHOLE series for ``T < 4``);
  * engine round + ``run_fl`` bit-identity for all policies x all drivers;
  * ``evaluate_rmse`` streaming == materialized, chunked == unchunked;
  * layout validation errors;
  * ``ExperimentSpec.streaming_windows`` end-to-end through
    ``run_experiment``;
  * the window fetch (``engine._windows``) equals scalar indexing bit for
    bit, and no streaming call site lowers to a gather that takes the raw
    slice one time step per index.
"""
import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import forecast as F
from repro.core.fl import client_store as CS
from repro.core.fl import engine as E
from repro.core.tasks import ExperimentSpec, get_task, run_experiment, task_forecaster
from repro.data.synthetic import nn5_synthetic
from repro.data.windowing import (clean_clients, client_datasets, client_series,
                                  client_series_datasets, make_windows,
                                  split_series, split_windows,
                                  window_split_counts)

TINY = dict(look_back=16, horizon=2, d_model=8, num_heads=2, d_ff=16,
            patch_len=8, stride=4)
L, H = TINY["look_back"], TINY["horizon"]


def _both_layouts(num_clients=6, num_days=120, look_back=L, horizon=H):
    series = nn5_synthetic(seed=0, num_clients=num_clients, num_days=num_days)
    mat = client_datasets(series, look_back, horizon)
    st = client_series_datasets(series, look_back, horizon)
    return series, mat, st


def _tiny_cfgs(policy="psgf", num_clients=6, **fl_kw):
    model_cfg = F.logtst_config(**TINY)
    base = dict(policy=policy, num_clients=num_clients, local_steps=2,
                batch_size=8, **fl_kw)
    return (model_cfg, E.FLConfig(**base),
            E.FLConfig(streaming_windows=True, **base))


# ---- data layer -------------------------------------------------------------


def test_split_series_windows_equal_materialized_splits():
    """Every stride-1 window of each raw split slice == the corresponding
    materialized split window, and the counts match window_split_counts."""
    series = nn5_synthetic(seed=1, num_clients=4, num_days=90)
    w = make_windows(series, L, H)
    mats = split_windows(w)
    raws = split_series(series, L, H)
    counts = window_split_counts(series.shape[1], L, H)
    assert sum(counts) == w.shape[1]
    for mat, raw, n in zip(mats, raws, counts):
        assert mat.shape[1] == n
        assert raw.shape[1] == n + (L + H) - 1  # adjacent windows share steps
        np.testing.assert_array_equal(make_windows(raw, L, H), mat)


def test_client_series_matches_client_datasets():
    """Same cleaning, same normalization stats, same split boundaries — the
    raw-series variant differs ONLY in not materializing windows."""
    series, (tr, va, te, info), (tr2, va2, te2, info2) = _both_layouts()
    np.testing.assert_array_equal(info["kept"], info2["kept"])
    for a, b in zip(info["norm"], info2["norm"]):
        np.testing.assert_array_equal(a, b)
    for mat, raw in ((tr, tr2), (va, va2), (te, te2)):
        np.testing.assert_array_equal(make_windows(raw, L, H), mat)
    # the (series, split_idx, info) form agrees with both
    norm_series, split_idx, info3 = client_series(series, L, H)
    assert split_idx == (tr.shape[1], va.shape[1], te.shape[1])
    np.testing.assert_array_equal(info["kept"], info3["kept"])
    np.testing.assert_array_equal(
        split_series(norm_series, L, H)[0], tr2)


def test_streaming_memory_factor():
    """The point of the layout: raw slices are ~(L+T)x smaller."""
    _, (tr, _, _, _), (tr2, _, _, _) = _both_layouts(num_days=300)
    assert tr.size / tr2.size > (L + H) / 2


def test_clean_clients_short_series_tail_clamped():
    """Regression: for T < 4, ``series[:, -T // 4:]`` was ``series[:, 0:]`` —
    the "alive tail" check silently tested the WHOLE history, keeping
    stations that died at the end. The tail is now clamped to >= 1 step."""
    # station 0 active throughout; station 1 active early, dead at the end
    s = np.array([[5.0, 5.0, 5.0],
                  [5.0, 5.0, 0.0]])
    out, kept = clean_clients(s)
    assert kept.tolist() == [0], (
        "dead-tail station survived: tail check saw the whole 3-step history")
    # T >= 4 behavior unchanged: quarter-tail, same keep decisions
    s4 = np.array([[5.0] * 8, [5.0] * 6 + [0.0] * 2, [0.0] * 8])
    out4, kept4 = clean_clients(s4)
    assert kept4.tolist() == [0]


# ---- engine: streaming == materialized, bitwise -----------------------------


@pytest.mark.parametrize("policy", ["online", "pso", "psgf", "psgf_topk"])
def test_fl_round_streaming_bit_identical(policy):
    """ONE engine round: the streaming start-index draw + on-device gather
    must reproduce the materialized minibatch indexing bit-for-bit (same RNG
    -> same indices -> same window values) for every policy."""
    _, (tr, _, te, _), (tr2, _, te2, _) = _both_layouts()
    model_cfg, fl_m, fl_s = _tiny_cfgs(policy)
    state, meta = E.init_fl_state(model_cfg, fl_m, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(7)
    s_m, m_m = E.fl_round(state, jnp.asarray(tr), key, model_cfg, fl_m, meta)
    s_s, m_s = E.fl_round(state, jnp.asarray(tr2), key, model_cfg, fl_s, meta)
    for k in s_m:
        np.testing.assert_array_equal(np.asarray(s_m[k]), np.asarray(s_s[k]),
                                      err_msg=f"state[{k}] diverged ({policy})")
    for k in m_m:
        np.testing.assert_array_equal(np.asarray(m_m[k]), np.asarray(m_s[k]),
                                      err_msg=f"metrics[{k}] diverged ({policy})")


@pytest.mark.parametrize("driver", ["loop", "scan", "while"])
@pytest.mark.parametrize("policy", ["online", "pso", "psgf", "psgf_topk"])
def test_run_fl_streaming_bit_identical(policy, driver):
    """The acceptance criterion: same seed -> identical per-round losses,
    comm counters, final state and final RMSE between the layouts, for every
    policy under every driver."""
    _, (tr, _, te, _), (tr2, _, te2, _) = _both_layouts()
    model_cfg, fl_m, fl_s = _tiny_cfgs(policy)
    kw = dict(max_rounds=4, patience=5, eval_every=2, driver=driver)
    h_m = E.run_fl(model_cfg, fl_m, jnp.asarray(tr), jnp.asarray(te),
                   jax.random.PRNGKey(0), **kw)
    h_s = E.run_fl(model_cfg, fl_s, jnp.asarray(tr2), jnp.asarray(te2),
                   jax.random.PRNGKey(0), **kw)
    assert h_m["rounds_run"] == h_s["rounds_run"]
    np.testing.assert_array_equal(np.asarray(h_m["train_loss"]),
                                  np.asarray(h_s["train_loss"]))
    np.testing.assert_array_equal(np.asarray(h_m["comm"]),
                                  np.asarray(h_s["comm"]))
    for k in h_m["state"]:
        np.testing.assert_array_equal(np.asarray(h_m["state"][k]),
                                      np.asarray(h_s["state"][k]),
                                      err_msg=f"state[{k}] ({policy}/{driver})")
    assert h_m["final_rmse"] == h_s["final_rmse"]
    assert [r for r, _ in h_m["rmse"]] == [r for r, _ in h_s["rmse"]]
    np.testing.assert_array_equal([v for _, v in h_m["rmse"]],
                                  [v for _, v in h_s["rmse"]])


def test_streaming_early_stop_parity():
    """Patience fires at the same boundary in both layouts (the on-device
    early stop compares the same losses)."""
    _, (tr, _, te, _), (tr2, _, te2, _) = _both_layouts()
    model_cfg, fl_m, fl_s = _tiny_cfgs("psgf")
    kw = dict(max_rounds=30, patience=1, eval_every=5, driver="while")
    h_m = E.run_fl(model_cfg, fl_m, jnp.asarray(tr), jnp.asarray(te),
                   jax.random.PRNGKey(0), **kw)
    h_s = E.run_fl(model_cfg, fl_s, jnp.asarray(tr2), jnp.asarray(te2),
                   jax.random.PRNGKey(0), **kw)
    assert h_m["rounds_run"] == h_s["rounds_run"] < 30


def test_evaluate_rmse_streaming_bit_identical():
    """Streaming eval == materialized eval, and the client_chunk'd streaming
    eval (per-client on-device gather inside lax.map) == the flat one."""
    _, (tr, _, te, _), (_, _, te2, _) = _both_layouts()
    model_cfg, fl_m, _ = _tiny_cfgs("psgf")
    state, meta = E.init_fl_state(model_cfg, fl_m, jax.random.PRNGKey(0))
    w = state["w_global"]
    full_mat = E.evaluate_rmse(model_cfg, w, meta, jnp.asarray(te))
    full_st = E.evaluate_rmse(model_cfg, w, meta, jnp.asarray(te2))
    assert full_st == full_mat
    for chunk in (1, 2, 4, 64):
        assert E.evaluate_rmse(model_cfg, w, meta, jnp.asarray(te2),
                               client_chunk=chunk) == full_mat, chunk


def test_run_fl_rejects_mismatched_layout():
    """The flag and the data layout must agree — a window tensor under
    streaming_windows (or raw series without it) is a loud error, not a
    silently wrong window count."""
    _, (tr, _, te, _), (tr2, _, te2, _) = _both_layouts()
    model_cfg, fl_m, fl_s = _tiny_cfgs("psgf")
    with pytest.raises(ValueError, match="streaming_windows=True"):
        E.run_fl(model_cfg, fl_s, jnp.asarray(tr), jnp.asarray(te),
                 jax.random.PRNGKey(0), max_rounds=1)
    with pytest.raises(ValueError, match="streaming_windows=False"):
        E.run_fl(model_cfg, fl_m, jnp.asarray(tr2), jnp.asarray(te2),
                 jax.random.PRNGKey(0), max_rounds=1)
    # raw slices shorter than one window: loud error too
    with pytest.raises(ValueError, match="too short"):
        E.run_fl(model_cfg, fl_s, jnp.asarray(tr2[:, :L]),
                 jnp.asarray(te2), jax.random.PRNGKey(0), max_rounds=1)


# ---- the window fetch: whole rows, never single elements -------------------


def _scalar_windows(row, starts, width):
    """The scalar-index form of the window fetch: one index per value."""
    return row[starts[:, None] + jnp.arange(width)[None, :]]


def _bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("wrap", ["jit", "client_vmap"])
@pytest.mark.parametrize("starts", ["first", "last", "random"])
def test_window_fetch_equals_scalar_indexing(starts, wrap):
    """``_windows`` returns the scalar-index form's values bit for bit (a
    signed zero included) at the first and last valid starts and at random
    ones, alone under ``jit`` and under the client ``vmap`` as
    ``_local_update_all`` runs it."""
    W, B = L + H, 8
    series = jnp.asarray(nn5_synthetic(seed=2, num_clients=5, num_days=70),
                         jnp.float32).at[:, 3].set(-0.0)
    K, n_win = series.shape[0], series.shape[1] - W + 1
    idx = {"first": jnp.zeros((K, B), jnp.int32),
           "last": jnp.full((K, B), n_win - 1, jnp.int32),
           "random": jax.random.randint(jax.random.PRNGKey(5), (K, B), 0,
                                        n_win)}[starts]
    fetch = partial(E._windows, width=W)
    want = jax.vmap(partial(_scalar_windows, width=W))(series, idx)
    if wrap == "jit":
        got = jnp.stack([jax.jit(fetch)(series[k], idx[k]) for k in range(K)])
    else:
        got = jax.jit(jax.vmap(fetch))(series, idx)
    assert got.shape == (K, B, W)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(
        _bits(E._all_windows(series, W)),
        _bits(make_windows(np.asarray(series), L, H)))


_GATHER = re.compile(
    r'"stablehlo\.gather".*?slice_sizes = array<i64: ([\d, ]+)>.*?'
    r': \(tensor<([\dx]+)xf32>, tensor<[^>]*>\) -> tensor<([\dx]+)xf32>')


def _gathers(text):
    """(operand shape, slice sizes, result shape) of every float gather in a
    lowered module's text."""
    dims = lambda s, sep: tuple(int(d) for d in s.split(sep))
    return [(dims(op, "x"), dims(sizes, ", "), dims(out, "x"))
            for sizes, op, out in _GATHER.findall(text)]


@pytest.mark.parametrize("site", ["local_update", "eval", "eval_chunked",
                                  "chunk_sse"])
def test_streaming_sites_never_gather_single_steps(site):
    """Lowering guard. Every streaming call site takes whole windows, never
    the raw slice one time step per index (a gather whose slice is one
    element wide along time: on a TPU v5e that form took 9.35 ms of a 76.7
    ms paper round). The local update's window gather moves ``L+T``-wide
    rows."""
    _, _, (tr2, _, te2, _) = _both_layouts()
    model_cfg, _, fl_s = _tiny_cfgs("psgf")
    state, meta = E.init_fl_state(model_cfg, fl_s, jax.random.PRNGKey(0))
    w = state["w_global"]
    if site == "local_update":
        raw = jnp.asarray(tr2)
        keys = jax.random.split(jax.random.PRNGKey(1), raw.shape[0])
        lowered = jax.jit(partial(E._local_update_all, model_cfg, fl_s,
                                  meta)).lower(
            state["w_clients"], state["adam_m"], state["adam_v"],
            state["adam_t"], raw, keys)
    elif site == "chunk_sse":
        raw = jnp.asarray(te2)
        lowered = CS._chunk_sse.lower(w, raw, model_cfg=model_cfg, meta=meta)
    else:
        raw = jnp.asarray(te2)
        chunk = 2 if site == "eval_chunked" else None
        lowered = jax.jit(lambda w_, d_: E._rmse_device(
            model_cfg, w_, meta, d_, chunk)).lower(w, raw)
    gathers = _gathers(lowered.as_text())
    T = raw.shape[1]
    scalar = [g for g in gathers if g[0][-1] == T and g[1][-1] == 1]
    assert not scalar, f"{site} gathers the raw slice step by step: {scalar}"
    if site == "local_update":
        K, B, W = raw.shape[0], fl_s.batch_size, L + H
        rows = [g for g in gathers if g[2] == (K, B, W)]
        assert rows, gathers
        assert all(g[1][-1] == W and math.prod(g[1]) == W for g in rows), rows


# ---- ExperimentSpec plumbing ------------------------------------------------


def test_run_experiment_streaming_matches_materialized():
    """The spec-level flag drives the whole grid through the raw layout and
    reproduces the materialized rows exactly (rounds, RMSE, comm)."""
    task = get_task("nn5", quick=True, num_clients=6, num_days=120,
                    look_back=16, horizon=2)
    model = task_forecaster(task, "logtst", quick=True, **TINY)
    base = dict(task=task, model=model, grid=(("psgf", {}), ("online", {})),
                local_steps=1, batch_size=8, max_rounds=2, patience=3,
                eval_every=2)
    res_m = run_experiment(ExperimentSpec(**base))
    res_s = run_experiment(ExperimentSpec(streaming_windows=True, **base))
    assert len(res_m["rows"]) == len(res_s["rows"]) == 2
    for rm, rs in zip(res_m["rows"], res_s["rows"]):
        assert rm["policy"] == rs["policy"]
        assert rm["rounds"] == rs["rounds"]
        assert rm["rmse"] == rs["rmse"]
        assert rm["comm_params"] == rs["comm_params"]
