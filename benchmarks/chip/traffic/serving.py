"""What the serving traffic kinds share: the routed forecast server they
drive, the requests they send, and the comparison of its answers with the
plain reference.

The server is the program's ``ForecastServer`` built from ``models=``: one
cluster model per entry of the mix's ``clusters`` (each with its own random
weights from the seed), a station-to-cluster table drawn from the seed, and
each station's normalisation statistics, so requests carry raw look-backs in
kWh and answers come back in kWh. A request is ``M`` look-back windows of
one station, cut from that station's synthetic series at offsets drawn from
the seed.

Correctness: after the window, a sample of the answered requests drawn from
the seed is run through the plain reference, its matrix products in float32
(``highest``): normalised with the station's statistics, the forward of the
station's cluster model, denormalised. ``serve_gap`` is the widest gap of an
answer from the reference, in units of the station's standard deviation
(the units the model predicts in).
"""
from __future__ import annotations

import os
import re
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import synthetic  # noqa: E402

CHECK_BLOCK = 1024
WAIT_AFTER_S = 60.0


class Server:
    def __init__(self, ctx):
        import jax

        from repro.core.forecast import ForecastConfig
        from repro.core.forecaster import Forecaster
        from repro.launch.serve_forecast import ForecastServer

        c, p = ctx.config, ctx.mix
        self.ctx, self.c, self.p = ctx, c, p
        self.series = synthetic.GENERATORS[p["dataset"]](
            ctx.data_seed, p["stations"], p["steps"])
        mu, sd = synthetic.norm_stats(self.series)
        self.mu, self.sd = mu.ravel(), sd.ravel()
        order = ctx.rng("clusters").permutation(p["stations"])
        self.cluster_of = np.zeros(p["stations"], np.int64)
        start = 0
        for i, n in enumerate(p["clusters"]):
            self.cluster_of[order[start:start + n]] = i
            start += n
        if start != p["stations"]:
            raise ValueError("cluster sizes do not add up to the stations")
        n_models = len(p["clusters"])
        stacked = jax.jit(partial(ctx.reference.init_params, c,
                                  copies=n_models))(ctx.key("weights"))
        self.params = [jax.tree_util.tree_map(lambda a, i=i: a[i], stacked)
                       for i in range(n_models)]
        cfg = ForecastConfig(
            mixers=tuple(c["mixers"]),
            **{k: c[k] for k in ("look_back", "horizon", "patch_len",
                                 "stride", "d_model", "num_heads", "d_ff",
                                 "revin", "use_flash_attn")})
        self.server = ForecastServer(
            models={i: (Forecaster(cfg), self.params[i])
                    for i in range(n_models)},
            station_cluster=self.cluster_of.tolist(),
            station_norm=(self.mu, self.sd),
            max_batch=p["max_batch"], max_wait_ms=p["max_wait_ms"])
        self.L = c["look_back"]
        self.offsets_hi = self.series.shape[1] - self.L

    def warm(self, channels):
        """Compile and run every bucket, and every live-row count a bucket
        can be cut to, for each channel count the traffic sends."""
        L = self.L
        for m in channels:
            for cl in range(len(self.params)):
                for b in range(1, self.server.max_batch + 1):
                    self.server.predict(np.zeros((b, m, L), np.float32),
                                        cluster=cl)
        self.server.start()

    def request(self, station: int, offsets) -> np.ndarray:
        idx = np.asarray(offsets)[:, None] + np.arange(self.L)[None, :]
        return self.series[station, idx]

    def counters(self) -> dict:
        """Series served and bucket slots padded, per channel count, from
        the server's exposition of its own counters."""
        series = padded = 0.0
        for line in self.server.metrics_text().splitlines():
            m = re.match(r'forecast_series_served_total\{[^}]*\} (\S+)$', line)
            if m:
                series += float(m.group(1))
            m = re.match(r'forecast_padded_slots_total\{[^}]*shape="(\d+)x'
                         r'\d+"[^}]*\} (\S+)$', line)
            if m:
                padded += float(m.group(2)) * int(m.group(1))
        return {"series_served": series, "padded_series": padded}

    def close(self):
        self.server.close()
        del self.server

    def reference_answers(self, reqs):
        """The reference's answers to ``reqs``, a list of (station, x)."""
        import jax
        import jax.numpy as jnp

        ref = self.ctx.reference
        fwd = jax.jit(lambda p, x: ref.forward(self.c, p, x))
        rows, owner = {}, []
        for i, (s, x) in enumerate(reqs):
            xn = (x - self.mu[s]) / self.sd[s]
            cl = int(self.cluster_of[s])
            for row in xn:
                rows.setdefault(cl, []).append(row)
                owner.append((cl, len(rows[cl]) - 1))
        out = {}
        for cl, rs in rows.items():
            xs = np.stack(rs).astype(np.float32)
            n = len(xs)
            pad = (-n) % CHECK_BLOCK
            xs = np.concatenate([xs, np.zeros((pad, self.L), np.float32)])
            ys = [np.asarray(fwd(self.params[cl],
                                 jnp.asarray(xs[i:i + CHECK_BLOCK])))
                  for i in range(0, len(xs), CHECK_BLOCK)]
            out[cl] = np.concatenate(ys)[:n]
        answers, k = [], 0
        for s, x in reqs:
            ys = []
            for _ in range(x.shape[0]):
                cl, j = owner[k]
                ys.append(out[cl][j])
                k += 1
            answers.append(np.stack(ys) * self.sd[s] + self.mu[s])
        return answers

    def gap(self, reqs, answers) -> float:
        """Widest gap of ``answers`` from the reference, in station sd."""
        worst = 0.0
        for (s, _), y, r in zip(reqs, answers, self.reference_answers(reqs)):
            g = float(np.max(np.abs(np.asarray(y, np.float64) - r))
                      / self.sd[s])
            worst = max(worst, g if np.isfinite(g) else np.inf)
        return worst


class Recorder:
    """Completion times and answers of the requests sent, by index."""

    def __init__(self):
        self.done = {}
        self.answers = {}
        self.failed = set()

    def callback(self, i: int, then=None):
        def on_done(fut):
            self.done[i] = time.perf_counter()
            if fut.cancelled() or fut.exception() is not None:
                self.failed.add(i)
            else:
                self.answers[i] = fut.result()
            if then is not None:
                then(i)
        return on_done


def check_sample(ctx, sent, rec, limit_n: int):
    """Indices of the answered requests the comparison covers: a sample
    drawn from the seed, with the request of the most channels in it."""
    answered = sorted(rec.answers)
    rng = ctx.rng("check")
    pick = sorted(rng.choice(answered, size=min(limit_n, len(answered)),
                             replace=False).tolist()) if answered else []
    if answered:
        widest = max(answered, key=lambda i: sent[i][1].shape[0])
        if widest not in pick:
            pick.append(widest)
    return pick
