"""Traffic kind ``serve_closed``: a planner re-forecasting the fleet, a
closed loop.

``in_flight`` requests are outstanding at every moment: each answer frees
its slot and the next request is submitted through
``ForecastServer.submit``. Requests go to every station in turn, with
``channels`` look-back windows at offsets drawn from the seed.

``serve_forecasts_per_s`` is the number of station-channel forecasts
answered inside the window, over the window's length.
"""
from __future__ import annotations

import os
import queue
import sys
import time
from concurrent.futures import wait

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import serving  # noqa: E402


class Cell:
    CHECKS_WINDOW = True        # the check reads the answers of a window

    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.mix

    def setup(self):
        p = self.p
        self.srv = serving.Server(self.ctx)
        self.rng = self.ctx.rng("offsets")
        self.srv.warm([p["channels"]])
        futs = [self.srv.server.submit(*self._next(i)[1:])
                for i in range(p["in_flight"])]
        wait(futs, timeout=serving.WAIT_AFTER_S)

    def _next(self, i):
        s = i % self.p["stations"]
        x = self.srv.request(s, self.rng.integers(
            0, self.srv.offsets_hi, size=self.p["channels"]))
        return s, x, s

    def window(self, seconds: float) -> dict:
        srv = self.srv.server
        rec = serving.Recorder()
        freed = queue.Queue()
        self.sent = []
        c0 = self.srv.counters()

        def send():
            # the harness keeps no future: a heap of live futures would make
            # each full collection of the garbage collector longer
            i = len(self.sent)
            s, x, _ = self._next(i)
            self.sent.append((s, x))
            with self.ctx.span("submit"):
                fut = srv.submit(x, station=s)
            fut.add_done_callback(rec.callback(i, then=freed.put))

        t0 = time.perf_counter()
        end = t0 + seconds
        for _ in range(self.p["in_flight"]):
            send()
        freed_n = 0
        while True:
            with self.ctx.span("wait"):
                freed.get(timeout=serving.WAIT_AFTER_S)
            freed_n += 1
            if time.perf_counter() >= end:
                break
            send()
        with self.ctx.span("drain"):
            try:
                for _ in range(len(self.sent) - freed_n):
                    freed.get(timeout=serving.WAIT_AFTER_S)
            except queue.Empty:
                pass
        c1 = self.srv.counters()
        self.rec = rec
        n = len(self.sent)
        in_window = sum(self.sent[i][1].shape[0] for i, t in rec.done.items()
                        if t <= end and i not in rec.failed)
        failed = n - len(rec.answers)
        return {"e2e": {"serve_forecasts_per_s": in_window / seconds},
                "attempted": n, "failed": failed,
                "counts": {"requests": n, "window_s": seconds,
                           "series_served": c1["series_served"]
                           - c0["series_served"],
                           "padded_series": c1["padded_series"]
                           - c0["padded_series"]}}

    def release(self):
        self.srv.close()

    def check(self) -> dict:
        pick = serving.check_sample(self.ctx, self.sent, self.rec,
                                    self.p["check_requests"])
        if not pick:
            return {"serve_gap": np.inf}
        return {"serve_gap": self.srv.gap([self.sent[i] for i in pick],
                                          [self.rec.answers[i]
                                           for i in pick])}
