"""Faults planted underneath the timed path, to show that the comparison
that decides ``correct`` catches them.

Each fault replaces one function of the program in this process (the
program's files are not touched) and JAX's caches are cleared, so the next
trace picks it up. ``planted(name)`` plants one for the duration of a
``with`` block.

Training cells (``fl_jobs``):
  * ``state_unchanged`` — a round returns the state it was given;
  * ``half_batch``      — the local loss is the mean over half of each
                          minibatch, the other half left out;
  * ``no_uplink``       — the uplink aggregation is left out: the global
                          model never takes the clients' uploads.
Serving cells (``serve_closed``):
  * ``answer_altered``  — the first forecast of every dispatched bucket is
                          moved by half a standard deviation where the
                          engine produces it;
  * ``misrouted``       — requests are served by the other cluster's model.
"""
from __future__ import annotations

import contextlib


def _swap(obj, attr, value):
    old = vars(obj)[attr]          # the raw attribute: a staticmethod stays one
    setattr(obj, attr, value)
    return lambda: setattr(obj, attr, old)


def state_unchanged():
    from repro.core.fl import engine

    orig = engine._round

    def frozen(state, *args):
        _, metrics = orig(state, *args)
        return state, metrics

    return _swap(engine, "_round", frozen)


def half_batch():
    from repro.core import forecast

    orig = forecast.mse_loss

    def half(cfg, params, x, y):
        h = x.shape[0] // 2
        return orig(cfg, params, x[:h], y[:h])

    return _swap(forecast, "mse_loss", half)


def no_uplink():
    from repro.core.fl import engine

    return _swap(engine, "aggregate",
                 lambda clients, global_, up, selected: global_)


def answer_altered():
    from repro.launch import serve_forecast as sf

    orig = sf._ClusterEngine.run_padded

    def altered(self, x, rows):
        out = orig(self, x, rows).copy()
        out[0] += 0.5
        return out

    return _swap(sf._ClusterEngine, "run_padded", altered)


def misrouted():
    from repro.launch import serve_forecast as sf

    orig = sf.ForecastServer._resolve

    def other(gen, station=None, cluster=None):
        c = orig(gen, station=station, cluster=cluster)
        if station is not None and cluster is None:
            return sorted(gen.engines)[1 - sorted(gen.engines).index(c)]
        return c

    return _swap(sf.ForecastServer, "_resolve", staticmethod(other))


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch, no_uplink,
                                  answer_altered, misrouted)}
TRAINING = ("state_unchanged", "half_batch", "no_uplink")
SERVING = ("answer_altered", "misrouted")


@contextlib.contextmanager
def planted(name):
    if name is None:
        yield
        return
    import jax

    undo = FAULTS[name]()
    jax.clear_caches()
    try:
        yield
    finally:
        undo()
        jax.clear_caches()
