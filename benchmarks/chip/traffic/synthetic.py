"""Synthetic series the traffic kinds draw from, kept with the benchmark so
that the yardstick does not move with the program.

``ev_synthetic`` and ``weather_like`` are copies of the generators in the
program's ``repro.data.synthetic`` (the UK-EV and Weather data sets cannot
be downloaded here); ``split_normalized`` is the per-client z-normalisation
and chronological split the program's data pipeline applies, written out
again.
"""
from __future__ import annotations

import numpy as np


def ev_synthetic(seed: int, num_clients: int = 58, num_days: int = 420):
    """(K, T) daily consumed energy in kWh per charging station: weak
    weekly seasonality, heavy noise, idle days, maintenance spans and
    per-station scale differences."""
    rng = np.random.default_rng(seed)
    t = np.arange(num_days)
    out = np.zeros((num_clients, num_days), np.float32)
    for i in range(num_clients):
        base = rng.gamma(3.0, 12.0)
        weekly = 1.0 + 0.25 * np.sin(2 * np.pi * (t + rng.integers(7)) / 7.0)
        trend = 1.0 + 0.3 * t / num_days * rng.uniform(-1, 1)
        lam = base * weekly * trend
        x = rng.gamma(2.0, lam / 2.0)
        idle = rng.random(num_days) < 0.08
        x[idle] = 0.0
        n_spans = rng.integers(1, 4)
        for _ in range(n_spans):
            s = rng.integers(0, num_days - 10)
            ln = rng.integers(3, 15)
            x[s:s + ln] = 0.0
        out[i] = x
    return out


def weather_like(seed: int, num_channels: int = 21, length: int = 2016):
    """(C, T) 10-minute weather-station-like series: a daily cycle, a slow
    seasonal term and an AR(1) component per channel."""
    rng = np.random.default_rng(seed)
    t = np.arange(length)
    daily = np.sin(2 * np.pi * t / 144.0)
    out = np.zeros((num_channels, length), np.float32)
    for c in range(num_channels):
        season = np.sin(2 * np.pi * t / (144.0 * 365) * rng.uniform(0.5, 2))
        ar = np.zeros(length)
        e = rng.standard_normal(length) * 0.4
        phi = rng.uniform(0.8, 0.98)
        for i in range(1, length):
            ar[i] = phi * ar[i - 1] + e[i]
        out[c] = rng.uniform(0.3, 1.0) * daily + 0.5 * season + ar
    return out


GENERATORS = {"ev": ev_synthetic, "weather": weather_like}


def norm_stats(series: np.ndarray, train_frac: float = 0.8):
    """Per-client mean and standard deviation over the first ``train_frac``
    of the steps: ``(mu, sd)``, each ``(K, 1)``."""
    n = int(series.shape[1] * train_frac)
    mu = series[:, :n].mean(axis=1, keepdims=True)
    sd = series[:, :n].std(axis=1, keepdims=True) + 1e-6
    return mu, sd


def split_normalized(series: np.ndarray, look_back: int, horizon: int,
                     train_frac: float = 0.7, val_frac: float = 0.1):
    """Z-normalise each client and cut the raw series into chronological
    train and test slices whose stride-1 windows of ``look_back + horizon``
    steps are the train and test windows."""
    mu, sd = norm_stats(series)
    x = ((series - mu) / sd).astype(np.float32)
    w = look_back + horizon
    n = x.shape[1] - w + 1
    if n <= 0:
        raise ValueError(f"series of {x.shape[1]} steps are too short for "
                         f"windows of {w}")
    n_tr, n_va = int(n * train_frac), int(n * val_frac)
    n_te = n - n_tr - n_va
    return (x[:, :n_tr + w - 1],
            x[:, n_tr + n_va:n_tr + n_va + n_te + w - 1])
