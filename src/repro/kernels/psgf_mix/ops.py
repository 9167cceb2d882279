"""Jit'd wrappers for the psgf_mix kernels: 1-D/2-D vector <-> (rows,128)
layout, padding with mask=0 (padding contributes local values and zero count).
``interpret=None`` resolves through :func:`repro.kernels.resolve_interpret`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.psgf_mix.kernel import (
    LANES, psgf_mix_batch_kernel, psgf_mix_kernel,
)


def _pick_block_rows(rows: int, block_rows: int) -> int:
    """Largest divisor of ``rows`` that is a multiple of 8 (f32 (8,128)
    sublane alignment) and <= ``block_rows`` (clamped up to 8, so the grid
    never degrades to scalar-row launches). ``rows`` is always a multiple of
    8 here — the wrappers pad the vector to LANES*8."""
    assert rows % 8 == 0, rows
    cap = max(block_rows, 8) // 8
    units = rows // 8
    best = 1
    for d in range(1, int(units ** 0.5) + 1):
        if units % d == 0:
            for u in (d, units // d):
                if u <= cap:
                    best = max(best, u)
    return 8 * best


def psgf_mix(w_global, w_local, mask, *, block_rows=256, interpret=None):
    """w_global/w_local: (D,) float; mask: (D,) bool/float.
    Returns (mixed (D,), count scalar f32)."""
    return _psgf_mix(w_global, w_local, mask, block_rows=block_rows,
                     interpret=resolve_interpret(interpret))


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _psgf_mix(w_global, w_local, mask, *, block_rows, interpret):
    D = w_global.shape[0]
    m = mask.astype(w_global.dtype)
    pad = (-D) % (LANES * 8)
    wg = jnp.pad(w_global, (0, pad))
    wl = jnp.pad(w_local, (0, pad))
    mp = jnp.pad(m, (0, pad))
    rows = wg.shape[0] // LANES
    br = _pick_block_rows(rows, block_rows)
    mixed, counts = psgf_mix_kernel(
        wg.reshape(rows, LANES), wl.reshape(rows, LANES), mp.reshape(rows, LANES),
        block_rows=br, interpret=interpret)
    return mixed.reshape(-1)[:D], jnp.sum(counts)


def psgf_mix_batch(w_global, w_clients, mask, *, block_rows=256,
                   interpret=None):
    """Client-batched fused mix + comm count (the FL engine's downlink).

    w_global: (D,) float; w_clients/mask: (K, D). Returns (mixed (K, D),
    count scalar f32 = sum over ALL clients' realized gates)."""
    return _psgf_mix_batch(w_global, w_clients, mask, block_rows=block_rows,
                           interpret=resolve_interpret(interpret))


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _psgf_mix_batch(w_global, w_clients, mask, *, block_rows, interpret):
    K, D = w_clients.shape
    m = mask.astype(w_clients.dtype)
    pad = (-D) % (LANES * 8)
    wg = jnp.pad(w_global, (0, pad)).reshape(-1, LANES)
    wl = jnp.pad(w_clients, ((0, 0), (0, pad))).reshape(K, -1, LANES)
    mp = jnp.pad(m, ((0, 0), (0, pad))).reshape(K, -1, LANES)
    br = _pick_block_rows(wg.shape[0], block_rows)
    mixed, counts = psgf_mix_batch_kernel(wg, wl, mp, block_rows=br,
                                          interpret=interpret)
    return mixed.reshape(K, -1)[:, :D], jnp.sum(counts)
