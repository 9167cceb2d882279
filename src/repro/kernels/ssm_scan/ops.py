"""Jit'd wrapper for ssm_scan: pads (S -> chunk multiple, D -> d_block
multiple) and unpads. Padding timesteps use dt=0 (identity state transition,
zero input) so they do not disturb the carried state. ``interpret=None``
resolves through :func:`repro.kernels.resolve_interpret`."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.ssm_scan.kernel import ssm_scan_kernel


def ssm_scan(x, dt, Bm, Cm, A, *, chunk=128, d_block=512, interpret=None):
    return _ssm_scan(x, dt, Bm, Cm, A, chunk=chunk, d_block=d_block,
                     interpret=resolve_interpret(interpret))


@partial(jax.jit, static_argnames=("chunk", "d_block", "interpret"))
def _ssm_scan(x, dt, Bm, Cm, A, *, chunk, d_block, interpret):
    B, S, D = x.shape
    N = A.shape[1]
    ck = min(chunk, S)
    db = min(d_block, D)
    pad_s = (-S) % ck
    pad_d = (-D) % db
    if pad_s:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_s), (0, 0)))  # dt=0 -> identity step
        Bm = jnp.pad(Bm, ((0, 0), (0, pad_s), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad_s), (0, 0)))
    if pad_d:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_d)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad_d)))
        A = jnp.pad(A, ((0, pad_d), (0, 0)))
    y = ssm_scan_kernel(x, dt, Bm, Cm, A, chunk=ck, d_block=db,
                        interpret=interpret)
    return y[:, :S, :D]
