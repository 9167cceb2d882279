"""Pallas TPU kernel for the paper's masked parameter mix (eqs. 4 & 6):

    w_out = S * w_global + (I - S) * w_local

fused with the communication accounting reduction sum(S) — the quantity the
paper's "#Params (Comm.)" column tracks. On the server this runs once per
round over the full flattened parameter vector (D ~ 5.4e5 for LoGTST, up to
~1e11 for the PSGF-DP variant), a purely memory-bound streaming op: the fusion
saves one full pass over the mask versus separate mix + reduce.

Layout: the 1-D vector is viewed as (rows, 128) lanes and tiled in
(block_rows, 128) VMEM blocks — (8,128)-aligned for the VPU. Each block
writes its mask count as 128 per-lane partial sums, a (1, 128) block of a
(grid, 1, 128) output: the TPU lowering takes a block whose last two dims
are (8, 128)-divisible or equal the array's, which a scalar-per-block
output is not. ops.py reduces the partial sums.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _kernel(wg_ref, wl_ref, m_ref, out_ref, cnt_ref):
    m = m_ref[...]
    out_ref[...] = (m * wg_ref[...] + (1.0 - m) * wl_ref[...]).astype(out_ref.dtype)
    cnt_ref[0] = jnp.sum(m.astype(jnp.float32), axis=0, keepdims=True)


def psgf_mix_kernel(w_global, w_local, mask, *, block_rows=256, interpret=False):
    """All inputs: (rows, 128) f32. Returns (mixed (rows, 128), per-lane
    counts (grid, 1, 128))."""
    rows = w_global.shape[0]
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0
    grid = (rows // block_rows,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), w_global.dtype),
            jax.ShapeDtypeStruct((grid[0], 1, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(w_global, w_local, mask)


def _batch_kernel(wg_ref, wl_ref, m_ref, out_ref, cnt_ref):
    m = m_ref[...]  # (1, block_rows, LANES)
    out_ref[...] = (m * wg_ref[...] + (1.0 - m) * wl_ref[...]).astype(out_ref.dtype)
    cnt_ref[0, 0] = jnp.sum(m[0].astype(jnp.float32), axis=0, keepdims=True)


def psgf_mix_batch_kernel(w_global, w_clients, mask, *, block_rows=256,
                          interpret=False):
    """Client-batched mix for the FL engine's downlink: ``w_global`` is
    (rows, 128), ``w_clients``/``mask`` are (K, rows, 128). Grid
    ``(K, rows // block_rows)`` — the global block is re-read per client from
    HBM but never materialized as a (K, rows, 128) broadcast. Returns
    ``(mixed (K, rows, 128), per-lane counts (K, rows // block_rows, 1,
    128))``."""
    K, rows = w_clients.shape[0], w_clients.shape[1]
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0
    grid = (K, rows // block_rows)
    return pl.pallas_call(
        _batch_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda k, i: (i, 0)),
            pl.BlockSpec((1, block_rows, LANES), lambda k, i: (k, i, 0)),
            pl.BlockSpec((1, block_rows, LANES), lambda k, i: (k, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows, LANES), lambda k, i: (k, i, 0)),
            pl.BlockSpec((1, 1, 1, LANES), lambda k, i: (k, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, rows, LANES), w_clients.dtype),
            jax.ShapeDtypeStruct((K, grid[1], 1, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(w_global, w_clients, mask)
