"""Pallas TPU chunked selective-scan (Mamba-style SSM) kernel.

The recurrence h_t = exp(dt_t * A) h_{t-1} + (dt_t x_t) B_t,  y_t = <h_t, C_t>
is sequential in t but embarrassingly parallel in (batch, d_inner). TPU
adaptation (vs the CUDA scan in the Mamba paper):

  * grid = (B, num_d_blocks, num_chunks); the chunk dimension is innermost
    and sequential ("arbitrary"), carrying h (d_block, N) in VMEM scratch
    across chunks — HBM traffic for the state is zero.
  * within a chunk the time loop runs over VMEM-resident tiles; all ops are
    (d_block, N)-shaped VPU elementwise work, d_block a multiple of 128 lanes.
  * dt/x: (1, chunk, d_block) tiles; B/C: (1, chunk, N) tiles; A: (d_block, N).

VMEM working set: chunk*(2*d_block + 2N) + 2*d_block*N floats
(chunk=128, d_block=512, N=16 -> ~0.6 MB), far under the 128 MB budget;
larger d_block amortizes grid overhead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, h_scr, *, chunk):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)    # (chunk, d_blk)
    dt = dt_ref[0].astype(jnp.float32)  # (chunk, d_blk)
    Bm = b_ref[0].astype(jnp.float32)   # (chunk, N)
    Cm = c_ref[0].astype(jnp.float32)   # (chunk, N)
    A = a_ref[...].astype(jnp.float32)  # (d_blk, N)

    def step(t, carry):
        h, ys = carry
        dt_t = jax.lax.dynamic_slice_in_dim(dt, t, 1, 0)[0]  # (d_blk,)
        x_t = jax.lax.dynamic_slice_in_dim(x, t, 1, 0)[0]
        B_t = jax.lax.dynamic_slice_in_dim(Bm, t, 1, 0)[0]   # (N,)
        C_t = jax.lax.dynamic_slice_in_dim(Cm, t, 1, 0)[0]
        dA = jnp.exp(dt_t[:, None] * A)                      # (d_blk, N)
        h = dA * h + (dt_t * x_t)[:, None] * B_t[None, :]
        y_t = jnp.sum(h * C_t[None, :], axis=1)              # (d_blk,)
        ys = jax.lax.dynamic_update_slice_in_dim(ys, y_t[None], t, 0)
        return h, ys

    ys0 = jnp.zeros((chunk, x.shape[1]), jnp.float32)
    h, ys = jax.lax.fori_loop(0, chunk, step, (h_scr[...], ys0))
    h_scr[...] = h
    y_ref[0] = ys.astype(y_ref.dtype)


def ssm_scan_kernel(x, dt, Bm, Cm, A, *, chunk=128, d_block=512, interpret=False):
    """x, dt: (B, S, D); Bm, Cm: (B, S, N); A: (D, N). Returns y (B, S, D).
    S must be a multiple of ``chunk`` and D of ``d_block`` (ops.py pads).
    """
    B, S, D = x.shape
    N = A.shape[1]
    chunk = min(chunk, S)
    d_block = min(d_block, D)
    assert S % chunk == 0 and D % d_block == 0
    grid = (B, D // d_block, S // chunk)
    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, jd, ic: (b, ic, jd)),
            pl.BlockSpec((1, chunk, d_block), lambda b, jd, ic: (b, ic, jd)),
            pl.BlockSpec((1, chunk, N), lambda b, jd, ic: (b, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, jd, ic: (b, ic, 0)),
            pl.BlockSpec((d_block, N), lambda b, jd, ic: (jd, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block), lambda b, jd, ic: (b, ic, jd)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((d_block, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, Bm, Cm, A)
