"""Pallas TPU flash attention (GQA, causal / sliding-window).

Design (TPU-native):
  * grid = (batch, q_heads, num_q_blocks, num_kv_blocks); the kv dimension is
    innermost and sequential ("arbitrary"), carrying the online-softmax state
    (m, l, acc) in VMEM scratch across kv steps.
  * The kernel takes heads-major (B, H, S, hd) operands, so each BlockSpec
    tile is q (1, 1, block_q, hd), k/v (1, 1, block_k, hd): the last two
    block dims are (block, hd), with block a multiple of 8 and hd the whole
    head dim, which is what the TPU lowering accepts. A (1, block, 1, hd)
    tile of a (B, S, H, hd) array puts a 1 against H in the second-to-last
    dim and is refused. q tiles stay resident while K/V stream HBM->VMEM
    block by block.
  * block sizes default to 512x512 with hd<=256: working set
    ~ (block_q + 2*block_k) * hd * 4B + block_q*block_k*4B ≈ 1.6 MB << VMEM.
  * MXU alignment: block_q/block_k multiples of 128; hd is the contraction.
  * GQA: the kv-head index is derived from the q-head grid index in the
    BlockSpec index_map (h // group) — no KV duplication in HBM.

Masking uses absolute positions (q_offset + iota), so causal and
sliding-window are one code path. Validated against ref.py in interpret mode
(tests/test_kernels.py) and compiled for a described v5e in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, block_q, block_k, num_kv_blocks, kv_len):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)  # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_len  # padding
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # Masked positions must contribute EXACT zeros. `exp(s - m_new)` alone is
    # not enough: on a block whose every key is masked (padding past kv_len,
    # or a window that excludes the whole block), m_new stays NEG_INF and
    # exp(NEG_INF - NEG_INF) == 1 — every masked key would leak 1.0 of
    # softmax mass. The sequential kv walk happens to wipe that mass once a
    # later block holds a valid key (corr underflows to 0), but rows with NO
    # valid key would return a garbage average of v instead of 0, and the
    # correctness of padded bidirectional calls would hinge on block-visit
    # order. Zeroing through the mask makes padded keys inert by
    # construction.
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal=True, window=None,
                           block_q=512, block_k=512, kv_len=None,
                           interpret=False):
    """Heads-major layout: q (B, H, Sq, hd); k, v (B, KV, Skv, hd) with
    H % KV == 0. Returns (B, H, Sq, hd).

    Sq/Skv must already be padded to block multiples (ops.py handles padding
    and unpadding); ``kv_len`` is the ORIGINAL (unpadded) kv length used to
    mask out padding keys.
    """
    B, H, Sq, hd = q.shape
    _, KV, Skv, _ = k.shape
    assert H % KV == 0
    group = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    nq = Sq // block_q
    nk = Skv // block_k
    scale = 1.0 / math.sqrt(hd)
    kv_len = Skv if kv_len is None else kv_len

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk, kv_len=kv_len)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
