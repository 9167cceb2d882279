"""Jit'd public wrapper for the flash_attention Pallas kernel: moves the
sequence-major (B, S, H, hd) operands to the kernel's heads-major layout,
pads sequence lengths to block multiples, dispatches, unpads. On a TPU the
call lowers to Mosaic. ``interpret=None`` (the default) resolves through
:func:`repro.kernels.resolve_interpret`: interpret mode on the CPU backend,
where the tests run the kernel body in Python, and the compiled kernel
everywhere else; interpret mode is refused off the CPU.

Differentiation: ``pallas_call`` has no autodiff rule, so ``flash_attention``
carries a ``jax.custom_vjp`` whose backward pass is the VJP of the dense jnp
oracle (:func:`repro.kernels.flash_attention.ref.attention_ref`) on the saved
(q, k, v) residuals. The oracle computes the same attention function (guarded
to tolerance in tests/test_kernels.py and tests/test_flash_forecast.py), so
the gradients are exact for the math while the backward recompute is the
O(S^2) dense form — the right trade at the forecaster's token counts
(num_tokens ~ 15-63), where the score matrix is tiny and a flash backward
kernel would be all overhead.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref


def _flash_fwd_impl(causal, window, block_q, block_k, interpret, q, k, v):
    """heads-major -> pad -> kernel -> unpad -> sequence-major (the primal
    pipeline)."""
    Sq = q.shape[1]
    Skv = k.shape[1]
    bq = min(block_q, _round_up(Sq, 128))
    bk = min(block_k, _round_up(Skv, 128))

    def heads_major(x, pad):
        x = jnp.swapaxes(x, 1, 2)   # (B, S, H, hd) -> (B, H, S, hd)
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else x

    out = flash_attention_kernel(
        heads_major(q, (-Sq) % bq), heads_major(k, (-Skv) % bk),
        heads_major(v, (-Skv) % bk), causal=causal, window=window,
        block_q=bq, block_k=bk, kv_len=Skv, interpret=interpret)
    return jnp.swapaxes(out[:, :, :Sq], 1, 2)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash(causal, window, block_q, block_k, interpret, q, k, v):
    return _flash_fwd_impl(causal, window, block_q, block_k, interpret, q, k, v)


def _flash_fwd(causal, window, block_q, block_k, interpret, q, k, v):
    out = _flash_fwd_impl(causal, window, block_q, block_k, interpret, q, k, v)
    return out, (q, k, v)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda a, b, c: attention_ref(a, b, c, causal=causal, window=window),
        q, k, v)
    return vjp(do)


_flash.defvjp(_flash_fwd, _flash_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def _flash_jit(q, k, v, *, causal, window, block_q, block_k, interpret):
    return _flash(causal, window, block_q, block_k, interpret, q, k, v)


def flash_attention(q, k, v, *, causal=True, window=None,
                    block_q=512, block_k=512, interpret=None):
    """q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd) -> (B,Sq,H,hd).

    ``interpret=None`` resolves through :func:`repro.kernels.
    resolve_interpret`. Differentiable via a custom VJP whose backward is
    the dense oracle's (see module docstring).
    """
    return _flash_jit(q, k, v, causal=causal, window=window, block_q=block_q,
                      block_k=block_k, interpret=resolve_interpret(interpret))


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
