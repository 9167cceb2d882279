"""The paper's forecasting models: LoGTST, PatchTST and MetaFormer variants.

Pipeline (Fig. 3 of the paper):
  RevIN -> Tokenization (1-D conv patch embed) -> N blocks -> DeTokenization
  (flatten + MLP) -> RevIN denorm.

Block token-mixers (Fig. 2):
  * ``attn`` — multi-head self-attention (Transformer block, eq. 2)
  * ``mlp``  — Time-MLP along the token axis (MLPFormer)
  * ``id``   — identity / no token mixing (IDFormer)

LoGTST = ("id", "id", "attn"): "the model can fully process the local
features and keep the final transformer block for parsing of global
dependency". PatchTST = ("attn", "attn", "attn").

Channel independence follows PatchTST: multivariate series are reshaped to
(B*M, L) and share weights across channels (paper §III.A.1).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import spec as S
from repro.models.spec import ArraySpec


@dataclasses.dataclass(frozen=True)
class ForecastConfig:
    look_back: int = 128        # paper FL setting: 128 steps
    horizon: int = 2            # EV: 2; NN5: 4; Table I: 96/192/336/720
    patch_len: int = 16         # P (conv kernel == patch length)
    stride: int = 8             # S
    d_model: int = 128
    num_heads: int = 16
    d_ff: int = 256
    mixers: Tuple[str, ...] = ("id", "id", "attn")   # LoGTST
    dropout: float = 0.0        # kept for config parity; eval-mode graphs
    revin: bool = True
    # use_flash_attn: route _self_attn through the Pallas flash-attention
    # kernel (repro.kernels.flash_attention, bidirectional causal=False;
    # compiled on the chip, interpreted only on the CPU backend — see
    # repro.kernels.resolve_interpret). Numerics match the dense jnp path to
    # FLASH_ATTN_TOL (guarded in tests/test_flash_forecast.py, the same
    # bit-tolerance contract psgf_mix carries); False (the default) is the
    # exact historical dense softmax, bitwise.
    use_flash_attn: bool = False

    @property
    def num_tokens(self) -> int:
        return (self.look_back - self.patch_len) // self.stride + 1

    @property
    def name(self) -> str:
        if all(m == "attn" for m in self.mixers):
            return f"patchtst/{self.num_tokens}"
        if all(m == "id" for m in self.mixers):
            return "idformer"
        if all(m == "mlp" for m in self.mixers):
            return "mlpformer"
        return f"logtst/{self.num_tokens}"


def logtst_config(**kw) -> ForecastConfig:
    return ForecastConfig(mixers=("id", "id", "attn"), **kw)


def patchtst_config(**kw) -> ForecastConfig:
    return ForecastConfig(mixers=("attn", "attn", "attn"), **kw)


def mlpformer_config(**kw) -> ForecastConfig:
    return ForecastConfig(mixers=("mlp", "mlp", "mlp"), **kw)


def idformer_config(**kw) -> ForecastConfig:
    return ForecastConfig(mixers=("id", "id", "id"), **kw)


# ---------------------------------------------------------------------------
# RevIN [18]
# ---------------------------------------------------------------------------


def revin_spec():
    return {
        "affine_w": ArraySpec((1,), (None,), init="ones"),
        "affine_b": ArraySpec((1,), (None,), init="zeros"),
    }


def revin_norm(params, x, eps: float = 1e-5):
    """x: (B, L). Returns normalized x and (mean, std) for denorm."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    std = jnp.sqrt(jnp.var(x, axis=-1, keepdims=True) + eps)
    y = (x - mean) / std
    y = y * params["affine_w"] + params["affine_b"]
    return y, (mean, std)


def revin_denorm(params, y, stats, eps: float = 1e-5):
    """Exact inverse of the affine step of :func:`revin_norm`.

    Divides by ``affine_w`` itself whenever it is nonzero (the earlier
    ``max(|w|, eps) * sign(w)`` clamp was off by ``eps/|w|`` for
    ``0 < |w| < eps`` and collapsed every prediction to the series mean at
    ``w == 0``, where ``sign`` is 0). Only ``w == 0`` — where the forward
    affine destroys the signal — falls back to ``eps``.
    """
    mean, std = stats
    w = params["affine_w"]
    safe_w = jnp.where(w == 0.0, eps, w)
    x = (y - params["affine_b"]) / safe_w
    return x * std + mean


# ---------------------------------------------------------------------------
# Tokenization / DeTokenization (eq. 1)
# ---------------------------------------------------------------------------


def tokenize_spec(cfg: ForecastConfig):
    return {
        "w": ArraySpec((cfg.patch_len, cfg.d_model), (None, "embed"), init="scaled"),
        "b": ArraySpec((cfg.d_model,), ("embed",), init="zeros"),
        "pos": ArraySpec((cfg.num_tokens, cfg.d_model), (None, "embed"), init="normal"),
    }


def tokenize(params, x, cfg: ForecastConfig):
    """x: (B, L) -> tokens (B, N, D). Conv1d(P, stride=S) == unfold + matmul."""
    B = x.shape[0]
    N = cfg.num_tokens
    idx = jnp.arange(N)[:, None] * cfg.stride + jnp.arange(cfg.patch_len)[None, :]
    patches = x[:, idx]  # (B, N, P)
    tok = patches @ params["w"] + params["b"]
    return tok + params["pos"]  # additive learnable positional encoding


def detokenize_spec(cfg: ForecastConfig):
    flat = cfg.num_tokens * cfg.d_model
    return {
        "w": ArraySpec((flat, cfg.horizon), (None, None), init="scaled"),
        "b": ArraySpec((cfg.horizon,), (None,), init="zeros"),
    }


def detokenize(params, tok):
    """Pred = MLP{Concat[Flat(V_0), Flat(V_1), ...]} (eq. 1)."""
    B = tok.shape[0]
    with jax.named_scope("forecast.head"):
        return tok.reshape(B, -1) @ params["w"] + params["b"]


# ---------------------------------------------------------------------------
# MetaFormer blocks
# ---------------------------------------------------------------------------


def _ln_spec(d):
    return {
        "scale": ArraySpec((d,), ("act_embed",), init="ones"),
        "bias": ArraySpec((d,), ("act_embed",), init="zeros"),
    }


def _ln(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]).astype(
        x.dtype
    )


def block_spec(cfg: ForecastConfig, mixer: str):
    d = cfg.d_model
    spec = {"ln1": _ln_spec(d), "ln2": _ln_spec(d)}
    if mixer == "attn":
        hd = d // cfg.num_heads
        spec["attn"] = {
            "wq": ArraySpec((d, cfg.num_heads, hd), ("embed", "heads", "head_dim"), init="scaled"),
            "wk": ArraySpec((d, cfg.num_heads, hd), ("embed", "heads", "head_dim"), init="scaled"),
            "wv": ArraySpec((d, cfg.num_heads, hd), ("embed", "heads", "head_dim"), init="scaled"),
            "wo": ArraySpec((cfg.num_heads, hd, d), ("heads", "head_dim", "embed"), init="scaled"),
            "bq": ArraySpec((cfg.num_heads, hd), ("heads", "head_dim"), init="zeros"),
            "bk": ArraySpec((cfg.num_heads, hd), ("heads", "head_dim"), init="zeros"),
            "bv": ArraySpec((cfg.num_heads, hd), ("heads", "head_dim"), init="zeros"),
            "bo": ArraySpec((d,), ("act_embed",), init="zeros"),
        }
    elif mixer == "mlp":
        n = cfg.num_tokens
        spec["time_mlp"] = {
            "w1": ArraySpec((n, n), (None, None), init="scaled"),
            "b1": ArraySpec((n,), (None,), init="zeros"),
        }
    elif mixer == "id":
        pass
    else:
        raise ValueError(mixer)
    spec["mlp"] = {
        "w1": ArraySpec((d, cfg.d_ff), ("embed", "mlp"), init="scaled"),
        "b1": ArraySpec((cfg.d_ff,), ("mlp",), init="zeros"),
        "w2": ArraySpec((cfg.d_ff, d), ("mlp", "embed"), init="scaled"),
        "b2": ArraySpec((d,), ("act_embed",), init="zeros"),
    }
    return spec


# Pinned flash-vs-dense tolerance: both paths softmax in fp32 over the same
# scores, so they differ only in accumulation order (online vs dense softmax)
# and the cast point of the output. Guarded per preset, forward AND
# VJP-through-mse_loss, in tests/test_flash_forecast.py — the same contract
# psgf_mix pins for the downlink mix.
FLASH_ATTN_TOL = 1e-5


def _self_attn(p, x, cfg: ForecastConfig):
    """Bidirectional MHSA over tokens (eq. 2). x: (B, N, D).

    ``cfg.use_flash_attn`` routes the softmax(QK^T)V contraction through the
    Pallas flash-attention kernel (online softmax, no materialized
    (B, H, N, N) score matrix); the default keeps the dense einsum path
    bitwise unchanged. Both share the projections and output mix.
    """
    with jax.named_scope("forecast.attn"):
        hd = cfg.d_model // cfg.num_heads
        q = jnp.einsum("bnd,dhk->bnhk", x, p["wq"]) + p["bq"]
        k = jnp.einsum("bnd,dhk->bnhk", x, p["wk"]) + p["bk"]
        v = jnp.einsum("bnd,dhk->bnhk", x, p["wv"]) + p["bv"]
        if cfg.use_flash_attn:
            from repro.kernels.flash_attention.ops import flash_attention

            # tokens attend bidirectionally (eq. 2), so causal=False
            o = flash_attention(q, k, v, causal=False)
        else:
            s = jnp.einsum("bnhk,bmhk->bhnm", q, k) / math.sqrt(hd)
            a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
            o = jnp.einsum("bhnm,bmhk->bnhk", a, v)
        return jnp.einsum("bnhk,hkd->bnd", o, p["wo"]) + p["bo"]


def block_apply(params, x, cfg: ForecastConfig, mixer: str):
    h = _ln(params["ln1"], x)
    if mixer == "attn":
        x = x + _self_attn(params["attn"], h, cfg)
    elif mixer == "mlp":
        # Time-MLP: MLP along the token axis
        t = jnp.einsum("bnd,nm->bmd", h, params["time_mlp"]["w1"]) + params["time_mlp"][
            "b1"
        ][None, :, None]
        x = x + jax.nn.gelu(t)
    elif mixer == "id":
        x = x + h  # identity mixer: the sublayer reduces to the norm residual
    h = _ln(params["ln2"], x)
    m = params["mlp"]
    with jax.named_scope("forecast.mlp"):
        x = x + (jax.nn.gelu(h @ m["w1"] + m["b1"]) @ m["w2"] + m["b2"])
    return x


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------


def model_spec(cfg: ForecastConfig):
    spec = {
        "tokenize": tokenize_spec(cfg),
        "blocks": {f"b{i}": block_spec(cfg, m) for i, m in enumerate(cfg.mixers)},
        "detokenize": detokenize_spec(cfg),
    }
    if cfg.revin:
        spec["revin"] = revin_spec()
    return spec


def init_params(cfg: ForecastConfig, key):
    return S.init_params(model_spec(cfg), key)


def num_params(cfg: ForecastConfig) -> int:
    return S.spec_num_params(model_spec(cfg))


def forward(cfg: ForecastConfig, params, x):
    """x: (B, L) univariate look-back -> (B, T) prediction."""
    stats = None
    if cfg.revin:
        x, stats = revin_norm(params["revin"], x)
    tok = tokenize(params["tokenize"], x, cfg)
    for i, m in enumerate(cfg.mixers):
        tok = block_apply(params["blocks"][f"b{i}"], tok, cfg, m)
    pred = detokenize(params["detokenize"], tok)
    if cfg.revin:
        pred = revin_denorm(params["revin"], pred, stats)
    return pred


def forward_multivariate(cfg: ForecastConfig, params, x):
    """x: (B, M, L) -> (B, M, T); channel-independent shared weights."""
    B, M, Lw = x.shape
    y = forward(cfg, params, x.reshape(B * M, Lw))
    return y.reshape(B, M, cfg.horizon)


def mse_loss(cfg: ForecastConfig, params, x, y):
    """Paper loss: L = 1/M sum ||x_hat - x||^2 (MSE over horizon)."""
    pred = forward(cfg, params, x) if x.ndim == 2 else forward_multivariate(cfg, params, x)
    return jnp.mean(jnp.square(pred - y))
