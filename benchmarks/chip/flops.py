"""Operations and bytes the benchmarked work needs, counted from shapes.

Only multiply-adds of matrix products count as operations (2 per
multiply-add); element-wise work, normalisation and softmax are left out, so
the counts are a floor that any implementation has to perform. Bytes are the
least traffic to and from device memory that the operation needs, whatever
implements it.
"""
from __future__ import annotations


def num_tokens(c: dict) -> int:
    return (c["look_back"] - c["patch_len"]) // c["stride"] + 1


def attention_core_flops(c: dict, rows: int) -> float:
    """QK^T and PV of one attention layer over ``rows`` windows, at the true
    token count (no padding)."""
    n, d = num_tokens(c), c["d_model"]
    return 2.0 * 2.0 * rows * n * n * d


def forward_flops(c: dict) -> float:
    """Matrix-product operations of one forward pass over one window."""
    n, d, f, p = num_tokens(c), c["d_model"], c["d_ff"], c["patch_len"]
    total = 2.0 * n * p * d                              # patch embedding
    for mixer in c["mixers"]:
        if mixer == "attn":
            total += 4 * 2.0 * n * d * d                 # q, k, v, o
            total += attention_core_flops(c, 1)
        total += 2 * 2.0 * n * d * f                     # feed-forward
    total += 2.0 * n * d * c["horizon"]                  # flatten head
    return total


def train_flops(c: dict) -> float:
    """Forward and backward over one training window: three forwards, with
    nothing recomputed counted."""
    return 3.0 * forward_flops(c)


def mix_bytes(clients: int, dim: int) -> float:
    """The downlink mix of ``clients`` rows of ``dim`` float32 parameters:
    every client row read and written, the 0/1 gate at one bit, and the
    global vector read once."""
    return clients * dim * (4.0 + 4.0 + 1.0 / 8.0) + 4.0 * dim
