"""Per-kernel validation: shape/dtype sweeps, interpret=True vs ref.py oracle
(the container is CPU-only; interpret mode executes kernel bodies in Python).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # optional-dep guard

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.psgf_mix.ops import _pick_block_rows, psgf_mix, psgf_mix_batch
from repro.kernels.psgf_mix.ref import psgf_mix_batch_ref, psgf_mix_ref
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref


# ---------------- flash_attention ----------------

FA_CASES = [
    # B, Sq, Skv, H, KV, hd, causal, window, dtype
    (2, 256, 256, 4, 2, 64, True, None, jnp.float32),
    (1, 200, 200, 4, 4, 128, True, 64, jnp.float32),
    (2, 128, 384, 8, 2, 64, False, None, jnp.float32),
    (1, 256, 256, 2, 1, 128, True, None, jnp.bfloat16),
    (1, 100, 100, 6, 3, 32, True, 17, jnp.float32),
]


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_vs_ref(case, rng_key):
    B, Sq, Skv, H, KV, hd, causal, window, dtype = case
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, KV, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, KV, hd)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_k=128, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_attention_block_size_invariance(rng_key):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    o1 = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    o2 = flash_attention(q, k, v, block_q=256, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


def test_flash_attention_causality(rng_key):
    """Perturbing future keys must not change earlier outputs."""
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    o1 = flash_attention(q, k, v, causal=True, interpret=True)
    k2 = k.at[:, 64:].set(9.0)
    v2 = v.at[:, 64:].set(-9.0)
    o2 = flash_attention(q, k2, v2, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(o1[:, :64]), np.asarray(o2[:, :64]),
                               atol=1e-6)
    assert not np.allclose(np.asarray(o1[:, 64:]), np.asarray(o2[:, 64:]))


PAD_BIDIR_CASES = [
    # bidirectional (causal=False) at sequence lengths NOT divisible by
    # block_k: the pad-to-block-multiple path must keep padded keys inert
    # (regression for the padded-KV masking sweep; N=15 is the forecaster's
    # LoGTST token count)
    (2, 15, 15, 4, 4, 8, 128),
    (1, 100, 100, 4, 2, 32, 64),
    (1, 130, 130, 8, 8, 16, 128),
    (3, 63, 63, 2, 1, 64, 128),
]


@pytest.mark.parametrize("case", PAD_BIDIR_CASES)
def test_flash_attention_bidirectional_padded_vs_oracle(case, rng_key):
    """causal=False at N % block_k != 0 against the dense jnp oracle — the
    exact shape class the forecaster's `_self_attn` routes through the
    kernel (tests/test_flash_forecast.py covers the end-to-end model)."""
    B, Sq, Skv, H, KV, hd, bk = case
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd))
    k = jax.random.normal(ks[1], (B, Skv, KV, hd))
    v = jax.random.normal(ks[2], (B, Skv, KV, hd))
    out = flash_attention(q, k, v, causal=False, block_q=bk, block_k=bk,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=False, window=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_padded_keys_inert(rng_key):
    """Garbage in the padded KV tail must not reach any output row: the
    kernel masks by kv_len, so poisoning k/v past the true length changes
    nothing (bidirectional, non-block-multiple lengths). The kernel takes
    heads-major (B, H, S, hd) operands."""
    from repro.kernels.flash_attention.kernel import flash_attention_kernel

    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 16))
    k = jax.random.normal(ks[1], (1, 2, 256, 16))
    v = jax.random.normal(ks[2], (1, 2, 256, 16))
    kv_len = 100                      # rows 100..255 are padding
    base = flash_attention_kernel(q, k, v, causal=False, block_q=128,
                                  block_k=128, kv_len=kv_len, interpret=True)
    kp = k.at[:, :, kv_len:].set(50.0)   # large scores if the mask leaked
    vp = v.at[:, :, kv_len:].set(-50.0)
    poisoned = flash_attention_kernel(q, kp, vp, causal=False, block_q=128,
                                      block_k=128, kv_len=kv_len,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))


def test_flash_attention_fully_masked_rows_zero(rng_key):
    """A query row with NO valid key must output exact zeros. Before the
    masked-exp hardening, a kv block with every key masked contributed
    exp(NEG_INF - NEG_INF) == 1 of softmax mass per key — rows whose valid
    window never materialized returned a garbage average of v instead."""
    from repro.kernels.flash_attention.kernel import flash_attention_kernel

    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 16))
    k = jax.random.normal(ks[1], (1, 2, 128, 16))
    v = jax.random.normal(ks[2], (1, 2, 128, 16))
    # bidirectional sliding window: q rows with q_pos - window >= kv_len see
    # only padding (valid keys would start past the true kv length)
    out = flash_attention_kernel(q, k, v, causal=False, window=16,
                                 block_q=128, block_k=128, kv_len=100,
                                 interpret=True)
    dead = np.asarray(out)[0, :, 120:]   # q_pos >= 116 has no valid key
    np.testing.assert_array_equal(dead, np.zeros_like(dead))
    live = np.asarray(out)[0, :, :100]
    seq_major = lambda x: jnp.swapaxes(x[:, :, :100], 1, 2)
    ref = np.asarray(attention_ref(seq_major(q), seq_major(k), seq_major(v),
                                   causal=False, window=16))
    np.testing.assert_allclose(live, np.swapaxes(ref[0], 0, 1),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_grad_matches_oracle(rng_key):
    """flash_attention carries a custom VJP (backward = dense oracle VJP):
    grads through the padded kernel must match grads of attention_ref."""
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (1, 60, 4, 16))
    k = jax.random.normal(ks[1], (1, 60, 2, 16))
    v = jax.random.normal(ks[2], (1, 60, 2, 16))

    def f(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=False, block_q=128, block_k=128, interpret=True)))

    def g(q, k, v):
        return jnp.sum(jnp.sin(attention_ref(q, k, v, causal=False,
                                             window=None)))

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


# ---------------- psgf_mix ----------------


@pytest.mark.parametrize("D", [64, 1000, 4096, 539_000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_psgf_mix_vs_ref(D, dtype, rng_key):
    ks = jax.random.split(rng_key, 3)
    wg = jax.random.normal(ks[0], (D,)).astype(dtype)
    wl = jax.random.normal(ks[1], (D,)).astype(dtype)
    m = jax.random.uniform(ks[2], (D,)) < 0.3
    out, cnt = psgf_mix(wg, wl, m, interpret=True)
    ref, rcnt = psgf_mix_ref(wg, wl, m)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=1e-6)
    assert float(cnt) == float(rcnt)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 999), ratio=st.floats(0.0, 1.0))
def test_psgf_mix_properties(seed, ratio):
    """mask=1 -> global; mask=0 -> local; count == mask sum (eq. 4/6)."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    D = 2000
    wg = jax.random.normal(ks[0], (D,))
    wl = jax.random.normal(ks[1], (D,))
    m = jax.random.uniform(ks[2], (D,)) < ratio
    out, cnt = psgf_mix(wg, wl, m, interpret=True)
    out = np.asarray(out)
    mn = np.asarray(m)
    np.testing.assert_allclose(out[mn], np.asarray(wg)[mn], atol=1e-7)
    np.testing.assert_allclose(out[~mn], np.asarray(wl)[~mn], atol=1e-7)
    assert float(cnt) == mn.sum()


@pytest.mark.parametrize("K,D", [(1, 64), (4, 1000), (6, 4096), (3, 539_000)])
def test_psgf_mix_batch_vs_ref(K, D, rng_key):
    """Client-batched fused mix (the FL engine's downlink): bitwise mix, exact
    count summed over all clients."""
    ks = jax.random.split(rng_key, 3)
    wg = jax.random.normal(ks[0], (D,))
    wc = jax.random.normal(ks[1], (K, D))
    m = jax.random.uniform(ks[2], (K, D)) < 0.3
    out, cnt = psgf_mix_batch(wg, wc, m, interpret=True)
    ref, rcnt = psgf_mix_batch_ref(wg, wc, m)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert float(cnt) == float(rcnt) == np.asarray(m).sum()


def test_psgf_mix_batch_block_size_invariance(rng_key):
    ks = jax.random.split(rng_key, 3)
    wg = jax.random.normal(ks[0], (3000,))
    wc = jax.random.normal(ks[1], (3, 3000))
    m = jax.random.uniform(ks[2], (3, 3000)) < 0.5
    o1, c1 = psgf_mix_batch(wg, wc, m, block_rows=8, interpret=True)
    o2, c2 = psgf_mix_batch(wg, wc, m, block_rows=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert float(c1) == float(c2)


def test_pick_block_rows_alignment():
    """The block-rows fallback must stay (8, 128)-aligned: the old linear
    ``while rows % br: br -= 1`` scan could settle on a NON-multiple-of-8
    divisor (e.g. rows=296 -> br=148) or degrade toward scalar-row blocks
    with small caps. The picker returns the largest divisor of ``rows`` that
    is a multiple of 8 and <= block_rows (clamped up to 8)."""
    # rows = 8 * 37 (prime): old code picked 148 (296 % 148 == 0, 148 % 8 != 0)
    assert _pick_block_rows(296, 256) == 8
    # exact divisor available: use the cap itself
    assert _pick_block_rows(2048, 256) == 256
    # rows smaller than the cap: whole array in one block
    assert _pick_block_rows(64, 256) == 64
    # caps below 8 clamp up to the minimum aligned tile, never 1-row blocks
    assert _pick_block_rows(296, 1) == 8
    assert _pick_block_rows(2048, 7) == 8
    # largest aligned divisor under the cap, not just any divisor
    assert _pick_block_rows(8 * 12, 8 * 5) == 8 * 4
    for rows, cap in [(296, 256), (2048, 100), (4096, 256), (8 * 30, 64)]:
        br = _pick_block_rows(rows, cap)
        assert rows % br == 0 and br % 8 == 0 and br <= max(cap, 8)


# ---------------- ssm_scan ----------------

SSM_CASES = [
    (2, 64, 128, 16, jnp.float32),
    (1, 200, 300, 8, jnp.float32),
    (3, 128, 256, 16, jnp.bfloat16),
    (1, 37, 64, 4, jnp.float32),
]


@pytest.mark.parametrize("case", SSM_CASES)
def test_ssm_scan_vs_ref(case, rng_key):
    B, S, D, N, dtype = case
    ks = jax.random.split(rng_key, 5)
    x = jax.random.normal(ks[0], (B, S, D)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, D))).astype(dtype)
    Bm = jax.random.normal(ks[2], (B, S, N)).astype(dtype)
    Cm = jax.random.normal(ks[3], (B, S, N)).astype(dtype)
    A = -jnp.exp(0.1 * jax.random.normal(ks[4], (D, N)))
    y = ssm_scan(x, dt, Bm, Cm, A, chunk=32, d_block=128, interpret=True)
    yr = ssm_scan_ref(x, dt, Bm, Cm, A)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=tol, rtol=tol)


def test_ssm_scan_chunk_invariance(rng_key):
    ks = jax.random.split(rng_key, 5)
    B, S, D, N = 1, 96, 128, 8
    x = jax.random.normal(ks[0], (B, S, D))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, D)))
    Bm = jax.random.normal(ks[2], (B, S, N))
    Cm = jax.random.normal(ks[3], (B, S, N))
    A = -jnp.exp(0.1 * jax.random.normal(ks[4], (D, N)))
    y1 = ssm_scan(x, dt, Bm, Cm, A, chunk=16, d_block=64, interpret=True)
    y2 = ssm_scan(x, dt, Bm, Cm, A, chunk=96, d_block=128, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


def test_model_ssm_pallas_path_matches_xla(rng_key):
    """hymba's ssm_apply(impl='pallas') == impl='xla' (end-to-end wiring)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import layers as L
    from repro.models.spec import init_params as spec_init

    cfg = dataclasses.replace(get_config("hymba-1.5b").reduced(), dtype="float32")
    p = spec_init(L.ssm_spec(cfg), rng_key)
    x = 0.1 * jax.random.normal(rng_key, (2, 48, cfg.d_model))
    # the kernel's interpret=None default runs the interpreter on the CPU
    y_x = L.ssm_apply(p, x, cfg, impl="xla")
    y_k = L.ssm_apply(p, x, cfg, impl="pallas")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_x), atol=2e-4, rtol=2e-4)
