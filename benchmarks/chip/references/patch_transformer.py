"""Plain reference of the patch-transformer forecasters (LoGTST, PatchTST)
and of one PSGF federated job over them, in straightforward ``jax.numpy``.

It imports nothing of the system under test. The model follows the LoGTST
paper (arXiv 2309.01297, Fig. 3: RevIN -> patch tokens -> pre-norm blocks
whose token mixer is attention or identity -> flatten + linear head ->
RevIN denorm) and PatchTST (arXiv 2211.14730), with the departures the
configuration files list (tanh GELU, layer norm, no dropout). The federated
job follows PSGF-Fed (paper eqs. 4-6): each round selects half of the
clients, sends selected clients a random share mask of the global model and
the others a random forward mask, runs ``local_steps`` Adam steps on every
client, and averages the selected clients' masked uploads into the global
model. Random draws use JAX's threefry PRNG through one documented key chain
(per round: selection, share masks, forward masks, upload masks, local
steps), so the program and the reference see the same clients, masks and
minibatches and only the arithmetic differs.

Every matrix product is computed in float32 (``highest`` precision), the
precision the configurations state.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
REVIN_EPS = 1e-5


def num_tokens(c: dict) -> int:
    return (c["look_back"] - c["patch_len"]) // c["stride"] + 1


def param_specs(c: dict) -> dict:
    """``{path: (shape, init, fan_in)}`` as a nested dict; ``init`` is
    ``normal`` (std 1/sqrt(fan_in)), ``pos`` (std 0.02), ``zeros`` or
    ``ones``."""
    d, f, h = c["d_model"], c["d_ff"], c["num_heads"]
    hd = d // h
    n = num_tokens(c)

    def ln():
        return {"scale": ((d,), "ones", 0), "bias": ((d,), "zeros", 0)}

    blocks = {}
    for i, mixer in enumerate(c["mixers"]):
        b = {"ln1": ln(), "ln2": ln(),
             "mlp": {"w1": ((d, f), "normal", d), "b1": ((f,), "zeros", 0),
                     "w2": ((f, d), "normal", f), "b2": ((d,), "zeros", 0)}}
        if mixer == "attn":
            b["attn"] = {
                "wq": ((d, h, hd), "normal", d), "wk": ((d, h, hd), "normal", d),
                "wv": ((d, h, hd), "normal", d), "wo": ((h, hd, d), "normal", d),
                "bq": ((h, hd), "zeros", 0), "bk": ((h, hd), "zeros", 0),
                "bv": ((h, hd), "zeros", 0), "bo": ((d,), "zeros", 0)}
        elif mixer != "id":
            raise ValueError(f"unknown mixer {mixer!r}")
        blocks[f"b{i}"] = b
    return {
        "tokenize": {"w": ((c["patch_len"], d), "normal", c["patch_len"]),
                     "b": ((d,), "zeros", 0), "pos": ((n, d), "pos", 0)},
        "blocks": blocks,
        "detokenize": {"w": ((n * d, c["horizon"]), "normal", n * d),
                       "b": ((c["horizon"],), "zeros", 0)},
        "revin": {"affine_w": ((1,), "ones", 0), "affine_b": ((1,), "zeros", 0)},
    }


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def init_params(c: dict, key, copies: int = 1):
    """Random float32 weights from ``key``; ``copies > 1`` stacks that many
    independent sets on a leading axis. Jit it to make them on the device in
    one call."""
    specs = param_specs(c)
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (shape, init, fan_in), k in zip(leaves, keys):
        full = (copies,) + shape if copies > 1 else shape
        if init == "zeros":
            out.append(jnp.zeros(full, jnp.float32))
        elif init == "ones":
            out.append(jnp.ones(full, jnp.float32))
        elif init == "pos":
            out.append(0.02 * jax.random.normal(k, full, jnp.float32))
        else:
            out.append(jax.random.normal(k, full, jnp.float32)
                       / math.sqrt(fan_in))
    return jax.tree_util.tree_unflatten(treedef, out)


def param_count(c: dict) -> int:
    leaves = jax.tree_util.tree_leaves(param_specs(c), is_leaf=_is_spec)
    return sum(int(np.prod(s)) for s, _, _ in leaves)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _layer_norm(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _attention(p, x, heads, mm):
    """Bidirectional multi-head self-attention over the patch tokens."""
    hd = x.shape[-1] // heads
    q = mm("bnd,dhk->bhnk", x, p["wq"]) + p["bq"][:, None, :]
    k = mm("bnd,dhk->bhnk", x, p["wk"]) + p["bk"][:, None, :]
    v = mm("bnd,dhk->bhnk", x, p["wv"]) + p["bv"][:, None, :]
    s = mm("bhnk,bhmk->bhnm", q, k) / math.sqrt(hd)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    a = jnp.exp(s)
    a = a / jnp.sum(a, axis=-1, keepdims=True)
    o = mm("bhnm,bhmk->bhnk", a, v)
    return mm("bhnk,hkd->bnd", o, p["wo"]) + p["bo"]


def _gelu(x):
    """GELU, tanh form (the form the configurations state)."""
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x * x * x)))


def forward(c: dict, p, x):
    """x: (B, look_back) -> (B, horizon), in float32."""
    mm = partial(jnp.einsum, precision="highest")
    rv = p["revin"]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    std = jnp.sqrt(jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
                   + REVIN_EPS)
    xn = (x - mean) / std * rv["affine_w"] + rv["affine_b"]
    n = num_tokens(c)
    idx = (np.arange(n)[:, None] * c["stride"]
           + np.arange(c["patch_len"])[None, :])
    tok = mm("bnp,pd->bnd", xn[:, idx], p["tokenize"]["w"]) \
        + p["tokenize"]["b"] + p["tokenize"]["pos"]
    for i, mixer in enumerate(c["mixers"]):
        b = p["blocks"][f"b{i}"]
        h = _layer_norm(b["ln1"], tok)
        tok = tok + (_attention(b["attn"], h, c["num_heads"], mm)
                     if mixer == "attn" else h)
        h = _layer_norm(b["ln2"], tok)
        m = b["mlp"]
        u = _gelu(mm("bnd,df->bnf", h, m["w1"]) + m["b1"])
        tok = tok + mm("bnf,fd->bnd", u, m["w2"]) + m["b2"]
    pred = mm("bi,ih->bh", tok.reshape(x.shape[0], -1),
              p["detokenize"]["w"]) + p["detokenize"]["b"]
    return (pred - rv["affine_b"]) / rv["affine_w"] * std + mean




# ---------------------------------------------------------------------------
# one PSGF federated job
# ---------------------------------------------------------------------------


class Flat:
    """The flattening of a parameter tree into the model vector ``w`` of the
    paper: leaves in JAX's tree order, each raveled."""

    def __init__(self, params):
        leaves, self.treedef = jax.tree_util.tree_flatten(params)
        self.shapes = [l.shape for l in leaves]
        self.sizes = [int(np.prod(s)) for s in self.shapes]
        self.offsets = np.cumsum([0] + self.sizes)
        self.names = [jax.tree_util.keystr(p) for p, _ in
                      jax.tree_util.tree_flatten_with_path(params)[0]]

    def flatten(self, params):
        return jnp.concatenate([jnp.ravel(l)
                                for l in jax.tree_util.tree_leaves(params)])

    def unflatten(self, vec):
        leaves = [vec[o:o + s].reshape(sh) for o, s, sh in
                  zip(self.offsets[:-1], self.sizes, self.shapes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def leaf_norms(self, vec):
        v = np.asarray(vec, np.float64)
        return np.array([np.linalg.norm(v[o:o + s]) for o, s in
                         zip(self.offsets[:-1], self.sizes)])


def _windows(series, starts, width):
    return series[starts[:, None] + jnp.arange(width)[None, :]]


def _round(c, fl, flat, state, train, rk):
    """One PSGF round over all K clients. Returns the new state, the mean local loss, the cumulative communication count and the
    mean over clients of each client's first-step gradient."""
    K, D = state["wc"].shape
    L, H = c["look_back"], c["horizon"]
    n_win = train.shape[1] - (L + H) + 1
    k_sel, k_share, k_fwd, k_up, k_local = jax.random.split(rk, 5)
    n_sel = max(1, int(round(K * fl["select_ratio"])))
    sel = jnp.zeros((K,), bool).at[
        jax.random.permutation(k_sel, K)[:n_sel]].set(True)

    def masks(key, ratio):
        return jax.vmap(lambda k: jax.random.uniform(k, (D,)) < ratio)(
            jax.random.split(key, K))

    down = jnp.where(sel[:, None], masks(k_share, fl["share_ratio"]),
                     masks(k_fwd, fl["forward_ratio"]))
    wc = jnp.where(down, state["wg"][None, :], state["wc"])
    n_down = jnp.sum(down, dtype=jnp.int32).astype(jnp.float32)

    def loss_fn(w, x, y):
        pred = forward(c, flat.unflatten(w), x)
        return jnp.mean(jnp.square(pred - y))

    b1, b2 = fl["adam_b1"], fl["adam_b2"]

    def client(w, m, v, t, series, key):
        def step(carry, skey):
            w, m, v, t = carry
            win = _windows(series, jax.random.randint(
                skey, (fl["batch_size"],), 0, n_win), L + H)
            loss, g = jax.value_and_grad(loss_fn)(w, win[:, :L], win[:, L:])
            t = t + 1
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            w = w - fl["lr"] * mhat / (jnp.sqrt(vhat) + fl["adam_eps"])
            return (w, m, v, t), (loss, g)

        (w, m, v, t), (losses, grads) = jax.lax.scan(
            step, (w, m, v, t), jax.random.split(key, fl["local_steps"]))
        return w, m, v, t, jnp.mean(losses), grads[0]

    w, m, v, t, losses, g1 = jax.vmap(client)(
        wc, state["m"], state["v"], state["t"], train,
        jax.random.split(k_local, K))
    up = jnp.where(sel[:, None], masks(k_up, fl["share_ratio"]), False)
    upf = up.astype(jnp.float32)
    contrib = upf * w + (sel[:, None].astype(jnp.float32) - upf) \
        * state["wg"][None, :]
    wg = jnp.sum(contrib, axis=0) / n_sel
    n_up = jnp.sum(up, dtype=jnp.int32).astype(jnp.float32)
    new = {"wg": wg, "wc": w, "m": m, "v": v, "t": t,
           "comm_down": state["comm_down"] + n_down,
           "comm_up": state["comm_up"] + n_up}
    return new, jnp.mean(losses), new["comm_down"] + new["comm_up"], \
        jnp.mean(g1, axis=0)


def _rmse(c, flat, wg, test):
    L, H = c["look_back"], c["horizon"]
    n = test.shape[1] - (L + H) + 1
    K = test.shape[0]
    win = test[:, np.arange(n)[:, None] + np.arange(L + H)[None, :]]
    win = win.reshape(K * n, L + H)
    pred = forward(c, flat.unflatten(wg), win[:, :L])
    err = pred - win[:, L:]
    return jnp.sqrt(jnp.mean(jnp.square(err)))


def fl_job(c: dict, fl: dict, params, train, test, key, rounds: int,
           eval_every: int):
    """Run ``rounds`` PSGF rounds from ``params`` with the run key ``key``.

    Returns per-round mean local losses and cumulative communication counts,
    the RMSE of the global model over all test windows after every
    ``eval_every`` rounds, the initial and final global vectors, the
    per-leaf norms of the first round's mean first-step gradient, and the
    leaf names."""
    flat = Flat(params)
    w0 = flat.flatten(params)
    w0_host = np.asarray(w0, np.float32)
    K = train.shape[0]
    train = jnp.asarray(train, jnp.float32)
    test = jnp.asarray(test, jnp.float32)
    state = {"wg": jnp.array(w0), "wc": jnp.tile(w0[None], (K, 1)),
             "m": jnp.zeros((K, w0.size)), "v": jnp.zeros((K, w0.size)),
             "t": jnp.zeros((K,), jnp.int32),
             "comm_down": jnp.zeros((), jnp.float32),
             "comm_up": jnp.zeros((), jnp.float32)}
    round_fn = jax.jit(partial(_round, c, fl, flat), donate_argnums=(0,))
    rmse_fn = jax.jit(partial(_rmse, c, flat))
    key, _ = jax.random.split(key)     # the run's init key, unused here
    losses, comms, rmses, g1 = [], [], [], None
    for r in range(rounds):
        key, rk = jax.random.split(key)
        state, loss, comm, g = round_fn(state, train, rk)
        losses.append(loss)
        comms.append(comm)
        if r == 0:
            g1 = np.asarray(g)
        if (r + 1) % eval_every == 0:
            rmses.append(rmse_fn(state["wg"], test))
    return {"loss": np.asarray(jnp.stack(losses), np.float64),
            "comm": np.asarray(jnp.stack(comms), np.float64),
            "rmse": np.asarray([float(x) for x in rmses], np.float64),
            "w0": w0_host,
            "w": np.asarray(state["wg"]),
            "g1_leaf_norms": flat.leaf_norms(g1),
            "leaf_names": flat.names,
            "leaf_offsets": flat.offsets}
