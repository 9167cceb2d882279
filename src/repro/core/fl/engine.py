"""Unified federated-learning engine: ONE gate/aggregate/distribute core for
every partial-sharing policy, plus a compiled multi-round driver.

The paper's algorithm family (Online-Fed / PSO-Fed / PSGF-Fed, eqs. 3-6) and
its datacenter mapping (repro/core/psgf_dp.py) used to be two separate
implementations. Here both are expressed through a :class:`~repro.core.fl.
policies.Policy` (downlink gates / uplink gates / train-set selection) driving
three primitives that work on any client-stacked pytree:

  * :func:`mix_down`   — clients receive ``gate * global + (1-gate) * local``
                         (eqs. 3/4/6, one lerp per leaf);
  * :func:`aggregate`  — the server folds gated client contributions into the
                         global model (eqs. 3/5), ``sum_k(up_k * w_k +
                         (sel_k - up_k) * g) / C``;
  * :func:`gate_count` / :func:`gate_bytes` — exact communication accounting
                         from the realized gates.

Round driving is compiled at three escalating levels (``run_fl(driver=...)``):
``"loop"`` dispatches one round at a time (legacy A/B baseline); ``"scan"``
compiles ``eval_every`` rounds per dispatch with a donated carry and host-syncs
(convergence / patience / RMSE eval) at chunk boundaries; ``"while"`` moves the
convergence check itself on-device — a ``lax.while_loop`` over scan chunks
carrying ``(best_loss, stall, stop)`` — so a full ``max_rounds`` run is ONE
dispatch with zero per-chunk host round-trips (per-round losses, cumulative
comm and per-chunk RMSE land in preallocated device buffers read back once at
the end). All three drivers run identical per-round math: same seed -> same
per-round states (bitwise on the pinned CPU toolchain).

Client state is a ``(K, D)`` matrix (plus Adam moments); ``FLConfig.
client_chunk`` bounds how many clients are materialized per LocalUpdate step
(chunked vmap via ``lax.map(batch_size=...)``) so ``num_clients=512+`` runs on
a single host, and :func:`shard_client_state` / :func:`client_state_shardings`
lay the client axis out across local devices — the while driver threads those
shardings through ``in_shardings`` on its donated carry so the one-dispatch run
stays client-sharded end-to-end. ``FLConfig.use_pallas_mix`` routes the
element-granularity downlink mix through the fused ``psgf_mix`` Pallas kernel
(mix + comm count in one pass over the mask; compiled on the chip, run in the
interpreter only on the CPU backend).
``FLConfig.streaming_windows`` drops the materialized ``(K, n_win, L+T)``
window tensors entirely: every driver carries only the raw ``(K, T)`` split
slices and gathers minibatch/eval windows ON DEVICE inside the compiled loop
(bit-identical states under the same RNG, ~``(L+T)``x less training-data
memory and H2D traffic — the 512-client ceiling moves from transfer to
compute).

``FLConfig.participation`` caps how many clients take part in any one round:
each round derives a fresh cohort of ``S`` client indices from the round key
(:func:`sample_cohort` — a fixed-size slice of a key-seeded permutation, so
shapes stay static), gathers the cohort's rows out of the ``(K, D)`` store,
runs the full gate/LocalUpdate/aggregate cycle on the cohort only, and
scatters the updated rows back. Non-participants exchange NOTHING that round
(eqs. 3-6 with ``sel_k = 0``): comm counters accrue only the cohort's gates,
so the accounting stays exact while per-round compute, uplink bytes and live
activations drop ~``K/S``. A sampled round is bit-identical to a full round
run on the gathered cohort (guarded in tests/test_participation.py), and
``participation=K`` (or ``None``) takes the exact unsampled code path — per-
round states reproduce the unsampled engine bitwise. For ``K`` too large to
keep client state device-resident at all, ``run_fl(driver="host")`` moves the
``(K, D)`` store into host memory (``repro.core.fl.client_store``) and
transfers only the sampled cohort per round.

Entry points:
  * :func:`fl_round` — one global iteration (flat client space);
  * :func:`run_fl`   — multi-round driver (``driver="scan"`` is the compiled
                       default; ``driver="while"`` is the fully-compiled
                       on-device early-stop variant; ``driver="loop"`` keeps
                       the legacy per-round Python loop for A/B benchmarking;
                       ``driver="host"`` is the host-resident client-store
                       path for six-figure ``num_clients``);
  * :func:`sync_round` — the train-free gate/aggregate/distribute cycle used
                       by ``psgf_dp.psgf_sync`` at leaf granularity.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial, wraps
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.common.pytree_utils import tree_flatten_to_vector, tree_unflatten_from_vector
from repro.core import forecast
from repro.core.fl import masks as M
from repro.core.fl import policies as pol

# One accounting dtype for every communication counter (comm_down / comm_up /
# wire_bytes): counters reach ~1e12 for paper-scale runs, well inside float32's
# exact-integer range only up to 2^24 — but these are *accumulated float sums*
# of mask densities, where float32's relative error is what matters (and is
# plenty). Unifying the dtype keeps scan carries stable and avoids the seed's
# conditional float64 leak.
ACCOUNTING_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class FLConfig:
    policy: str = "psgf"           # online | pso | psgf | psgf_topk
    num_clients: int = 58
    select_ratio: float = 0.5      # paper: 50% for all methods
    share_ratio: float = 0.3       # PSO/PSGF S-mask density (paper col. 2)
    forward_ratio: float = 0.2     # PSGF F-mask density (PSGF-Fed-20%/30%)
    local_steps: int = 4
    batch_size: int = 32
    lr: float = 1e-3               # Adam, paper setting
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    adam_eps: float = 1e-8
    # ---- beyond-paper knobs -------------------------------------------------
    # psgf_topk: replace RANDOM S/F masks with magnitude-based ones — share the
    # share_ratio*D parameters where |w_global - w_client| is largest (server
    # ranks against its stale copy of each client's last upload).
    # comm_bits: payload precision on the wire (32 = paper; 16 = bf16-style
    # quantized exchange; 8 = int8 + one fp32 scale per param leaf, symmetric
    # absmax — mirrors checkpoint.quantize_tree(bits=8)). Counted in
    # metrics["comm_bytes"]; at 8 bits the per-payload scale headers are real
    # wire overhead and accrue in the state's "comm_scales" counter.
    comm_bits: int = 32
    # client_chunk: upper bound on clients materialized per LocalUpdate step
    # AND per evaluate_rmse forward. None = plain vmap over all K clients
    # (fine to ~100 clients); set to e.g. 64 to run num_clients=512+ without
    # K-way replication of activations.
    client_chunk: Optional[int] = None
    # use_pallas_mix: route the element-granularity (K, D) downlink mix through
    # the fused psgf_mix Pallas kernel (mix + comm count in ONE pass over the
    # mask instead of separate mix_down + gate_count reductions). Compiled on
    # the chip; the CPU backend runs it in the Pallas interpreter (the only
    # place interpret mode is allowed). Bit-identical to the jnp path.
    use_pallas_mix: bool = False
    # streaming_windows: train and evaluate straight off RAW (K, T) series
    # slices (repro.data.windowing.client_series_datasets) instead of the
    # materialized (K, n_win, L+T) window tensor. LocalUpdate turns its
    # minibatch index draw into a start-index draw and gathers (batch, L+T)
    # windows from each client's raw row ON DEVICE inside the compiled round
    # loop; the eval path gathers test windows the same way. Same RNG, same
    # values -> bit-identical per-round states and RMSE to the materialized
    # layout, at ~(L+T)x less training-data device memory and H2D traffic.
    streaming_windows: bool = False
    # participation: per-round client subsampling. None = every client takes
    # part every round (the paper's setting and the engine's historical
    # behavior). An int S >= 1 is an absolute per-round cohort size; a float
    # in (0, 1] is a fraction of num_clients (resolved by
    # participation_size()). Each round samples a fresh size-S cohort from the
    # round key, runs gating/LocalUpdate/aggregation on the cohort ONLY and
    # scatters the updated rows back into the (K, D) store — comm counters
    # accrue only the sampled clients' gates (non-participants exchange
    # nothing: eqs. 3-6 with sel_k = 0). participation == num_clients (and
    # None) takes the exact unsampled code path: per-round states are
    # BIT-IDENTICAL to the engine without this knob.
    participation: Optional[float] = None

    def participation_size(self) -> int:
        """The resolved per-round cohort size S: ``participation`` as an
        absolute count, as a fraction of ``num_clients`` (``max(1,
        round(K * fraction))``), or ``num_clients`` when ``None``."""
        if self.participation is None:
            return self.num_clients
        if isinstance(self.participation, float):
            return max(1, int(round(self.num_clients * self.participation)))
        return int(self.participation)

    def __post_init__(self):
        # Cross-field validation: fail loudly at config time instead of as an
        # opaque shape/tracer error deep inside lax.map or the scatter.
        if self.comm_bits not in (8, 16, 32):
            raise ValueError(
                f"FLConfig.comm_bits: unsupported payload width: "
                f"{self.comm_bits} bits (choose 8, 16 or 32)")
        if self.client_chunk is not None and self.client_chunk <= 0:
            raise ValueError(
                f"client_chunk must be a positive client count or None, got "
                f"{self.client_chunk}")
        if self.participation is None:
            return
        p = self.participation
        ok_int = (isinstance(p, (int, np.integer))
                  and not isinstance(p, bool)
                  and 1 <= p <= self.num_clients)
        ok_frac = (isinstance(p, float) and 0.0 < p <= 1.0)
        if not (ok_int or ok_frac):
            raise ValueError(
                f"participation must be an int cohort size in [1, "
                f"num_clients={self.num_clients}] or a float fraction in "
                f"(0, 1], got {p!r}")
        S = self.participation_size()
        if self.client_chunk is not None and self.client_chunk > S:
            raise ValueError(
                f"client_chunk={self.client_chunk} exceeds the per-round "
                f"cohort size {S} (participation={p!r}): LocalUpdate only ever "
                f"sees the cohort, so the chunk can never fill — lower "
                f"client_chunk to <= {S} or raise participation")


# ---------------------------------------------------------------------------
# gate/aggregate/distribute core (granularity-agnostic)
# ---------------------------------------------------------------------------


def mix_down(client_tree, global_tree, gates):
    """Clients receive ``gate * global + (1 - gate) * local`` (eqs. 3/4/6).

    ``client_tree`` leaves are ``(K, *s)``; ``global_tree`` leaves ``(*s)``;
    ``gates`` leaves broadcast against the client leaves ((K, *s) at element
    granularity, (K, 1, ..., 1) at leaf granularity).
    """
    return jax.tree_util.tree_map(
        lambda l, g, m: m * g[None] + (1.0 - m) * l,
        client_tree, global_tree, gates,
    )


def aggregate(client_tree, global_tree, up_gates, selected):
    """Server update (eqs. 3/5): gated mean over the selected clients.

    Per leaf: ``sum_k(up_k * w_k + (sel_k - up_k) * g) / C`` — parameters a
    selected client does NOT share contribute the server's own value, so the
    mean stays well-normalized at any gate density. With scalar per-leaf
    gates this reduces to psgf_dp's ``gs * mean_sel + (1 - gs) * g``.

    When NO client is selected (reachable through the public API with external
    masks) the global model is preserved as-is: every contribution is zero, so
    dividing by the clamped ``C = 1`` would silently collapse the model toward
    zero.
    """
    num_sel = jnp.sum(selected)
    C = jnp.maximum(num_sel, 1).astype(jnp.float32)

    def per_leaf(l, g, m):
        sel = selected.reshape((selected.shape[0],) + (1,) * (l.ndim - 1))
        contrib = m * l + (sel.astype(jnp.float32) - m) * g[None]
        return jnp.where(num_sel > 0, jnp.sum(contrib, axis=0) / C, g)

    return jax.tree_util.tree_map(per_leaf, client_tree, global_tree, up_gates)


def _gate_scale(gate_leaf, client_leaf) -> int:
    """Elements of a client leaf covered by ONE gate entry (1 at element
    granularity, leaf_size at leaf granularity)."""
    g = max(int(np.prod(gate_leaf.shape[1:], dtype=np.int64)), 1)
    return int(np.prod(client_leaf.shape[1:], dtype=np.int64)) // g


def gate_count(gates, client_tree):
    """Number of parameters crossing the wire given realized gates."""
    total = jnp.zeros((), ACCOUNTING_DTYPE)
    for g, l in zip(jax.tree_util.tree_leaves(gates),
                    jax.tree_util.tree_leaves(client_tree)):
        s = jnp.sum(g, dtype=ACCOUNTING_DTYPE)
        scale = _gate_scale(g, l)
        total = total + (s if scale == 1 else s * scale)
    return total


def _payload_clients(gate_leaf):
    """Per-client 0/1 indicator of "this client exchanges >= 1 element of
    this leaf" — the clients that pull/push a wire payload for it."""
    flat = gate_leaf.reshape(gate_leaf.shape[0], -1)
    return jnp.any(flat != 0, axis=1)


def wire_scale_count(gates):
    """Number of per-payload scale headers an int8 wire carries for the
    realized ``gates``: one fp32 scale per (client, gated leaf) payload —
    a client exchanging any element of a leaf ships that leaf's scale."""
    total = jnp.zeros((), ACCOUNTING_DTYPE)
    for g in jax.tree_util.tree_leaves(gates):
        total = total + jnp.sum(_payload_clients(g).astype(ACCOUNTING_DTYPE))
    return total


def gate_bytes(gates, client_tree, comm_bits: Optional[int] = None):
    """Bytes crossing the wire given realized gates.

    Default (``comm_bits=None``): each client leaf's dtype itemsize — the
    materialized-state view (a float32 leaf is a 32-bit wire). With
    ``comm_bits``, the WIRE payload width instead; at ``comm_bits=8`` the
    per-payload fp32 scale headers (:func:`wire_scale_count` — one per
    (client, leaf) payload actually exchanged) are real bytes on the wire
    and are counted on top of the int8 elements. A uniform ``comm_bits / 8``
    per element is NOT the whole story below 16 bits.
    """
    total = jnp.zeros((), ACCOUNTING_DTYPE)
    for g, l in zip(jax.tree_util.tree_leaves(gates),
                    jax.tree_util.tree_leaves(client_tree)):
        width = (jnp.dtype(l.dtype).itemsize if comm_bits is None
                 else comm_bits / 8.0)
        per_gate = _gate_scale(g, l) * width
        total = total + jnp.sum(g, dtype=ACCOUNTING_DTYPE) * per_gate
    if comm_bits == 8:
        total = total + wire_scale_count(gates) * 4.0
    return total


def quantize_wire_vec(vec, meta, comm_bits: int, key=None):
    """Wire round-trip of ONE flat ``(D,)`` param payload at ``comm_bits``:
    what the receiver reconstructs. ``16`` is the bf16 round-trip; ``8``
    unflattens through ``meta`` and round-trips every param leaf through
    ``checkpoint.quantize_tree(bits=8)`` (int8 + per-leaf fp32 absmax
    scale), so training-side wire math and serving-side restore
    (``load_forecaster(comm_bits=8)``) reconstruct identically.

    ``key`` (int8 only) selects stochastic rounding — the round hot path
    passes a per-round key so the training-time quantizer is unbiased;
    ``None`` is the deterministic round-to-nearest that restore uses."""
    if comm_bits == 32:
        return vec
    if comm_bits == 16:
        return vec.astype(jnp.bfloat16).astype(jnp.float32)
    from repro.checkpoint import quantize_tree

    tree = tree_unflatten_from_vector(vec, meta)
    out, _ = tree_flatten_to_vector(
        quantize_tree(tree, comm_bits, where="FLConfig.comm_bits", key=key))
    return out


def mix_down_count(client_tree, global_tree, gates, *, use_pallas: bool = False):
    """Fused downlink: returns ``(mix_down(...), gate_count(...))``.

    ``use_pallas=True`` runs the fused ``psgf_mix`` Pallas kernel, which
    produces the mixed matrix and the comm count in a single pass over the
    mask (the separate ``gate_count`` reduction re-reads the whole mask
    otherwise). The kernel takes the element-granularity path only — ONE
    float32 ``(K, D)`` client leaf with dense ``(K, D)`` gates — and any other
    tree raises instead of quietly taking the jnp path. Gate sums are 0/1
    integers, so the fused count is bit-identical to ``gate_count`` while the
    per-round total stays inside float32's exact-integer range (2^24 ~ 1.6e7
    gated params/round); beyond that both paths carry ACCOUNTING_DTYPE's
    relative error, in possibly different rounding orders (see the accounting
    note at the top of this module). The mix math is the same lerp either way.
    """
    if not use_pallas:
        return (mix_down(client_tree, global_tree, gates),
                gate_count(gates, client_tree))
    cl = jax.tree_util.tree_leaves(client_tree)
    gl = jax.tree_util.tree_leaves(global_tree)
    gt = jax.tree_util.tree_leaves(gates)
    if not (len(cl) == 1 and len(gl) == 1 and len(gt) == 1
            and cl[0].ndim == 2 and gl[0].ndim == 1
            and gt[0].shape == cl[0].shape and cl[0].dtype == jnp.float32):
        raise ValueError(
            "use_pallas=True needs one float32 (K, D) client leaf with dense "
            f"(K, D) gates; got client leaves "
            f"{[(l.shape, str(l.dtype)) for l in cl]} and gate leaves "
            f"{[g.shape for g in gt]}")
    from jax.sharding import PartitionSpec as P

    from repro.kernels.psgf_mix.ops import psgf_mix_batch

    mesh = jax.sharding.get_abstract_mesh()
    if "clients" in mesh.axis_names:
        # a Pallas kernel cannot be partitioned by the compiler: under
        # run_fl's client mesh each device mixes its own rows and the counts
        # are summed (exact: integer-valued partial sums)
        rows = (P("clients") if cl[0].shape[0] % mesh.shape["clients"] == 0
                else P())

        def local(g, w, m):
            mixed, count = psgf_mix_batch(g, w, m)
            return mixed, (jax.lax.psum(count, "clients") if rows != P()
                           else count)

        mixed, count = jax.shard_map(
            local, mesh=mesh, in_specs=(P(), rows, rows),
            out_specs=(rows, P()), check_vma=False)(gl[0], cl[0], gt[0])
    else:
        mixed, count = psgf_mix_batch(gl[0], cl[0], gt[0])
    structure = jax.tree_util.tree_structure(client_tree)
    return (jax.tree_util.tree_unflatten(structure, [mixed]),
            count.astype(ACCOUNTING_DTYPE))


def sync_round(local, global_, key, policy, select_ratio: float):
    """Train-free gate/aggregate/distribute cycle over client-stacked pytrees.

    The traced path of ``psgf_dp.psgf_sync`` expressed through the engine:
    select clients -> uplink-aggregate into the global model -> downlink-mix
    the fresh global back into every client. Returns
    ``(new_local, new_global, stats)`` with exact wire-byte accounting.
    """
    num_clients = jax.tree_util.tree_leaves(local)[0].shape[0]
    k_sel, k_share, k_fwd = jax.random.split(key, 3)
    selected = M.select_clients(k_sel, num_clients, select_ratio)

    down = policy.downlink_gates((k_share, k_fwd), global_, local, selected)
    # k_share (not a fresh key) ties the uplink S-masks to the downlink ones:
    # the same leaf subset is aggregated and written back within one sync.
    up = policy.uplink_gates(k_share, global_, local, selected)

    new_global = aggregate(local, global_, up, selected)
    new_local = mix_down(local, new_global, down)
    stats = {
        "wire_bytes": gate_bytes(down, local) + gate_bytes(up, local),
        "num_selected": jnp.sum(selected),
    }
    return new_local, new_global, stats


# ---------------------------------------------------------------------------
# flat client space: state init + LocalUpdate
# ---------------------------------------------------------------------------


def init_fl_state(model_cfg: forecast.ForecastConfig, fl_cfg: FLConfig, key,
                  init_params=None):
    """State: global vector, per-client vectors + per-client Adam moments.

    ``init_params`` WARM-STARTS the run from an existing param pytree (the
    flywheel's retrain path fine-tunes the serving checkpoint instead of
    re-learning from scratch); optimizer moments still start at zero."""
    params = (forecast.init_params(model_cfg, key) if init_params is None
              else init_params)
    vec, meta = tree_flatten_to_vector(params)
    K = fl_cfg.num_clients
    state = {
        "w_global": vec,
        "w_clients": jnp.tile(vec[None, :], (K, 1)),
        "adam_m": jnp.zeros((K, vec.shape[0])),
        "adam_v": jnp.zeros((K, vec.shape[0])),
        "adam_t": jnp.zeros((K,), jnp.int32),
        "round": jnp.zeros((), jnp.int32),
        "comm_down": jnp.zeros((), ACCOUNTING_DTYPE),
        "comm_up": jnp.zeros((), ACCOUNTING_DTYPE),
    }
    if fl_cfg.comm_bits == 8:
        # int8 wire: count per-payload fp32 scale headers too. Added ONLY at
        # 8 bits so the carry structure of every existing config is
        # unchanged (comm_bits is jit-static, so the structure stays static
        # per config).
        state["comm_scales"] = jnp.zeros((), ACCOUNTING_DTYPE)
    return state, meta


def _scoped(name: str):
    """Trace the decorated function under ``jax.named_scope(name)``: the
    name lands in each of its operations' metadata (the profiler's
    ``tf_op``), so a device trace can split a compiled round into its
    stages. A fresh scope per call, since one scope object is not safe to
    enter from two threads at once."""
    def deco(f):
        @wraps(f)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return f(*args, **kwargs)
        return scoped
    return deco


def _all_windows(series, width: int):
    """Every stride-1 ``width``-long window of ``series`` along its last axis:
    ``(..., n, width)`` with ``n = series.shape[-1] - width + 1``, window
    ``i`` == ``series[..., i : i + width]``. Built from ``width`` static
    slices (column ``j`` is ``series[..., j : j + n]``), so it is pure data
    movement, with no gather at all."""
    n = series.shape[-1] - width + 1
    return jnp.stack([series[..., j:j + n] for j in range(width)], axis=-1)


def _windows(row, starts, width: int):
    """The ``width``-long windows of the 1-D ``row`` that begin at ``starts``:
    ``(len(starts), width)``, row ``k`` == ``row[starts[k] : starts[k] +
    width]``. One row gather over :func:`_all_windows` fetches each window as
    one contiguous slice. Indexing the row itself instead, scalar by scalar
    (``row[starts[:, None] + arange(width)]``) or window by window
    (``lax.dynamic_slice`` under ``vmap``), compiles for the TPU to a gather
    of single elements or to a loop over the windows: on a TPU v5e, at the
    paper's 58 clients and batch 32 over four steps, ~10 ms against ~0.7 ms
    for building every window and taking rows."""
    return _all_windows(row, width)[starts]


def _local_update(model_cfg, fl_cfg, meta, w, m, v, t, data, key):
    """Per-client LocalUpdate: ``local_steps`` Adam steps on minibatches.

    data: ONE client's ``(n_win, L+T)`` materialized windows, or its raw
    ``(T,)`` series slice under ``streaming_windows`` — the minibatch draw is
    then a START-INDEX draw and the ``(batch, L+T)`` windows are taken as
    whole rows of the raw row's window view (:func:`_windows`).
    Window ``i`` of the raw slice is ``data[i : i + L+T]`` == materialized
    row ``i``, and the index draw uses the same bounds, so both layouts see
    bit-identical minibatches under the same RNG. Operates on the flat
    vector.
    """
    Lb = model_cfg.look_back
    streaming = data.ndim == 1
    n_win = data.shape[0] - (Lb + model_cfg.horizon) + 1 if streaming \
        else data.shape[0]

    def loss_vec(wv, x, y):
        params = tree_unflatten_from_vector(wv, meta)
        return forecast.mse_loss(model_cfg, params, x, y)

    def step(carry, skey):
        w, m, v, t = carry
        with jax.named_scope("fl.window_gather"):
            idx = jax.random.randint(skey, (fl_cfg.batch_size,), 0, n_win)
            if streaming:
                batch = _windows(data, idx, Lb + model_cfg.horizon)
            else:
                batch = data[idx]
        x, y = batch[:, :Lb], batch[:, Lb:]
        loss, g = jax.value_and_grad(loss_vec)(w, x, y)
        with jax.named_scope("fl.adam"):
            t = t + 1
            m = fl_cfg.adam_b1 * m + (1 - fl_cfg.adam_b1) * g
            v = fl_cfg.adam_b2 * v + (1 - fl_cfg.adam_b2) * jnp.square(g)
            mhat = m / (1 - fl_cfg.adam_b1 ** t)
            vhat = v / (1 - fl_cfg.adam_b2 ** t)
            w = w - fl_cfg.lr * mhat / (jnp.sqrt(vhat) + fl_cfg.adam_eps)
        return (w, m, v, t), loss

    keys = jax.random.split(key, fl_cfg.local_steps)
    (w, m, v, t), losses = jax.lax.scan(step, (w, m, v, t), keys)
    return w, m, v, t, jnp.mean(losses)


@_scoped("fl.local_update")
def _local_update_all(model_cfg, fl_cfg, meta, w, m, v, t, data, keys):
    """LocalUpdate across all K clients: plain vmap, or chunked vmap via
    ``lax.map(batch_size=client_chunk)`` so only ``client_chunk`` clients'
    activations are live at once (the (K, D) state itself stays resident —
    it is O(K*D), the activations are what explode with K). ``data`` is the
    client-stacked minibatch source in either layout — ``(K, n_win, L+T)``
    materialized or ``(K, T)`` raw (``streaming_windows``); both map over
    axis 0."""
    K = w.shape[0]
    xs = (w, m, v, t, data, keys)
    f = lambda w_, m_, v_, t_, d_, k_: _local_update(
        model_cfg, fl_cfg, meta, w_, m_, v_, t_, d_, k_)
    if fl_cfg.client_chunk is not None and fl_cfg.client_chunk < K:
        return jax.lax.map(lambda a: f(*a), xs, batch_size=fl_cfg.client_chunk)
    return jax.vmap(f)(*xs)


# ---------------------------------------------------------------------------
# one round (flat client space)
# ---------------------------------------------------------------------------


def sample_cohort(key, num_clients: int, size: int):
    """The per-round participant cohort: the first ``size`` entries of a
    key-seeded permutation of ``arange(num_clients)``. Fixed-size (static
    shapes inside the compiled drivers) and without replacement, so the
    cohort gather never duplicates a client and comm accounting stays exact.
    Every driver — loop/scan/while on-device, the host-store driver on host —
    derives cohorts through this one function, so the same seed yields the
    same cohort sequence everywhere."""
    return jax.random.permutation(key, num_clients)[:size]


@_scoped("fl.round_down")
def _round_down(state, key, fl_cfg, meta, policy):
    """Stage 1/3 of a round: client selection, downlink gates, wire payload
    and the downlink mix — everything :func:`_round_body` computes BEFORE
    LocalUpdate. Split out so the multi-process host driver
    (``repro.core.fl.client_store``) can run it replicated on every process
    while sharding only the LocalUpdate stage; composed inline by
    :func:`_round_body`, so single- and multi-process rounds share one
    definition of the math (staged == fused bitwise on the pinned CPU
    toolchain, guarded in tests/test_distributed.py)."""
    K = state["w_clients"].shape[0]
    k_sel, k_smask, k_fmask, k_upmask, k_local = jax.random.split(key, 5)

    selected = M.select_clients(k_sel, K, fl_cfg.select_ratio)  # (K,)

    # ---- downlink: policy builds per-client receive gates ------------------
    gates = policy.downlink_gates(
        (k_smask, k_fmask), state["w_global"], state["w_clients"], selected)

    down = {"selected": selected, "gates": gates,
            "k_upmask": k_upmask, "k_local": k_local}
    if fl_cfg.comm_bits == 8:
        # int8 + per-leaf scale downlink payload: the server quantizes ONE
        # w_global payload; every receiver dequantizes the same ints+scales.
        # Stochastic rounding (fresh key per round, folded off the round key
        # without disturbing the split chain): nearest-rounding is biased and
        # stalls training once updates drop below half a quantization step.
        k_wire = jax.random.fold_in(key, 8)
        down["k_wire"] = k_wire
        w_wire = quantize_wire_vec(state["w_global"], meta, 8,
                                   key=jax.random.fold_in(k_wire, 0))
    elif fl_cfg.comm_bits < 32:
        # quantized downlink payload (beyond-paper): bf16-style round-trip
        w_wire = state["w_global"].astype(jnp.bfloat16).astype(jnp.float32)
    else:
        w_wire = state["w_global"]

    use_pallas = (fl_cfg.use_pallas_mix
                  and getattr(policy, "granularity", "element") == "element")
    w_mixed, n_down = mix_down_count(state["w_clients"], w_wire, gates,
                                     use_pallas=use_pallas)
    down["w_mixed"] = w_mixed
    down["comm_down"] = state["comm_down"] + n_down
    return down


@_scoped("fl.round_up")
def _round_up(state, down, upd, fl_cfg, meta, policy):
    """Stage 3/3 of a round: fold the LocalUpdate results back into the
    client rows, uplink gates + wire quantization, aggregation and comm
    accounting. ``down`` is :func:`_round_down`'s output; ``upd`` the
    ``(w_new, m_new, v_new, t_new, losses)`` tuple from
    :func:`_local_update_all` (possibly reassembled from per-process
    blocks)."""
    K = state["w_clients"].shape[0]
    selected = down["selected"]
    w_mixed = down["w_mixed"]
    comm_down = down["comm_down"]
    trains = policy.train_mask(selected)
    w_new, m_new, v_new, t_new, losses = upd

    tr = trains[:, None].astype(jnp.float32)
    w_clients = tr * w_new + (1 - tr) * w_mixed
    adam_m = tr * m_new + (1 - tr) * state["adam_m"]
    adam_v = tr * v_new + (1 - tr) * state["adam_v"]
    adam_t = jnp.where(trains, t_new, state["adam_t"])

    # ---- uplink + aggregation (eq. 5; eq. 3 when S' == I) ------------------
    up_masks = policy.uplink_gates(down["k_upmask"], state["w_global"],
                                   w_clients, selected)

    if fl_cfg.comm_bits == 8:
        # each uploader quantizes its OWN row (per-client per-leaf scales)
        # under its own stochastic-rounding key
        k_wire = down["k_wire"]
        w_clients_wire = jax.vmap(
            lambda i, row: quantize_wire_vec(
                row, meta, 8, key=jax.random.fold_in(k_wire, 1 + i))
        )(jnp.arange(K), w_clients)
    elif fl_cfg.comm_bits < 32:
        w_clients_wire = w_clients.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        w_clients_wire = w_clients

    w_global = aggregate(w_clients_wire, state["w_global"], up_masks, selected)
    comm_up = state["comm_up"] + gate_count(up_masks, w_clients)

    new_state = {
        "w_global": w_global,
        "w_clients": w_clients,
        "adam_m": adam_m,
        "adam_v": adam_v,
        "adam_t": adam_t,
        "round": state["round"] + 1,
        "comm_down": comm_down,
        "comm_up": comm_up,
    }
    metrics = {
        "train_loss": jnp.sum(losses * trains) / jnp.maximum(jnp.sum(trains), 1),
        "num_selected": jnp.sum(selected),
        "comm_total": comm_down + comm_up,
        "comm_bytes": (comm_down + comm_up) * (fl_cfg.comm_bits / 8.0),
    }
    if fl_cfg.comm_bits == 8:
        # scale headers: every (client, param leaf) payload actually
        # exchanged ships one fp32 scale — len(meta.sizes) leaves per flat
        # payload, for each client with any gated element that direction.
        n_leaves = float(len(meta.sizes))
        scales = (state["comm_scales"]
                  + n_leaves * wire_scale_count(down["gates"])
                  + n_leaves * wire_scale_count(up_masks))
        new_state["comm_scales"] = scales
        metrics["comm_scales"] = scales
        metrics["comm_bytes"] = metrics["comm_bytes"] + scales * 4.0
    return new_state, metrics


def _round_body(state, data, key, model_cfg, fl_cfg, meta, policy):
    """One global FL iteration over the clients present in ``state`` — the
    full fleet, or a gathered cohort under participation sampling (the client
    count comes from the state's leading axis, NOT ``fl_cfg.num_clients``).
    data: (K, n_win, L+T) materialized windows or (K, T) raw series
    (``streaming_windows``) — see :func:`_local_update`.

    Composed from :func:`_round_down` (selection/gates/mix), the vmapped
    :func:`_local_update_all`, and :func:`_round_up` (merge/uplink/
    aggregate) — pure function composition, so this traces to the exact
    jaxpr the pre-split body produced. The multi-process host driver runs
    the same three stages as separate dispatches with only the LocalUpdate
    block sharded (see ``repro.core.fl.client_store``)."""
    K = state["w_clients"].shape[0]
    down = _round_down(state, key, fl_cfg, meta, policy)
    local_keys = jax.random.split(down["k_local"], K)
    upd = _local_update_all(model_cfg, fl_cfg, meta, down["w_mixed"],
                            state["adam_m"], state["adam_v"], state["adam_t"],
                            data, local_keys)
    return _round_up(state, down, upd, fl_cfg, meta, policy)


_CLIENT_AXIS_KEYS = ("w_clients", "adam_m", "adam_v", "adam_t")


def _round(state, data, key, model_cfg, fl_cfg, meta, policy):
    """One global FL iteration: the full fleet, or — with
    ``FLConfig.participation`` — a per-round sampled cohort.

    The sampled path splits a cohort key off the round key, gathers the
    cohort's rows of every client-axis leaf (ONE ``(S,)`` gather out of the
    ``(K, D)`` store, plus the matching data rows), runs :func:`_round_body`
    on the cohort with the remaining key, and scatters the updated rows back.
    Because the body receives the post-split key exactly as an unsampled
    round would, a sampled round is BIT-IDENTICAL to a full round executed on
    the gathered cohort (tests/test_participation.py relies on this to check
    comm accounting covers sampled clients only). ``participation`` at
    ``num_clients`` (or ``None``) skips the split entirely — the exact
    historical code path, bitwise."""
    K = fl_cfg.num_clients
    S = fl_cfg.participation_size()
    if S >= K:
        return _round_body(state, data, key, model_cfg, fl_cfg, meta, policy)
    k_cohort, k_round = jax.random.split(key)
    cohort = sample_cohort(k_cohort, K, S)
    sub = dict(state)
    for name in _CLIENT_AXIS_KEYS:
        sub[name] = state[name][cohort]
    new_sub, metrics = _round_body(sub, data[cohort], k_round, model_cfg,
                                   fl_cfg, meta, policy)
    new_state = dict(new_sub)
    for name in _CLIENT_AXIS_KEYS:
        new_state[name] = state[name].at[cohort].set(new_sub[name])
    return new_state, metrics


@partial(jax.jit, static_argnames=("model_cfg", "fl_cfg", "meta", "policy"))
def _round_jit(state, data, key, model_cfg, fl_cfg, meta, policy):
    return _round(state, data, key, model_cfg, fl_cfg, meta, policy)


def fl_round(state, data, key, model_cfg: forecast.ForecastConfig,
             fl_cfg: FLConfig, meta, policy=None):
    """One jitted global FL iteration. ``policy=None`` resolves the element-
    granularity policy from ``fl_cfg.policy``."""
    policy = pol.from_config(fl_cfg) if policy is None else policy
    return _round_jit(state, data, key, model_cfg, fl_cfg, meta, policy)


# ---------------------------------------------------------------------------
# multi-round drivers
# ---------------------------------------------------------------------------


@partial(jax.jit,
         static_argnames=("model_cfg", "fl_cfg", "meta", "policy", "num_rounds"),
         donate_argnames=("state",))
def _run_chunk(state, key, data, model_cfg, fl_cfg, meta, policy, num_rounds):
    """``num_rounds`` FL rounds in ONE dispatch: lax.scan with donated client
    state (the (K, D) matrices are updated in place across rounds). Returns
    the final carry plus per-round stacked metrics."""

    def body(carry, _):
        state, key = carry
        key, rk = jax.random.split(key)
        state, metrics = _round(state, data, rk, model_cfg, fl_cfg, meta, policy)
        return (state, key), {"train_loss": metrics["train_loss"],
                              "comm_total": metrics["comm_total"]}

    (state, key), ms = jax.lax.scan(body, (state, key), None, length=num_rounds)
    return state, key, ms


_WHILE_STATICS = ("model_cfg", "fl_cfg", "meta", "policy", "max_rounds",
                  "eval_every", "patience")


def _improved(loss, best) -> bool:
    """Host-side convergence test in FLOAT32 arithmetic — the exact compare
    the while driver runs on-device (`loss < best - 1e-5` on f32 operands).
    The losses come off the device as exact f32 values; doing the threshold
    subtraction in f64 here could flip borderline rounds and break the
    loop/scan/while early-stop parity."""
    return bool(np.float32(loss) < np.float32(best) - np.float32(1e-5))


def _run_while_impl(state, key, train_data, test_data, model_cfg, fl_cfg,
                    meta, policy, max_rounds, eval_every, patience):
    """The FULL run — up to ``max_rounds`` rounds, convergence/patience and
    per-chunk RMSE included — as ONE dispatch.

    A ``lax.while_loop`` over ``eval_every``-round scan chunks carries
    ``(best_loss, stall, stop)`` on-device, replicating the scan driver's
    host-side patience logic exactly: per-round ``best_loss``/``stall``
    updates, frozen once ``stall >= patience`` fires, loop exit at the next
    chunk boundary. Rounds past ``max_rounds`` inside the final (partial)
    chunk still execute but their state/key updates are masked out, so the
    per-round state sequence is identical to the scan driver's for the same
    seed. Per-round losses and cumulative comm land in preallocated
    ``(n_chunks * eval_every,)`` buffers and the per-chunk RMSE (computed
    on-device via :func:`_rmse_device`) in an ``(n_chunks,)`` buffer; the
    caller reads everything back with a single host sync after the dispatch.

    Returns ``(state, key, loss_buf, comm_buf, rmse_buf, rounds_run,
    chunks_run)``.
    """
    n_chunks = -(-max_rounds // eval_every)
    loss_buf = jnp.zeros((n_chunks * eval_every,), jnp.float32)
    comm_buf = jnp.zeros((n_chunks * eval_every,), ACCOUNTING_DTYPE)
    rmse_buf = jnp.zeros((n_chunks,), jnp.float32)

    def round_body(rcarry, i):
        state, key, best, stall, stop, r = rcarry
        active = (r + i) < max_rounds
        key2, rk = jax.random.split(key)
        new_state, metrics = _round(state, train_data, rk, model_cfg, fl_cfg,
                                    meta, policy)
        state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(active, n, o), new_state, state)
        key = jnp.where(active, key2, key)
        loss = metrics["train_loss"]
        # the scan driver's host loop verbatim: improve resets stall, a miss
        # increments it, and once stop fires best/stall freeze for the rest
        # of the chunk (the host loop `break`s)
        upd = active & ~stop
        improved = loss < best - 1e-5
        nbest = jnp.where(improved, loss, best)
        nstall = jnp.where(improved, 0, stall + 1)
        best = jnp.where(upd, nbest, best)
        stall = jnp.where(upd, nstall, stall)
        stop = stop | (upd & (nstall >= patience))
        return ((state, key, best, stall, stop, r),
                (loss, metrics["comm_total"]))

    def chunk_body(carry):
        state, key, best, stall, stop, r, c, loss_buf, comm_buf, rmse_buf = carry
        (state, key, best, stall, stop, _), (losses, comms) = jax.lax.scan(
            round_body, (state, key, best, stall, stop, r),
            jnp.arange(eval_every))
        # r is always a multiple of eval_every and the buffers hold
        # n_chunks * eval_every entries, so these writes never clamp
        loss_buf = jax.lax.dynamic_update_slice(loss_buf, losses, (r,))
        comm_buf = jax.lax.dynamic_update_slice(comm_buf, comms, (r,))
        rmse = _rmse_device(model_cfg, state["w_global"], meta, test_data,
                            fl_cfg.client_chunk)
        rmse_buf = rmse_buf.at[c].set(rmse)
        return (state, key, best, stall, stop, r + eval_every, c + 1,
                loss_buf, comm_buf, rmse_buf)

    def chunk_cond(carry):
        _, _, _, _, stop, r, _, _, _, _ = carry
        return (r < max_rounds) & ~stop

    carry = (state, key, jnp.array(jnp.inf, jnp.float32),
             jnp.zeros((), jnp.int32), jnp.zeros((), bool),
             jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
             loss_buf, comm_buf, rmse_buf)
    (state, key, _, _, _, r, c, loss_buf, comm_buf, rmse_buf) = \
        jax.lax.while_loop(chunk_cond, chunk_body, carry)
    return (state, key, loss_buf, comm_buf, rmse_buf,
            jnp.minimum(r, max_rounds), c)


_run_while_jit = partial(jax.jit, static_argnames=_WHILE_STATICS,
                         donate_argnames=("state",))(_run_while_impl)


# The single flat eval forward holds every test window's attention scores at
# once, (K * n_win, heads, tokens, tokens) float32 per attention layer. Past
# this many bytes it runs over blocks of clients instead, as ``client_chunk``
# makes it (same reduction, same result). On a TPU v5e, PatchTST/64's flat
# eval (1.5 GB of scores a layer) compiled into the ``while`` job changed the
# float32 arithmetic of the job's training rounds (round 0's loss 2.8e-5 off
# the reference, 1.7e-2 by round 2); in blocks the rounds match the
# reference and the scan driver.
EVAL_SCORE_BYTES = 256 * 2 ** 20


def _eval_chunk(model_cfg: forecast.ForecastConfig, num_clients: int,
                n_win: int, client_chunk: Optional[int]) -> Optional[int]:
    """``client_chunk`` if given, else the most clients whose eval scores fit
    :data:`EVAL_SCORE_BYTES` (``None``, the flat forward, when all do)."""
    if client_chunk is not None or "attn" not in model_cfg.mixers:
        return client_chunk
    per_client = n_win * model_cfg.num_heads * model_cfg.num_tokens ** 2 * 4
    if num_clients * per_client <= EVAL_SCORE_BYTES:
        return None
    return max(1, EVAL_SCORE_BYTES // per_client)


@_scoped("fl.eval")
def _rmse_device(model_cfg: forecast.ForecastConfig, w_vec, meta, data,
                 client_chunk: Optional[int] = None):
    """On-device RMSE of the global model over all clients' test windows.

    data: (K, n_win, L+T) materialized windows, or the raw (K, T) test-split
    series slice under ``streaming_windows`` — the stride-1 windows are then
    built on device from static slices of each client's row
    (:func:`_all_windows`; per client inside the chunked ``lax.map``, so only
    ``client_chunk`` clients' windows exist at once; the raw slice is the only
    resident copy of the test data). With ``client_chunk`` the forward runs
    per client through ``lax.map(batch_size=client_chunk)`` so at most
    ``client_chunk * n_win`` windows' activations are live at once (the single
    flat forward materializes all ``K * n_win`` — OOM at num_clients=512
    full-preset). Without ``client_chunk``, an attention model whose flat
    forward would hold more than :data:`EVAL_SCORE_BYTES` of scores runs in
    client blocks too (:func:`_eval_chunk`). The reduction always runs over
    the full (K*n, T) prediction matrix in the same order, so the chunked
    result matches the flat one and both layouts match each other (bitwise
    on the pinned CPU toolchain).
    Returns a scalar jnp array (jit-safe; the while driver calls this inside
    its one-dispatch loop).
    """
    params = tree_unflatten_from_vector(w_vec, meta)
    Lb = model_cfg.look_back
    H = model_cfg.horizon
    W = Lb + H
    streaming = data.ndim == 2
    K = data.shape[0]
    n = data.shape[1] - W + 1 if streaming else data.shape[1]
    client_chunk = _eval_chunk(model_cfg, K, n, client_chunk)
    if client_chunk is not None and client_chunk < K:
        win = (lambda cl: _all_windows(cl, W)) if streaming else (lambda cl: cl)
        pred = jax.lax.map(
            lambda cl: forecast.forward(model_cfg, params, win(cl)[:, :Lb]),
            data, batch_size=client_chunk)
        pred = pred.reshape(K * n, H)
        # (K, n, H) truth: horizon-wide windows only, never the full L+T
        y = _all_windows(data[:, Lb:], H) if streaming else data[:, :, Lb:]
    else:
        win = _all_windows(data, W) if streaming else data     # (K, n, W)
        x = win[:, :, :Lb].reshape(K * n, Lb)
        pred = forecast.forward(model_cfg, params, x)
        y = win[:, :, Lb:]
    y = y.reshape(K * n, H)
    return jnp.sqrt(jnp.mean(jnp.square(pred - y)))


def evaluate_rmse(model_cfg: forecast.ForecastConfig, w_vec, meta, data,
                  client_chunk: Optional[int] = None) -> float:
    """RMSE of the global model over all clients' test windows.

    data: (K, n_win, L+T) materialized windows or the raw (K, T) test-split
    slice (streaming — windows built on device). ``client_chunk`` chunks
    the forward over clients (see :func:`_rmse_device`); ``None`` keeps the
    single flat forward.
    """
    return float(_rmse_device(model_cfg, w_vec, meta, data, client_chunk))


_CLIENT_STATE_KEYS = frozenset(_CLIENT_AXIS_KEYS)


def axis0_shardings(mesh_axis: str = "clients", mesh=None):
    """The ONE axis-0 layout both training and serving shard with: a
    ``(sharded, replicated)`` NamedSharding pair over a 1-D mesh of all local
    devices (axis 0 split ``mesh_axis``-ways), or ``None`` on a single device.

    :func:`client_state_shardings` applies it to the FL state's client axis;
    ``repro.launch.serve_forecast.ForecastServer(shard_batch=True)`` applies
    the same layout to each inference bucket's batch axis (with the serving
    mesh from ``repro.launch.mesh.make_batch_mesh``).
    """
    if mesh is None:
        devices = jax.devices()
        if len(devices) <= 1:
            return None
        from repro.launch.mesh import _make_mesh

        mesh = _make_mesh((len(devices),), (mesh_axis,))
    from jax.sharding import NamedSharding, PartitionSpec

    return (NamedSharding(mesh, PartitionSpec(mesh_axis)),
            NamedSharding(mesh, PartitionSpec()))


def client_state_shardings(state, mesh_axis: str = "clients", mesh=None):
    """NamedSharding tree for the FL state: client-axis ``(K, ...)`` leaves
    sharded N-way along axis 0 across the N local devices — or across an
    explicit 1-D ``mesh`` (``launch.mesh.make_client_mesh(multi_host=True)``
    spans the whole ``jax.distributed`` cluster) — server-side
    scalars/vectors replicated. Returns ``None`` on a single device with no
    explicit mesh. Leaves whose client axis does not divide N stay
    replicated.

    The while driver passes this tree as ``in_shardings`` on its donated
    carry, so the fully-compiled run keeps the client axis distributed
    end-to-end instead of gathering it on dispatch.
    """
    pair = axis0_shardings(mesh_axis, mesh=mesh)
    if pair is None:
        return None
    sharded, replicated = pair
    ndev = sharded.mesh.devices.size
    return {
        k: (sharded if k in _CLIENT_STATE_KEYS and v.shape[0] % ndev == 0
            else replicated)
        for k, v in state.items()
    }


def shard_client_state(state, mesh_axis: str = "clients"):
    """Lay the client axis of the FL state out across local devices.

    No-op on a single device. With N devices, the (K, ...) client arrays are
    sharded N-way along axis 0 (server-side scalars/vectors replicated), so
    the vmapped LocalUpdate runs clients in parallel across devices instead
    of replicating all client state on one. Sharding decisions come from
    :func:`client_state_shardings`.
    """
    shardings = client_state_shardings(state, mesh_axis)
    if shardings is None:
        return state
    return {k: jax.device_put(v, shardings[k]) for k, v in state.items()}


def run_fl(
    model_cfg: forecast.ForecastConfig,
    fl_cfg: FLConfig,
    train_data,
    test_data,
    key,
    max_rounds: int = 300,
    patience: int = 10,
    eval_every: int = 10,
    verbose: bool = False,
    driver: str = "scan",
    policy=None,
    shard_clients: bool = False,
    client_mesh=None,
    checkpoint_dir: Optional[str] = None,
    init_params=None,
):
    """Multi-round FL driver. Returns a history dict with per-round loss,
    cumulative comm, and final RMSE.

    ``init_params`` warm-starts every client (and the global model) from an
    existing param pytree instead of a fresh init — the flywheel's
    per-cluster retrain fine-tunes the live serving checkpoint on grown
    data; Adam moments and the round/comm counters still start at zero.

    ``train_data``/``test_data`` arrive in one of two layouts, selected by
    ``fl_cfg.streaming_windows``:

    * materialized (default) — ``(K, n_win, L+T)`` stride-1 window tensors
      (``repro.data.windowing.client_datasets``);
    * streaming — the raw ``(K, T)`` train/test split slices
      (``client_series_datasets``); every driver gathers ``(batch, L+T)``
      windows on device inside its compiled loop, so the raw slices are the
      ONLY training-data device residency (~``(L+T)``x less memory and H2D
      traffic). Same RNG, same gathered values -> per-round states, comm
      counters and RMSE are bit-identical to the materialized layout on the
      pinned CPU toolchain (guarded in tests/test_streaming_windows.py).

    Drivers (identical round-by-round math — same seed -> same per-round
    states, bitwise on the pinned CPU toolchain; they differ only in how much
    of the run compiles into one dispatch):

    * ``driver="loop"`` — the legacy per-round Python loop: one dispatch + two
      host syncs per round, patience can stop mid-chunk. Kept for A/B
      benchmarking (benchmarks/fl_rounds.py).
    * ``driver="scan"`` (default) — compiles ``eval_every`` rounds per
      dispatch (donated carry) and checks convergence host-side at chunk
      boundaries only; when patience triggers mid-chunk the run stops at the
      NEXT boundary, so ``rounds_run`` can exceed the loop driver's by up to
      ``eval_every - 1``.
    * ``driver="while"`` — fully compiled: a ``lax.while_loop`` over scan
      chunks carries ``(best_loss, stall, stop)`` ON-DEVICE, so the whole
      ``max_rounds`` run (per-chunk RMSE eval included) is ONE dispatch with
      zero per-chunk host round-trips; the host reads the result buffers back
      once at the end. Stop semantics match the scan driver exactly (same
      ``rounds_run``). With ``shard_clients=True`` the client-axis shardings
      are passed as ``in_shardings`` on the donated carry (one fresh jit per
      call on multi-device hosts; the single-device path uses the cached jit).
    * ``driver="host"`` — the six-figure-``num_clients`` path: client params,
      Adam moments and the raw series live in a HOST-resident
      :class:`repro.core.fl.client_store.ClientStore` (numpy); each round
      samples its cohort on host through the same :func:`sample_cohort` key
      chain the compiled drivers use in-graph, transfers ONLY the cohort's
      rows to the device, runs the jitted cohort round and scatters the
      result back. Requires ``fl_cfg.streaming_windows`` (the store holds raw
      ``(K, T)`` slices) and numpy ``train_data``/``test_data`` — pass
      device arrays to the other drivers instead. Loop-driver stop semantics
      (patience can fire mid-chunk).

    ``FLConfig.participation`` applies to every driver: each round trains and
    exchanges with a sampled size-S cohort only, comm counters accrue only
    the cohort's gates, and the loop/scan/while drivers keep their donated-
    carry / one-dispatch structure — the cohort gather/scatter compiles into
    the round itself (the while driver's 22-host-transfer pin holds under
    sampling). ``participation=num_clients`` (or ``None``) reproduces the
    unsampled engine bitwise — same per-round states on the pinned CPU
    toolchain, guarded in tests/test_participation.py.

    ``checkpoint_dir`` persists the final GLOBAL model (params + config) via
    :func:`repro.core.forecaster.save_forecaster`, restorable by
    ``load_forecaster`` / ``repro.launch.serve_forecast``.
    """
    if eval_every < 1:
        raise ValueError(f"eval_every must be >= 1, got {eval_every}")
    if client_mesh is not None and driver not in ("while", "scan"):
        raise ValueError(
            f"client_mesh applies to driver='while'|'scan' (got {driver!r}); "
            f"driver='host' spans processes through the ClientStore's own "
            f"partition mode (automatic under jax.distributed)")
    if driver == "host":
        # host-resident client store: dispatched before any (K, D) device
        # allocation happens — that residency is exactly what it avoids
        from repro.core.fl.client_store import run_fl_host

        return run_fl_host(model_cfg, fl_cfg, train_data, test_data, key,
                           max_rounds=max_rounds, patience=patience,
                           eval_every=eval_every, verbose=verbose,
                           policy=policy, checkpoint_dir=checkpoint_dir,
                           init_params=init_params)
    want = 2 if fl_cfg.streaming_windows else 3
    if train_data.ndim != want or test_data.ndim != want:
        raise ValueError(
            f"streaming_windows={fl_cfg.streaming_windows} expects "
            f"{want}-D train/test data "
            f"({'raw (K, T) series slices' if want == 2 else 'materialized (K, n_win, L+T) windows'}), "
            f"got ndim {train_data.ndim}/{test_data.ndim} — build the inputs "
            f"with repro.data.windowing."
            f"{'client_series_datasets' if want == 2 else 'client_datasets'}")
    if fl_cfg.streaming_windows:
        W = model_cfg.look_back + model_cfg.horizon
        if min(train_data.shape[1], test_data.shape[1]) < W:
            raise ValueError(
                f"raw series slices too short for look_back+horizon={W}: "
                f"train T={train_data.shape[1]}, test T={test_data.shape[1]}")
    policy = pol.from_config(fl_cfg) if policy is None else policy
    key, init_key = jax.random.split(key)
    state, meta = init_fl_state(model_cfg, fl_cfg, init_key,
                                init_params=init_params)
    shardings = None
    multihost = False
    if client_mesh is not None:
        # explicit (possibly multi-host) 1-D client mesh: every process runs
        # this same program (SPMD); init_fl_state is deterministic from the
        # shared key, so each process holds an identical host-side state and
        # we assemble per-process GLOBAL arrays from it — each process's
        # devices carry only their own client-axis rows
        shard_clients = True
        multihost = len({d.process_index
                         for d in client_mesh.devices.flat}) > 1
        shardings = client_state_shardings(state, mesh=client_mesh)
        if multihost:
            from jax.sharding import NamedSharding, PartitionSpec

            from repro.launch.distributed import host_to_global, is_main

            rep = NamedSharding(client_mesh, PartitionSpec())
            ndev = client_mesh.devices.size
            data_sh = (NamedSharding(client_mesh, PartitionSpec("clients"))
                       if train_data.shape[0] % ndev == 0 else rep)
            state = {k: host_to_global(np.asarray(v), shardings[k])
                     for k, v in state.items()}
            train_data = host_to_global(np.asarray(train_data), data_sh)
            test_data = host_to_global(np.asarray(test_data), rep)
            key = host_to_global(np.asarray(key), rep)
            if checkpoint_dir is not None and not is_main():
                checkpoint_dir = None   # process 0 owns the checkpoint write
        else:
            state = {k: jax.device_put(v, shardings[k])
                     for k, v in state.items()}
    elif shard_clients:
        shardings = client_state_shardings(state)
        state = shard_client_state(state)
    # drivers run under the client mesh, where mix_down_count partitions the
    # fused downlink kernel by hand
    mesh = None if shardings is None else next(iter(shardings.values())).mesh

    def in_mesh():
        return contextlib.nullcontext() if mesh is None else jax.set_mesh(mesh)

    history = {"round": [], "train_loss": [], "comm": [], "rmse": []}
    best_loss = math.inf
    stall = 0
    comm_total = 0.0
    stop = False

    if driver == "loop":
        for r in range(max_rounds):
            key, rk = jax.random.split(key)
            with in_mesh():
                state, metrics = _round_jit(state, train_data, rk, model_cfg,
                                            fl_cfg, meta, policy)
            loss = float(metrics["train_loss"])
            comm_total = float(metrics["comm_total"])
            history["round"].append(r)
            history["train_loss"].append(loss)
            history["comm"].append(comm_total)
            if (r + 1) % eval_every == 0 or r == max_rounds - 1:
                rmse = evaluate_rmse(model_cfg, state["w_global"], meta,
                                     test_data, fl_cfg.client_chunk)
                history["rmse"].append((r, rmse))
                if verbose:
                    print(f"round {r:4d}  loss {loss:.4f}  rmse {rmse:.4f}  "
                          f"comm {comm_total:.3e}")
            if _improved(loss, best_loss):
                best_loss = loss
                stall = 0
            else:
                stall += 1
                if stall >= patience:
                    break
    elif driver == "scan":
        r = 0
        while r < max_rounds and not stop:
            n = min(eval_every, max_rounds - r)
            with in_mesh():
                state, key, ms = _run_chunk(state, key, train_data, model_cfg,
                                            fl_cfg, meta, policy, n)
            losses = np.asarray(ms["train_loss"])   # ONE host sync per chunk
            comms = np.asarray(ms["comm_total"])
            history["round"].extend(range(r, r + n))
            history["train_loss"].extend(losses.tolist())
            history["comm"].extend(comms.tolist())
            comm_total = float(comms[-1])
            r += n
            # host-side convergence/patience, chunk boundary only
            for loss in losses.tolist():
                if _improved(loss, best_loss):
                    best_loss = loss
                    stall = 0
                else:
                    stall += 1
                    if stall >= patience:
                        stop = True
                        break
            rmse = evaluate_rmse(model_cfg, state["w_global"], meta, test_data,
                                 fl_cfg.client_chunk)
            history["rmse"].append((r - 1, rmse))
            if verbose:
                print(f"round {r - 1:4d}  loss {losses[-1]:.4f}  "
                      f"rmse {rmse:.4f}  comm {comm_total:.3e}")
    elif driver == "while":
        if shardings is None:
            fn = _run_while_jit
        else:
            # fresh jit so the donated carry's client-axis layout is pinned
            # via in_shardings (train_data rides along client-sharded too)
            from jax.sharding import NamedSharding, PartitionSpec

            ndev = mesh.devices.size
            data_spec = (PartitionSpec("clients")
                         if train_data.shape[0] % ndev == 0
                         else PartitionSpec())
            data_sh = NamedSharding(mesh, data_spec)
            if not multihost:   # multihost train_data is already global
                train_data = jax.device_put(train_data, data_sh)
            fn = jax.jit(_run_while_impl, static_argnames=_WHILE_STATICS,
                         donate_argnames=("state",),
                         in_shardings=(shardings, None, data_sh, None))
        # statics ride positionally: pjit rejects kwargs with in_shardings
        with in_mesh(), TraceAnnotation("fl.dispatch"):
            out = fn(state, key, train_data, test_data, model_cfg, fl_cfg,
                     meta, policy, max_rounds, eval_every, patience)
        state, key, loss_buf, comm_buf, rmse_buf, rounds_dev, chunks_dev = out
        with TraceAnnotation("fl.readback"):
            if multihost:
                # gather the run-level history to every host ONCE at run end
                # (the per-round loop stays collective-free beyond the round
                # math)
                from repro.launch.distributed import fetch

                loss_buf, comm_buf, rmse_buf, rounds_dev, chunks_dev = (
                    fetch(loss_buf), fetch(comm_buf), fetch(rmse_buf),
                    fetch(rounds_dev), fetch(chunks_dev))
            rounds_run = int(rounds_dev)  # the ONE host sync of the whole run
            chunks_run = int(chunks_dev)
            losses = np.asarray(loss_buf)[:rounds_run]
            comms = np.asarray(comm_buf)[:rounds_run]
            rmses = np.asarray(rmse_buf)[:chunks_run].tolist()
        history["round"] = list(range(rounds_run))
        history["train_loss"] = losses.tolist()
        history["comm"] = comms.tolist()
        comm_total = float(comms[-1]) if rounds_run else 0.0
        for i, rmse in enumerate(rmses):
            r_end = min((i + 1) * eval_every, max_rounds) - 1
            history["rmse"].append((r_end, rmse))
            if verbose:
                print(f"round {r_end:4d}  loss {losses[min(r_end, rounds_run - 1)]:.4f}  "
                      f"rmse {rmse:.4f}  comm {comm_total:.3e}")
    else:
        raise ValueError(f"unknown driver: {driver!r}")

    # scan/while always evaluate the final state at the last chunk boundary;
    # reuse that entry instead of a second full test-set forward (the loop
    # driver can break mid-chunk, where the last entry is stale -> recompute)
    if history["rmse"] and history["rmse"][-1][0] == len(history["round"]) - 1:
        final_rmse = history["rmse"][-1][1]
    else:
        final_rmse = evaluate_rmse(model_cfg, state["w_global"], meta,
                                   test_data, fl_cfg.client_chunk)
    with TraceAnnotation("fl.finalize"):
        return _finalize_history(history, state, meta, model_cfg, fl_cfg,
                                 final_rmse, comm_total, checkpoint_dir)


def _finalize_history(history, state, meta, model_cfg, fl_cfg, final_rmse,
                      comm_total, checkpoint_dir):
    """Shared run_fl tail (device drivers AND the host-store driver): attach
    the summary fields and optionally checkpoint the trained GLOBAL model in
    ``load_forecaster`` format — the deployable artifact the serving path
    (launch/serve_forecast) restores."""
    history["final_rmse"] = final_rmse
    history["final_comm"] = comm_total
    # Wire bytes: payload elements at comm_bits each, PLUS — at 8 bits — the
    # accumulated per-payload fp32 scale headers (state["comm_scales"]).
    scale_count = (float(state["comm_scales"])
                   if "comm_scales" in state else 0.0)
    history["final_scale_bytes"] = scale_count * 4.0
    history["final_comm_bytes"] = (comm_total * (fl_cfg.comm_bits / 8.0)
                                   + scale_count * 4.0)
    history["rounds_run"] = len(history["round"])
    history["state"] = state
    history["meta"] = meta
    if checkpoint_dir is not None:
        from repro.core.forecaster import Forecaster, save_forecaster

        params = tree_unflatten_from_vector(state["w_global"], meta)
        history["checkpoint"] = save_forecaster(
            checkpoint_dir, Forecaster(model_cfg), params,
            step=history["rounds_run"],
            extra={"final_rmse": final_rmse, "final_comm": comm_total,
                   "policy": fl_cfg.policy, "num_clients": fl_cfg.num_clients})
    return history
