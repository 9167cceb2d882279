"""Host-resident client store: six-figure ``num_clients`` on one host.

The compiled drivers (``run_fl(driver="loop"/"scan"/"while")``) keep the whole
``(K, D)`` client state device-resident — the right call up to a few thousand
clients, but at the paper's deployment scale (geographically dispersed EV
charging stations, ``K`` ~ 1e5) the state alone is gigabytes and only a
size-``S`` cohort (``FLConfig.participation``) actually trains each round.
:class:`ClientStore` flips the residency: client params, Adam moments and the
raw ``(K, T)`` series live in HOST memory (numpy), and :func:`run_fl_host`
(the ``driver="host"`` path of ``repro.core.fl.engine.run_fl``) transfers
ONLY the sampled cohort per round:

  1. sample the cohort on host via the exact key chain the compiled drivers
     use in-graph (``engine.sample_cohort`` on the post-split round key), so
     the same seed yields the same cohort sequence as every other driver;
  2. gather the cohort's rows out of the numpy store (one fancy-index per
     leaf) and ship the ``(S, D)`` slices to the device;
  3. run the jitted cohort round — ``engine._round_body``, the SAME function
     every other driver compiles, with donated input buffers;
  4. scatter the updated rows back into the store and keep only the server
     state (global vector + comm counters) device-resident.

Per-round H2D traffic is ``O(S * D)`` instead of ``O(K * D)`` residency, so
``num_clients=100_000`` runs honestly on one host (benchmarks/fl_rounds.py
records the store/device byte split). Per-round math is bit-identical to the
device drivers under the same seed on the pinned CPU toolchain — the cohort
round is literally the same jitted body — guarded in
tests/test_participation.py.

Evaluation never materializes the fleet either: :meth:`ClientStore.
evaluate_rmse` streams the held-out raw slices through the forward in
client chunks (two compiled shapes at most: the chunk and the remainder).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.pytree_utils import tree_flatten_to_vector
from repro.core import forecast
from repro.core.fl import engine as E
from repro.core.fl import policies as pol

# The cohort round: engine._round_body — the same per-round math every
# compiled driver embeds — jitted standalone with donated cohort buffers
# (fresh cohort slices arrive every round; their buffers are dead after the
# scatter, so XLA reuses them in place).
_cohort_round = partial(
    jax.jit, static_argnames=("model_cfg", "fl_cfg", "meta", "policy"),
    donate_argnames=("state",))(E._round_body)

# The staged cohort round for the multi-process partition mode: the same
# three stages _round_body composes, jitted separately so each process can
# run selection/downlink and uplink/aggregation REPLICATED (identical inputs
# -> identical outputs, no collectives) while computing LocalUpdate only for
# its own contiguous cohort-position block. Staged == fused is bitwise on
# the pinned CPU toolchain (tests/test_distributed.py).
_stage_down = partial(
    jax.jit, static_argnames=("fl_cfg", "meta", "policy"))(E._round_down)
_stage_local = partial(
    jax.jit, static_argnames=("model_cfg", "fl_cfg", "meta"))(
        E._local_update_all)
_stage_up = partial(
    jax.jit, static_argnames=("fl_cfg", "meta", "policy"))(E._round_up)


@partial(jax.jit, static_argnames=("model_cfg", "meta"))
def _chunk_sse(w_vec, data, model_cfg, meta):
    """Sum of squared forecast errors of the global model over one client
    chunk's raw ``(C, T)`` test slice (stride-1 windows built on device from
    static slices of each client's row — the chunk slice is the only
    test-data device residency)."""
    params = E.tree_unflatten_from_vector(w_vec, meta)
    Lb, H = model_cfg.look_back, model_cfg.horizon
    W = Lb + H
    C = data.shape[0]
    n = data.shape[1] - W + 1
    win = E._all_windows(data, W)                         # (C, n, W)
    pred = forecast.forward(model_cfg, params,
                            win[:, :, :Lb].reshape(C * n, Lb))
    return jnp.sum(jnp.square(pred - win[:, :, Lb:].reshape(C * n, H)))


class ClientStore:
    """Host-resident (numpy) FL client state + raw series store.

    Mirrors ``engine.init_fl_state`` exactly — same init key path, same
    per-client tiled global vector, zero Adam moments — but allocates the
    client-axis arrays in host memory. The server-side global vector stays a
    device array (``w_global``); everything keyed by client is numpy.

    ``train``/``test`` are the raw ``(K, T)`` streaming split slices
    (``repro.data.windowing.client_series_datasets``) — the store requires
    ``fl_cfg.streaming_windows`` because the raw layout is what makes cohort
    swaps cheap (~``(L+T)``x smaller rows than materialized windows).
    """

    def __init__(self, model_cfg, fl_cfg, train, test, key,
                 init_params=None, partition=None):
        if not fl_cfg.streaming_windows:
            raise ValueError(
                "ClientStore requires FLConfig.streaming_windows=True: the "
                "store holds raw (K, T) series slices "
                "(repro.data.windowing.client_series_datasets)")
        train = np.ascontiguousarray(np.asarray(train, np.float32))
        test = np.ascontiguousarray(np.asarray(test, np.float32))
        if train.ndim != 2 or test.ndim != 2:
            raise ValueError(
                f"expected raw (K, T) series slices, got ndim "
                f"{train.ndim}/{test.ndim}")
        if train.shape[0] != fl_cfg.num_clients:
            raise ValueError(
                f"train series has {train.shape[0]} clients, FLConfig says "
                f"num_clients={fl_cfg.num_clients}")
        params = (forecast.init_params(model_cfg, key) if init_params is None
                  else init_params)
        vec, self.meta = tree_flatten_to_vector(params)
        self.model_cfg, self.fl_cfg = model_cfg, fl_cfg
        self.w_global = vec                               # device (D,)
        K, D = fl_cfg.num_clients, int(vec.shape[0])
        # partition=(index, count): multi-process mode — this store holds
        # ONLY its contiguous [lo, hi) block of the client axis (state rows
        # AND raw series), so K's host RSS spreads count-ways across the
        # jax.distributed processes (run_fl_host owns the cohort exchange).
        if partition is not None and partition[1] > 1:
            idx, cnt = int(partition[0]), int(partition[1])
            if not 0 <= idx < cnt:
                raise ValueError(f"partition index {idx} out of range "
                                 f"for count {cnt}")
            if K % cnt:
                raise ValueError(
                    f"partition mode needs num_clients divisible by the "
                    f"process count, got K={K} over {cnt} processes")
            self.partition = (idx, cnt)
            self.lo, self.hi = (K * idx) // cnt, (K * (idx + 1)) // cnt
        else:
            self.partition = None
            self.lo, self.hi = 0, K
        Kp = self.hi - self.lo
        vec_np = np.asarray(vec)
        self.w_clients = np.tile(vec_np[None, :], (Kp, 1))
        self.adam_m = np.zeros((Kp, D), np.float32)
        self.adam_v = np.zeros((Kp, D), np.float32)
        self.adam_t = np.zeros((Kp,), np.int32)
        self.train = np.ascontiguousarray(train[self.lo:self.hi])
        self.test = np.ascontiguousarray(test[self.lo:self.hi])
        self.num_clients = K
        self._test_T = test.shape[1]

    @property
    def state_nbytes(self) -> int:
        """Host bytes of the client-axis state (params + Adam moments)."""
        return int(self.w_clients.nbytes + self.adam_m.nbytes
                   + self.adam_v.nbytes + self.adam_t.nbytes)

    @property
    def series_nbytes(self) -> int:
        """Host bytes of the raw train + test series."""
        return int(self.train.nbytes + self.test.nbytes)

    @property
    def nbytes(self) -> int:
        """Total host-resident bytes (client state + series)."""
        return self.state_nbytes + self.series_nbytes

    def gather(self, cohort: np.ndarray) -> dict:
        """The cohort's client-axis rows as device arrays (one fancy-index
        per leaf + one H2D transfer each — ``O(S * D)``, never ``O(K)``)."""
        return {
            "w_clients": jnp.asarray(self.w_clients[cohort]),
            "adam_m": jnp.asarray(self.adam_m[cohort]),
            "adam_v": jnp.asarray(self.adam_v[cohort]),
            "adam_t": jnp.asarray(self.adam_t[cohort]),
        }

    def gather_train(self, cohort: np.ndarray):
        """The cohort's raw train slices as a device ``(S, T)`` array."""
        return jnp.asarray(self.train[cohort])

    def scatter(self, cohort: np.ndarray, sub: dict) -> None:
        """Write a cohort round's updated client rows back into the store."""
        self.w_clients[cohort] = np.asarray(sub["w_clients"])
        self.adam_m[cohort] = np.asarray(sub["adam_m"])
        self.adam_v[cohort] = np.asarray(sub["adam_v"])
        self.adam_t[cohort] = np.asarray(sub["adam_t"])

    # --- multi-process partition exchange ---------------------------------
    def cohort_payload(self, cohort: np.ndarray):
        """This process's contribution to the cohort exchange: full-shape
        ``(S, ...)`` client-state leaves plus the ``(S, T)`` train-slice
        matrix, with the cohort positions whose client id falls in this
        store's ``[lo, hi)`` block filled from the local rows and ZEROS
        everywhere else. ``launch.distributed.merge_disjoint`` of every
        process's payload reconstructs the full cohort bit-exactly (disjoint
        int32-bitcast sum — no float arithmetic on the wire)."""
        S = int(cohort.shape[0])
        pos = np.nonzero((cohort >= self.lo) & (cohort < self.hi))[0]
        loc = cohort[pos] - self.lo
        D = self.w_clients.shape[1]
        w = np.zeros((S, D), np.float32)
        m = np.zeros((S, D), np.float32)
        v = np.zeros((S, D), np.float32)
        t = np.zeros((S,), np.int32)
        data = np.zeros((S, self.train.shape[1]), np.float32)
        w[pos] = self.w_clients[loc]
        m[pos] = self.adam_m[loc]
        v[pos] = self.adam_v[loc]
        t[pos] = self.adam_t[loc]
        data[pos] = self.train[loc]
        return (w, m, v, t, data), pos, loc

    def scatter_owned(self, pos: np.ndarray, loc: np.ndarray,
                      sub: dict) -> None:
        """Write back ONLY the cohort positions this store owns (``pos`` ->
        local rows ``loc``, from :meth:`cohort_payload`) out of a full
        replicated ``(S, ...)`` round result."""
        self.w_clients[loc] = np.asarray(sub["w_clients"])[pos]
        self.adam_m[loc] = np.asarray(sub["adam_m"])[pos]
        self.adam_v[loc] = np.asarray(sub["adam_v"])[pos]
        self.adam_t[loc] = np.asarray(sub["adam_t"])[pos]

    def evaluate_rmse(self, w_vec, client_chunk: Optional[int] = None) -> float:
        """RMSE of the global model over ALL clients' test windows, streamed
        from the host store in client chunks (default ``min(K, 1024)``; at
        most two compiled shapes — the chunk and the remainder). Matches
        ``engine.evaluate_rmse`` up to float summation order.

        In partition mode each process streams only its own client block and
        the per-chunk f32 SSE values are allgathered and reduced in
        (process, chunk) order — identical to the single-process chunk order
        (hence a bitwise-identical RMSE) whenever ``chunk`` divides the
        per-process block size ``K / count``."""
        Kp = self.test.shape[0]
        K = self.num_clients
        chunk = client_chunk if client_chunk is not None else min(K, 1024)
        W = self.model_cfg.look_back + self.model_cfg.horizon
        n = self.test.shape[1] - W + 1
        local = []
        for i in range(0, Kp, chunk):
            part = jnp.asarray(self.test[i:i + chunk])
            local.append(float(_chunk_sse(w_vec, part, self.model_cfg,
                                          self.meta)))
        if self.partition is not None:
            from repro.launch.distributed import allgather_blocks

            cnt = self.partition[1]
            merged = allgather_blocks(np.asarray(local, np.float32),
                                      cnt * len(local))
            local = [float(x) for x in merged]
        sse = 0.0
        for v in local:
            sse += v
        return math.sqrt(sse / (K * n * self.model_cfg.horizon))


def run_fl_host(model_cfg, fl_cfg, train_data, test_data, key, *,
                max_rounds: int = 300, patience: int = 10,
                eval_every: int = 10, verbose: bool = False, policy=None,
                checkpoint_dir: Optional[str] = None,
                init_params=None, partition=None) -> dict:
    """The ``run_fl(driver="host")`` implementation: loop-driver round/stop
    semantics with the ``(K, D)`` client state host-resident and only the
    per-round cohort on device. See the module docstring for the round cycle
    and ``engine.run_fl`` for the shared contract; the returned history
    additionally carries ``history["client_store"]`` (the live
    :class:`ClientStore`) so callers can read residency stats or keep
    training.

    ``partition=(index, count)`` is the MULTI-PROCESS mode (defaults to
    ``(jax.process_index(), jax.process_count())`` under an initialized
    ``jax.distributed`` cluster, i.e. it activates automatically): every
    process replays the identical server-side key chain and cohort sequence,
    holds only its own ``K / count`` client block (state + raw series), and
    each round (1) reconstructs the cohort's rows on every process via the
    exact disjoint-bitcast merge, (2) runs selection/downlink replicated,
    (3) computes LocalUpdate for its own contiguous ``S / count``
    cohort-position block only, (4) allgathers the blocks (pure movement)
    and (5) runs uplink/aggregation replicated. Every arithmetic stage is
    either replicated or batch-invariant vmapped rows, and every exchange is
    exact — so per-round states, comm counters and (chunk-aligned) RMSE are
    BITWISE identical to the single-process run on the pinned CPU toolchain
    (tests/test_distributed.py). Requires ``num_clients`` and the cohort
    size divisible by ``count``, with at least 2 cohort rows per process."""
    if partition is None and jax.process_count() > 1:
        partition = (jax.process_index(), jax.process_count())
    if partition is not None and partition[1] <= 1:
        partition = None
    policy = pol.from_config(fl_cfg) if policy is None else policy
    key, init_key = jax.random.split(key)
    store = ClientStore(model_cfg, fl_cfg, train_data, test_data, init_key,
                        init_params=init_params, partition=partition)
    W = model_cfg.look_back + model_cfg.horizon
    if min(store.train.shape[1], store.test.shape[1]) < W:
        raise ValueError(
            f"raw series slices too short for look_back+horizon={W}: "
            f"train T={store.train.shape[1]}, test T={store.test.shape[1]}")

    K, S = fl_cfg.num_clients, fl_cfg.participation_size()
    meta = store.meta
    if partition is not None:
        idx, cnt = store.partition
        if S % cnt or S // cnt < 2:
            raise ValueError(
                f"partition mode needs the cohort size divisible by the "
                f"process count with >= 2 rows per process (vmapped "
                f"LocalUpdate rows are batch-invariant only for batches "
                f">= 2), got participation={S} over {cnt} processes")
        blo, bhi = (S * idx) // cnt, (S * (idx + 1)) // cnt
        if checkpoint_dir is not None and idx != 0:
            checkpoint_dir = None   # process 0 owns the checkpoint write
    server = {
        "w_global": store.w_global,
        "round": jnp.zeros((), jnp.int32),
        "comm_down": jnp.zeros((), E.ACCOUNTING_DTYPE),
        "comm_up": jnp.zeros((), E.ACCOUNTING_DTYPE),
    }
    if fl_cfg.comm_bits == 8:
        # int8 wire: the scale-header counter rides with the server state
        # (mirrors engine.init_fl_state — added only at 8 bits so existing
        # configs keep their carry structure)
        server["comm_scales"] = jnp.zeros((), E.ACCOUNTING_DTYPE)
    full_cohort = np.arange(K)

    history = {"round": [], "train_loss": [], "comm": [], "rmse": []}
    best_loss = math.inf
    stall = 0
    comm_total = 0.0
    for r in range(max_rounds):
        key, rk = jax.random.split(key)
        if S < K:
            # the device drivers' in-graph key chain, replayed on host:
            # _round splits (k_cohort, k_round) off the round key
            k_cohort, rk = jax.random.split(rk)
            cohort = np.asarray(E.sample_cohort(k_cohort, K, S))
        else:
            cohort = full_cohort
        # The STAGED round (downlink -> LocalUpdate -> uplink), single- and
        # multi-process alike, so both partitionings run the identical
        # compiled stages (the fused _round_body computes bitwise-identical
        # STATES, but XLA may fuse the train_loss reduction differently
        # around a chunked lax.map — staging pins the metric too).
        if partition is None:
            sub = store.gather(cohort)
            w_c, a_m, a_v, a_t = (sub["w_clients"], sub["adam_m"],
                                  sub["adam_v"], sub["adam_t"])
            data = store.gather_train(cohort)
        else:
            # exact cohort reconstruction: disjoint int32-bitcast merge of
            # every process's owned rows
            from repro.launch.distributed import merge_disjoint

            payload, pos, loc = store.cohort_payload(cohort)
            w_c, a_m, a_v, a_t, data = merge_disjoint(*payload)
        sub_state = {**server, "w_clients": w_c, "adam_m": a_m,
                     "adam_v": a_v, "adam_t": a_t}
        down = _stage_down(sub_state, rk, fl_cfg, meta, policy)
        local_keys = jax.random.split(down["k_local"], S)
        if partition is None:
            upd = _stage_local(model_cfg, fl_cfg, meta, down["w_mixed"],
                               a_m, a_v, a_t, data, local_keys)
        else:
            # LocalUpdate only for this process's contiguous cohort-position
            # block; the blocks reassemble by pure movement (allgather)
            from repro.launch.distributed import allgather_blocks

            upd = _stage_local(model_cfg, fl_cfg, meta,
                               down["w_mixed"][blo:bhi], a_m[blo:bhi],
                               a_v[blo:bhi], a_t[blo:bhi], data[blo:bhi],
                               local_keys[blo:bhi])
            upd = tuple(allgather_blocks([np.asarray(u) for u in upd], S))
        sub_new, metrics = _stage_up(sub_state, down, upd, fl_cfg, meta,
                                     policy)
        if partition is None:
            store.scatter(cohort, sub_new)
        else:
            store.scatter_owned(pos, loc, sub_new)
        server = {k: sub_new[k] for k in server}

        loss = float(metrics["train_loss"])
        comm_total = float(metrics["comm_total"])
        history["round"].append(r)
        history["train_loss"].append(loss)
        history["comm"].append(comm_total)
        if (r + 1) % eval_every == 0 or r == max_rounds - 1:
            rmse = store.evaluate_rmse(server["w_global"], fl_cfg.client_chunk)
            history["rmse"].append((r, rmse))
            if verbose:
                print(f"round {r:4d}  loss {loss:.4f}  rmse {rmse:.4f}  "
                      f"comm {comm_total:.3e}")
        if E._improved(loss, best_loss):
            best_loss = loss
            stall = 0
        else:
            stall += 1
            if stall >= patience:
                break

    if history["rmse"] and history["rmse"][-1][0] == len(history["round"]) - 1:
        final_rmse = history["rmse"][-1][1]
    else:
        final_rmse = store.evaluate_rmse(server["w_global"], fl_cfg.client_chunk)
    state = {
        "w_global": server["w_global"],
        "w_clients": store.w_clients,
        "adam_m": store.adam_m,
        "adam_v": store.adam_v,
        "adam_t": store.adam_t,
        "round": server["round"],
        "comm_down": server["comm_down"],
        "comm_up": server["comm_up"],
    }
    if "comm_scales" in server:
        state["comm_scales"] = server["comm_scales"]
    history["client_store"] = store
    return E._finalize_history(history, state, meta, model_cfg, fl_cfg,
                               final_rmse, comm_total, checkpoint_dir)
