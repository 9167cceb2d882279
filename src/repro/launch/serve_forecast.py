"""Forecast serving endpoint: restore federated checkpoints and serve them.

The deployable artifact of the paper's system is the trained GLOBAL
forecaster — ONE PER DTW CLUSTER of charging stations. ``run_fl
(checkpoint_dir=...)`` / ``run_experiment(checkpoint_dir=...)`` write each
cluster's model in ``load_forecaster`` format plus a ROUTING MANIFEST; this
module turns those checkpoints into a batched, routed inference endpoint:

  * the step is a jitted ``forward_multivariate`` (one compile per shape
    bucket per cluster) writing into a DONATED per-bucket output buffer —
    steady-state serving allocates no fresh output arrays;
  * ragged request batches are padded up to a small set of SHAPE BUCKETS
    (powers of two up to ``max_batch``) so the jit cache stays bounded no
    matter what batch sizes arrive;
  * ONE server restores N per-cluster checkpoints
    (:meth:`ForecastServer.from_manifest`) and routes every request by its
    station's cluster label; the micro-batching worker coalesces the queue
    per (cluster, shape) group, so heterogeneous traffic across clusters
    still coalesces into full buckets. Routed outputs are bit-identical to
    serving each cluster's checkpoint directly (same compiled step, same
    buckets — guarded in tests/test_routed_serving.py);
  * ``shard_batch=True`` shards each bucket's batch axis over the local
    devices (``repro.launch.mesh.make_batch_mesh`` +
    ``repro.core.fl.engine.axis0_shardings`` — the same axis-0 layout the FL
    engine shards client state with); buckets the device count does not
    divide stay replicated;
  * ``comm_bits=16`` restores bf16-QUANTIZED payloads, ``comm_bits=8``
    int8 + per-leaf-scale payloads (``repro.checkpoint.quantize_tree``),
    mirroring ``FLConfig.comm_bits`` on the inference side;
  * :func:`stream_evaluate` is the continuous-evaluation harness: it replays
    a held-out day of ``ForecastTask`` windows through the queue in arrival
    order and tracks per-cluster ONLINE RMSE (a per-request timeout skips and
    counts stuck futures instead of stalling the whole replay);
  * every server counts through one ``repro.launch.metrics.MetricsRegistry``
    (``ForecastServer.stats`` and ``cluster_stats`` are read-only views of
    it; ``metrics=False`` only hides it from exposition): the worker loop
    records submit->result latency histograms, per-(cluster, shape) batch
    fill and padded-slot waste, per-cluster request/series counters,
    reject/error tallies and the process's garbage-collector pauses —
    dumped by :meth:`ForecastServer.metrics_text` and served over HTTP at
    ``GET /metricz`` by ``repro.launch.gateway.ForecastGateway``, the
    production front door (auth, rate limiting, load shedding) for this
    server;
  * the serving path names its steps for the profiler
    (``jax.profiler.TraceAnnotation``, about a microsecond each when no
    trace is running): ``serve.submit`` on the caller's thread; on the
    worker ``serve.queue_wait``, ``serve.coalesce`` and, per dispatched
    group, ``serve.group`` around ``serve.assemble``, ``serve.step``,
    ``serve.copy_back`` and ``serve.resolve``; ``gc.collect`` around each
    collection while a server is started (docs/serving.md);
  * :meth:`ForecastServer.close` is the TERMINAL shutdown: it stops the
    worker, fails every still-pending future with ``RuntimeError``, and
    fails anything submitted afterwards — waiters never hang on a dead
    server (``stop()`` remains the pausable variant: the worker drains its
    current window and can be ``start()``-ed again);
  * the routing state (engines + station table + norm stats) lives in one
    swappable GENERATION snapshot: :meth:`ForecastServer.reload` restores a
    newer manifest generation's changed clusters, warms them off the serving
    path, and publishes the snapshot with a single atomic store —
    zero-drop hot swap (queued old-generation requests drain through their
    own engines; see docs/flywheel.md) — while
    :meth:`ForecastServer.watch_manifest` runs that reload from a background
    poller and ``repro.core.fl.flywheel.RetrainController`` is the writer
    that produces the new generations (drift-triggered per-cluster
    retraining).

Routing manifest format (written by ``repro.core.tasks.run_experiment`` via
``write_routing_manifest`` at ``<checkpoint_dir>/routing.json``)::

    {"task": "ev", "model": "logtst/15",
     "look_back": 64, "horizon": 2, "clusters": 2,
     "station_cluster": [0, 1, 0, ...],     # request routing key
     "norm": {"mu": [...], "sd": [...]},    # per-station z-norm stats
     "policies": {"psgf-s30-f20": {"0": "psgf-s30-f20_c0",     # cluster ->
                                   "1": "psgf-s30-f20_c1"}}}   # ckpt subdir

``ForecastServer.from_manifest(root)`` restores every cluster of one policy
(the only one, unless ``policy=`` picks from a multi-policy grid) and routes
``submit(x, station=s)`` through ``station_cluster[s]``. A station whose
cluster has no checkpoint (skipped for ``min_cluster_clients``) fails only
its own future. With ``denormalize=True`` the manifest's per-station ``norm``
stats (the exact z-norm each station trained under) make station-routed
requests RAW: the look-back is normalized on the way in and the forecast
rescaled to the station's original units on the way out — no client-side
knowledge of the training normalization needed.

Streaming evaluation usage::

    server = ForecastServer.from_manifest(ckpt_root)
    rep = stream_evaluate(server, task)      # replays the held-out windows
    rep["per_cluster"][0]["rmse"]            # online RMSE, cluster 0

CLI (restore + synthetic load, reports forecasts/sec):

  PYTHONPATH=src python -m repro.launch.serve_forecast --ckpt-dir CKPT \
      [--requests 256] [--channels 3] [--max-batch 32] [--no-queue]
  PYTHONPATH=src python -m repro.launch.serve_forecast --manifest ROOT \
      [--policy P] [--comm-bits 16] [--shard-batch]      # routed serving

Benchmarked in ``benchmarks/serve_forecast.py``; demoed end-to-end (train ->
checkpoint -> routed serving -> streaming eval) in
``examples/serve_forecast_demo.py``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError
from functools import lru_cache
from types import MappingProxyType
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.common.compile_cache import enable_compile_cache
from repro.core.forecaster import Forecaster, load_forecaster
from repro.launch.metrics import DEFAULT_LATENCY_BUCKETS, MetricsRegistry

_STOP = object()
_NO_DEFAULT = object()  # multi-cluster servers have no default route


def _safe_set(fut: Future, result=None, exc: Optional[BaseException] = None):
    """Resolve a waiter that may ALREADY be done: a gateway deadline (or any
    caller) can cancel a queued future, and set_result on it would raise
    InvalidStateError out of the worker loop — killing the thread and
    hanging every later waiter. A cancelled/raced future just discards the
    late result."""
    try:
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(result)
    except InvalidStateError:
        pass


# --- collector pauses --------------------------------------------------------
# ONE gc.callbacks hook for the whole process, shared by every started server:
# a collection holds the interpreter lock, so it stalls the worker and every
# caller at once, whichever server's heap it walks.
_GC_SINKS: tuple = ()       # started servers' per-generation gc counters
_GC_LOCK = threading.Lock()  # guards (un)registration; never taken by the hook
_gc_open = None             # (span, start) of the collection in progress


def _gc_hook(phase: str, info: dict):
    """A ``gc.collect`` span around each collection, and its count and pause
    in every started server's registry. The collector runs one collection at
    a time, so a ``start`` and its ``stop`` pair up."""
    global _gc_open
    if phase == "start":
        span = TraceAnnotation("gc.collect", generation=info["generation"])
        span.__enter__()
        _gc_open = (span, time.perf_counter())
        return
    if _gc_open is None:
        return
    span, t0 = _gc_open
    _gc_open = None
    pause = time.perf_counter() - t0
    span.__exit__(None, None, None)
    g = info["generation"]
    for counts, pauses in _GC_SINKS:
        counts[g].inc()
        pauses[g].inc(pause)


def _gc_watch(sink, on: bool):
    """Add (``on``) or remove one server's gc counters; the hook is in
    ``gc.callbacks`` exactly while some server is watching."""
    global _GC_SINKS
    with _GC_LOCK:
        sinks = tuple(s for s in _GC_SINKS if s is not sink)
        _GC_SINKS = sinks + (sink,) if on else sinks
        hooked = _gc_hook in gc.callbacks
        if _GC_SINKS and not hooked:
            gc.callbacks.append(_gc_hook)
        elif not _GC_SINKS and hooked:
            gc.callbacks.remove(_gc_hook)


def _total(family, **match) -> int:
    """Sum of a counter family's series whose labels include ``match``."""
    return int(sum(child.get() for values, child in family.samples()
                   if all(dict(zip(family.label_names, values))[k] == v
                          for k, v in match.items())))


def batch_buckets(max_batch: int) -> Tuple[int, ...]:
    """Powers of two up to (and always including) ``max_batch``."""
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


@lru_cache(maxsize=None)
def _bucket_step(cfg):
    """ONE jitted donated-buffer step per ForecastConfig. Params are a traced
    argument, so every cluster engine with the same geometry SHARES this jit
    (and its XLA compile cache): an N-cluster routed server compiles each
    (bucket, channels) shape once, not N times."""
    from repro.core.forecast import forward_multivariate

    return jax.jit(
        lambda p, x, out: out.at[:].set(forward_multivariate(cfg, p, x)),
        donate_argnums=(2,))


class _ClusterEngine:
    """One restored model's inference machinery: the (config-shared) jitted
    donated-buffer step plus this model's per-(bucket, channels) output
    buffers. The routed server holds one engine per cluster and the
    single-model server is the one-engine degenerate case, so routed and
    direct serving run EXACTLY the same compiled step on the same params —
    bit-identical outputs."""

    def __init__(self, forecaster: Forecaster, params, shardings=None):
        self.forecaster = forecaster
        self.shardings = shardings  # (sharded, replicated) pair or None
        self.params = (jax.device_put(params) if shardings is None
                       else jax.device_put(params, shardings[1]))
        self._ndev = 1 if shardings is None else shardings[0].mesh.devices.size
        # (bucket, channels) -> donated output buffer; replaced on every step
        self._out: Dict[Tuple[int, int], jax.Array] = {}
        self._step = _bucket_step(forecaster.cfg)

    def run_padded(self, x: np.ndarray, rows: int) -> np.ndarray:
        """x: (bucket, M, L) already padded to a bucket size. Runs the
        donated-output step and returns the first ``rows`` live rows COPIED
        off the buffer — the copy must happen before the buffer is
        republished to ``self._out``, where a concurrent caller (worker
        thread + a warmup/predict from another thread) could pop and donate
        it again."""
        bucket, M, _ = x.shape
        T = self.forecaster.cfg.horizon
        with TraceAnnotation("serve.step"):
            xj = jnp.asarray(x, jnp.float32)
            shard = self.shardings is not None and bucket % self._ndev == 0
            if shard:
                xj = jax.device_put(xj, self.shardings[0])
            key = (bucket, M)
            out = self._out.pop(key, None)
            if out is None:
                out = jnp.zeros((bucket, M, T), jnp.float32)
                if shard:
                    out = jax.device_put(out, self.shardings[0])
            out = self._step(self.params, xj, out)
        with TraceAnnotation("serve.copy_back"):
            result = np.asarray(out[:rows])
        self._out[key] = out
        return result


class _Generation:
    """One immutable ROUTING SNAPSHOT: the per-cluster engines, the
    station->cluster table, the per-station norm stats and the monotonic
    ``generation`` number they were published under. The server holds exactly
    one live snapshot and swaps whole snapshots atomically (a single
    attribute store); every request reads ONE snapshot at entry and queued
    requests carry a reference to theirs, so a hot swap can never leave a
    request half-routed — old-generation futures drain through the
    old-generation engines, which are released (GC'd) only after the last
    queued reference resolves."""

    __slots__ = ("generation", "engines", "station_cluster", "station_norm",
                 "default", "sources")

    def __init__(self, generation: int, engines: Dict,
                 station_cluster=None, station_norm=None,
                 sources: Optional[Dict] = None):
        self.generation = int(generation)
        self.engines = engines
        self.station_cluster = (None if station_cluster is None
                                else [int(c) for c in station_cluster])
        # (mu, sd) per station: when set, station-routed requests are RAW —
        # normalized in, forecasts denormalized out (see _norm_for)
        self.station_norm = None
        if station_norm is not None:
            mu, sd = station_norm
            self.station_norm = (np.asarray(mu, np.float32).ravel(),
                                 np.asarray(sd, np.float32).ravel())
        self.default = (next(iter(engines))
                        if len(engines) == 1 else _NO_DEFAULT)
        # cluster -> checkpoint subdir each engine was restored from: reload
        # reuses the live engine when a cluster's subdir is unchanged, so a
        # per-cluster retrain rebuilds ONLY the retrained cluster's engine
        self.sources = dict(sources or {})


class ForecastServer:
    """Batched, bucketed, micro-batching inference over one forecaster or a
    ROUTED family of per-cluster forecasters.

    Single model (the PR 2 surface, unchanged)::

        ForecastServer(forecaster, params).predict(x)

    Multi-cluster routed (``models``: cluster label -> (forecaster, params);
    ``station_cluster``: per-station routing table)::

        server = ForecastServer.from_manifest(ckpt_root)
        server.submit(x, station=17)     # routed by station 17's cluster
        server.predict(x, cluster=1)     # or routed explicitly

    The routing state lives in a swappable :class:`_Generation` snapshot:
    :meth:`reload` re-reads the (generational) routing manifest, restores the
    changed clusters' checkpoints and warms their buckets OFF the serving
    path, then atomically publishes the new snapshot — in-flight and queued
    requests keep the snapshot they were admitted under, so a hot swap drops
    nothing and no request ever observes a half-swapped server.
    :meth:`watch_manifest` runs that reload on a background poller whenever
    the manifest's generation moves.
    """

    def __init__(self, forecaster: Optional[Forecaster] = None, params=None,
                 max_batch: int = 32,
                 buckets: Optional[Sequence[int]] = None,
                 max_wait_ms: float = 2.0,
                 *,
                 models: Optional[Dict] = None,
                 station_cluster: Optional[Sequence[int]] = None,
                 station_norm: Optional[Tuple] = None,
                 shard_batch: bool = False,
                 metrics: bool = True,
                 generation: int = 0,
                 process_shard: Optional[Tuple[int, int]] = None):
        if process_shard is not None:
            idx, cnt = int(process_shard[0]), int(process_shard[1])
            if not (cnt >= 1 and 0 <= idx < cnt):
                raise ValueError(
                    f"process_shard must be (index, count) with "
                    f"0 <= index < count, got {process_shard}")
            process_shard = (idx, cnt)
        self.process_shard = process_shard
        if models is None:
            if forecaster is None or params is None:
                raise ValueError("pass (forecaster, params) or models=")
            models = {None: (forecaster, params)}
        self.buckets = tuple(sorted(set(buckets or batch_buckets(max_batch))))
        self.max_batch = self.buckets[-1]
        self.max_wait_ms = max_wait_ms
        self._shardings = None
        if shard_batch and len(jax.devices()) > 1:
            from repro.core.fl.engine import axis0_shardings
            from repro.launch.mesh import make_batch_mesh

            self._shardings = axis0_shardings("batch", mesh=make_batch_mesh())
        self._gen = _Generation(
            generation,
            {c: _ClusterEngine(fc, p, self._shardings)
             for c, (fc, p) in models.items()},
            station_cluster=station_cluster, station_norm=station_norm)
        self._manifest_source: Optional[dict] = None  # set by from_manifest
        self._reload_lock = threading.Lock()   # serializes builds + swaps
        # two-phase swap state (process-sharded serving): the built-and-warmed
        # next generation this process has announced but not yet published,
        # kept across reload() ticks so waiting on peers never rebuilds it
        self._staged_gen: Optional[_Generation] = None
        self._watch_thread: Optional[threading.Thread] = None
        self._watch_stop: Optional[threading.Event] = None
        # cluster labels -> the cluster keys of every generation published,
        # so the per-cluster tallies map back to the keys callers route by
        self._cluster_keys = {str(c): c for c in self._gen.engines}
        self._queue: "queue.Queue" = queue.Queue()
        self._worker_thread: Optional[threading.Thread] = None
        self._closed = False
        self._lifecycle = threading.Lock()  # guards _closed vs enqueue
        self._init_metrics()
        # metrics=False hides the registry (no exposition); the server still
        # counts through it, since stats and cluster_stats read it
        self.metrics: Optional[MetricsRegistry] = (
            self._registry if metrics else None)

    # --- generation snapshot (compat views) -------------------------------
    @property
    def generation(self) -> int:
        """The ACTIVE generation number (what /healthz and /metricz show)."""
        return self._gen.generation

    @property
    def engines(self) -> Dict:
        return self._gen.engines

    @property
    def station_cluster(self):
        return self._gen.station_cluster

    @property
    def station_norm(self):
        return self._gen.station_norm

    @property
    def _default(self):
        return self._gen.default

    @property
    def stats(self):
        """Server-wide tallies, read from the registry's counters (a
        read-only snapshot): requests accepted, batches dispatched, bucket
        slots padded, series served, hot swaps made."""
        return MappingProxyType({
            "requests": _total(self._m_requests),
            "batches": _total(self._m_batches),
            "padded_slots": _total(self._m_padded),
            "series_served": _total(self._m_series),
            "reloads": _total(self._m_reloads, outcome="swapped")})

    @property
    def cluster_stats(self):
        """Per-cluster requests and series served, read from the registry's
        counters (a read-only snapshot). Tallies survive swaps; every
        cluster of every generation published has an entry."""
        return MappingProxyType({
            c: {"requests": _total(self._m_requests, cluster=lbl),
                "series_served": _total(self._m_series, cluster=lbl)}
            for lbl, c in list(self._cluster_keys.items())})

    def _init_metrics(self):
        """Declare the serving metric families (catalogued in
        docs/serving.md). Hot-path recordings go through the cached label
        children, so steady-state cost is a dict hit + a locked float add."""
        m = self._registry = MetricsRegistry()
        self._m_requests = m.counter(
            "forecast_requests_total",
            "submit() requests accepted into the micro-batch queue",
            ("cluster",))
        self._m_rejected = m.counter(
            "forecast_rejected_total",
            "submit() requests failed before enqueue (never dispatched)",
            ("kind",))
        self._m_latency = m.histogram(
            "forecast_latency_seconds",
            "submit() -> resolved-future latency",
            ("cluster",), buckets=DEFAULT_LATENCY_BUCKETS)
        self._m_batches = m.counter(
            "forecast_batches_total",
            "micro-batches dispatched to a cluster engine",
            ("cluster", "shape"))
        self._m_padded = m.counter(
            "forecast_padded_slots_total",
            "bucket slots padded (wasted) in dispatched micro-batches",
            ("cluster", "shape"))
        self._m_fill = m.histogram(
            "forecast_batch_fill",
            "live-row fraction of each dispatched bucket",
            ("cluster", "shape"),
            buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))
        self._m_series = m.counter(
            "forecast_series_served_total",
            "series (station-channels) forecast per cluster",
            ("cluster",))
        self._m_errors = m.counter(
            "forecast_dispatch_errors_total",
            "micro-batch dispatches that failed their whole group",
            ("cluster",))
        m.gauge("forecast_queue_depth",
                "requests waiting in the micro-batch queue",
                fn=self._queue.qsize)
        m.gauge("forecast_clusters", "restored cluster engines",
                fn=lambda: float(len(self.engines)))
        m.gauge("forecast_generation",
                "active routing-manifest generation",
                fn=lambda: float(self._gen.generation))
        if self.process_shard is not None:
            m.gauge("forecast_process_index",
                    "this server's shard index (process-sharded serving)",
                    fn=lambda: float(self.process_shard[0]))
            m.gauge("forecast_process_count",
                    "total serving processes the cluster set is sharded over",
                    fn=lambda: float(self.process_shard[1]))
        self._m_reloads = m.counter(
            "forecast_reloads_total",
            "manifest hot-swaps by outcome (swapped/stale/waiting/error)",
            ("outcome",))
        gc_runs = m.counter(
            "forecast_gc_collections_total",
            "garbage collections in the serving process while it is started",
            ("generation",))
        gc_pause = m.counter(
            "forecast_gc_pause_seconds_total",
            "seconds those collections held the interpreter lock",
            ("generation",))
        # children made up front: the hook then never takes a family lock
        # (a collection can start while this thread holds one)
        gens = range(len(gc.get_count()))
        self._gc_sink = ({g: gc_runs.labels(g) for g in gens},
                         {g: gc_pause.labels(g) for g in gens})

    def metrics_text(self) -> str:
        """Prometheus text exposition of the server registry (the body the
        gateway serves at GET /metricz); empty with ``metrics=False``."""
        return "" if self.metrics is None else self.metrics.expose()

    # --- restore ----------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, ckpt_dir: str, step: Optional[int] = None,
                        comm_bits: int = 32, **kw) -> "ForecastServer":
        """Single-model server from one ``load_forecaster`` checkpoint;
        ``comm_bits=16`` restores a bf16-quantized payload."""
        fc, params, _ = load_forecaster(ckpt_dir, step=step,
                                        comm_bits=comm_bits)
        return cls(fc, params, **kw)

    @classmethod
    def from_manifest(cls, ckpt_root: str, policy: Optional[str] = None,
                      step: Optional[int] = None, comm_bits: int = 32,
                      denormalize: bool = False,
                      process_shard: Optional[Tuple[int, int]] = None,
                      **kw) -> "ForecastServer":
        """ROUTED server from ``run_experiment``'s routing manifest: restores
        every cluster checkpoint of ``policy`` (the manifest's only policy by
        default) and routes requests via its ``station_cluster`` table.

        ``denormalize=True`` loads the manifest's per-station ``norm`` stats
        so station-routed requests are served in RAW units: the server
        applies each station's training z-norm to the incoming look-back and
        rescales the forecast back (``y * sd + mu``). Requests routed by
        explicit ``cluster=`` stay in normalized units (no station, no
        stats).

        The manifest read is GENERATIONAL (``tasks.read_routing_manifest``:
        latest complete generation wins) and the restore source is recorded,
        so :meth:`reload` / :meth:`watch_manifest` can later hot-swap the
        server to a newer generation with the same policy/step/quantization
        settings.

        ``process_shard=(index, count)`` builds one member of a
        PROCESS-SHARDED serving fleet (see docs/distributed.md): the manifest's
        sorted cluster labels are dealt round-robin across ``count`` processes
        and this server restores ONLY the clusters at positions
        ``i % count == index`` — each process holds 1/count of the model
        memory while the full routing table stays replicated, so an unowned
        station fails fast with a routing KeyError instead of silently
        serving the wrong model. :meth:`reload` then coordinates
        generation swaps across the fleet with a two-phase publish (every
        process warms the new generation and announces a ready marker in the
        manifest dir before ANY process serves it)."""
        from repro.core.tasks import read_routing_manifest

        generation, manifest = read_routing_manifest(ckpt_root)
        if denormalize and "norm" not in manifest:
            raise ValueError(
                "denormalize=True but the manifest has no 'norm' stats — "
                "re-run run_experiment(checkpoint_dir=...) to record "
                "per-station normalization")
        policy, models, sources = cls._restore_generation(
            ckpt_root, manifest, policy, step, comm_bits,
            process_shard=process_shard)
        if denormalize:
            kw["station_norm"] = (manifest["norm"]["mu"],
                                  manifest["norm"]["sd"])
        server = cls(models=models,
                     station_cluster=manifest["station_cluster"],
                     generation=generation, process_shard=process_shard, **kw)
        server._gen.sources = sources
        server._manifest_source = dict(root=ckpt_root, policy=policy,
                                       step=step, comm_bits=comm_bits,
                                       denormalize=denormalize)
        return server

    @staticmethod
    def _restore_generation(ckpt_root: str, manifest: dict,
                            policy: Optional[str], step: Optional[int],
                            comm_bits: int,
                            reuse: Optional[Dict] = None,
                            process_shard: Optional[Tuple[int, int]] = None):
        """Resolve the policy and restore its cluster checkpoints. With
        ``reuse`` (cluster -> (subdir, engine) of the LIVE generation),
        clusters whose checkpoint subdir is unchanged keep their existing
        engine object — a per-cluster retrain restores only the retrained
        cluster. With ``process_shard=(index, count)`` only the OWNED
        clusters (position ``i % count == index`` in sorted label order) are
        restored. Returns ``(policy, models_or_engines, sources)``."""
        policies = manifest["policies"]
        if policy is None:
            if len(policies) != 1:
                raise ValueError(
                    f"manifest has {sorted(policies)}; pass policy=")
            policy = next(iter(policies))
        if policy not in policies:
            raise KeyError(f"unknown policy {policy!r}; "
                           f"manifest has {sorted(policies)}")
        out, sources = {}, {}
        entries = sorted(policies[policy].items(), key=lambda kv: int(kv[0]))
        for i, (label, sub) in enumerate(entries):
            if process_shard is not None and i % process_shard[1] != process_shard[0]:
                continue   # owned by another process of the serving fleet
            c = int(label)
            sources[c] = sub
            if reuse is not None and reuse.get(c, (None,))[0] == sub:
                out[c] = reuse[c][1]   # unchanged checkpoint: keep the engine
                continue
            fc, params, _ = load_forecaster(os.path.join(ckpt_root, sub),
                                            step=step, comm_bits=comm_bits)
            out[c] = (fc, params)
        return policy, out, sources

    # --- manifest hot-swap ------------------------------------------------
    @staticmethod
    def _ready_marker(root: str, generation: int, index: int) -> str:
        """Phase-one publish marker of the two-phase process-sharded swap:
        ``<root>/.ready.g<generation>.p<index>`` announces that process
        ``index`` has BUILT AND WARMED generation ``generation`` (written via
        tmp + ``os.replace``, so peers never read a torn marker)."""
        return os.path.join(root, f".ready.g{generation:06d}.p{index}")

    def reload(self, warm_channels: Sequence[int] = (1,),
               sync_timeout_s: float = 30.0) -> bool:
        """Hot-swap to the manifest's LATEST COMPLETE GENERATION without
        dropping a single request. Returns True if a newer generation was
        published, False if the on-disk manifest is at (or behind) the
        active generation.

        The expensive work happens OFF the serving path: clusters whose
        checkpoint subdir changed are restored from disk (clusters with an
        unchanged subdir REUSE the live engine object — a per-cluster
        retrain reloads exactly one model) and every fresh engine's shape
        buckets are warmed against the NEW snapshot. Only then does the swap
        happen, as one atomic attribute store. Requests already queued carry
        their old snapshot and drain through the old engines; requests
        admitted after the store route through the new table and engines.
        Nothing in between is observable.

        On a PROCESS-SHARDED server (``from_manifest(process_shard=(i, n))``
        with n > 1) the swap is TWO-PHASE across the fleet: after building
        and warming its owned clusters this process announces a ready marker
        in the manifest dir, then publishes only once ALL n processes'
        markers for the generation exist — so no process ever serves a
        generation a peer hasn't warmed (a station rerouted to another shard
        mid-swap would hit a cold or absent model otherwise). If the peers
        have not announced within ``sync_timeout_s`` the built generation is
        KEPT STAGED (no rebuild on the next tick), the outcome is tallied as
        ``forecast_reloads_total{outcome="waiting"}`` and the server keeps
        serving the old generation — a crashed or erroring peer delays the
        fleet's swap but never poisons the processes that are up."""
        src = self._manifest_source
        if src is None:
            raise RuntimeError(
                "reload() needs a manifest-backed server "
                "(ForecastServer.from_manifest)")
        from repro.core.tasks import read_routing_manifest

        with self._reload_lock:
            generation, manifest = read_routing_manifest(src["root"])
            if generation <= self._gen.generation:
                self._m_reloads.labels("stale").inc()
                return False
            staged = self._staged_gen
            if staged is not None and staged.generation == generation:
                new_gen = staged   # already built and warmed on a prior tick
            else:
                try:
                    old = self._gen
                    reuse = {c: (old.sources.get(c), e)
                             for c, e in old.engines.items()}
                    _, restored, sources = self._restore_generation(
                        src["root"], manifest, src["policy"], src["step"],
                        src["comm_bits"], reuse=reuse,
                        process_shard=self.process_shard)
                    engines = {
                        c: (v if isinstance(v, _ClusterEngine)
                            else _ClusterEngine(v[0], v[1], self._shardings))
                        for c, v in restored.items()}
                    station_norm = None
                    if src["denormalize"]:
                        station_norm = (manifest["norm"]["mu"],
                                        manifest["norm"]["sd"])
                    new_gen = _Generation(
                        generation, engines,
                        station_cluster=manifest["station_cluster"],
                        station_norm=station_norm, sources=sources)
                    for c in engines:
                        self._cluster_keys.setdefault(str(c), c)
                    fresh = [c for c, e in engines.items()
                             if e is not old.engines.get(c)]
                    for ch in warm_channels:
                        for c in fresh:
                            L = engines[c].forecaster.cfg.look_back
                            for b in self.buckets:
                                self._run_bucket(
                                    np.zeros((b, ch, L), np.float32), c,
                                    new_gen)
                except Exception:
                    self._m_reloads.labels("error").inc()
                    raise
            if self.process_shard is not None and self.process_shard[1] > 1:
                if not self._announce_and_await(src["root"], generation,
                                                sync_timeout_s):
                    self._staged_gen = new_gen   # reuse next tick, no rebuild
                    self._m_reloads.labels("waiting").inc()
                    return False
            self._gen = new_gen   # THE swap: one atomic attribute store
            self._staged_gen = None
            self._m_reloads.labels("swapped").inc()
        return True

    def _announce_and_await(self, root: str, generation: int,
                            sync_timeout_s: float) -> bool:
        """Phase one of the cross-process swap: write THIS process's ready
        marker for ``generation``, then poll for every peer's. True once all
        ``count`` markers exist (everyone warmed — safe to publish), False on
        timeout (keep serving the old generation, retry next tick)."""
        from repro.checkpoint import atomic_write_bytes

        idx, cnt = self.process_shard
        atomic_write_bytes(self._ready_marker(root, generation, idx),
                           json.dumps({"generation": generation,
                                       "process": idx}).encode())
        deadline = time.perf_counter() + sync_timeout_s
        while True:
            missing = [p for p in range(cnt)
                       if not os.path.exists(
                           self._ready_marker(root, generation, p))]
            if not missing:
                return True
            if time.perf_counter() >= deadline:
                return False
            time.sleep(min(0.05, sync_timeout_s / 10))

    def watch_manifest(self, interval_s: float = 2.0,
                       sync_timeout_s: float = 30.0):
        """Background poller: every ``interval_s`` seconds, :meth:`reload`
        if the manifest's generation moved past the active one. The manifest
        writer publishes atomically (snapshot file + ``os.replace``), so the
        poller can never read a torn manifest; transient filesystem/restore
        errors are tallied (``forecast_reloads_total{outcome="error"}``) and
        retried next tick. On a process-sharded server ``sync_timeout_s`` is
        forwarded to :meth:`reload`'s two-phase peer wait. Idempotent;
        stopped by :meth:`unwatch` or :meth:`close`."""
        if self._manifest_source is None:
            raise RuntimeError(
                "watch_manifest() needs a manifest-backed server "
                "(ForecastServer.from_manifest)")
        if self._watch_thread is not None:
            return self._watch_thread
        self._watch_stop = threading.Event()

        def _poll():
            while not self._watch_stop.wait(interval_s):
                try:
                    self.reload(sync_timeout_s=sync_timeout_s)
                except Exception:
                    pass  # already tallied as outcome="error"; retry next tick

        self._watch_thread = threading.Thread(
            target=_poll, daemon=True, name="manifest-watch")
        self._watch_thread.start()
        return self._watch_thread

    def unwatch(self):
        """Stop the :meth:`watch_manifest` poller (no-op when not running)."""
        if self._watch_thread is None:
            return
        self._watch_stop.set()
        self._watch_thread.join()
        self._watch_thread = None
        self._watch_stop = None

    # --- routing ----------------------------------------------------------
    @property
    def forecaster(self) -> Forecaster:
        """The first engine's forecaster (all clusters of one experiment
        share the config geometry)."""
        return next(iter(self.engines.values())).forecaster

    @property
    def params(self):
        return next(iter(self.engines.values())).params

    def resolve_cluster(self, station=None, cluster=None):
        """Explicit ``cluster`` wins; else ``station`` routes through the
        manifest's ``station_cluster`` table; else the single-model default.
        Raises for unroutable requests (unknown station / cluster without a
        checkpoint / routed server with neither key). Always answers from the
        CURRENT generation snapshot."""
        return self._resolve(self._gen, station=station, cluster=cluster)

    @staticmethod
    def _resolve(gen: "_Generation", station=None, cluster=None):
        """Route within ONE generation snapshot — a request reads its
        snapshot exactly once, so a concurrent hot swap can never half-route
        it (table from one generation, engine from another)."""
        if cluster is None and station is not None:
            if gen.station_cluster is None:
                if gen.default is not _NO_DEFAULT:  # single model: no ambiguity
                    return gen.default
                raise ValueError(
                    "no routing table: build the server with from_manifest "
                    "(or station_cluster=) to route by station")
            s = int(station)
            if not 0 <= s < len(gen.station_cluster):
                raise KeyError(f"unknown station {s}: manifest covers "
                               f"{len(gen.station_cluster)} stations")
            cluster = gen.station_cluster[s]
        if cluster is None and None not in gen.engines:
            if gen.default is _NO_DEFAULT:
                raise ValueError(
                    "multi-cluster server: pass station= or cluster= "
                    f"(have {sorted(gen.engines, key=str)})")
            cluster = gen.default
        if cluster not in gen.engines:
            raise KeyError(f"no checkpoint for cluster {cluster!r} "
                           f"(have {sorted(gen.engines, key=str)})")
        return cluster

    @staticmethod
    def _norm_for_gen(gen: "_Generation", station):
        """The (mu, sd) pair a station-routed RAW request is rescaled with,
        or None when raw serving is off / the request has no station. Called
        after ``_resolve``, which already rejects unknown stations
        (``station_cluster`` and the stats tables cover the same fleet)."""
        if gen.station_norm is None or station is None:
            return None
        mu, sd = gen.station_norm
        s = int(station)
        if not 0 <= s < len(mu):
            raise KeyError(f"no normalization stats for station {s}: "
                           f"manifest covers {len(mu)} stations")
        return float(mu[s]), float(sd[s])

    def _norm_for(self, station):
        return self._norm_for_gen(self._gen, station)

    def routable_stations(self):
        """Stations the routing table maps to a RESTORED engine (clusters
        skipped at training time drop out); empty without a routing table."""
        if self.station_cluster is None:
            return []
        return [s for s, c in enumerate(self.station_cluster)
                if c in self.engines]

    # --- bucketed batch inference -----------------------------------------
    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _run_bucket(self, x: np.ndarray, cluster=None,
                    gen: Optional["_Generation"] = None) -> np.ndarray:
        """x: (b, M, L) with b <= max_batch. Pads to the bucket, runs the
        cluster engine's donated-output step, unpads. ``gen`` pins the
        generation the request was admitted under (queued requests drain
        through THEIR engines even after a swap); default is the current."""
        gen = gen or self._gen
        b, M, L = x.shape
        cluster = self._resolve(gen, cluster=cluster)
        bucket = self.bucket_for(b)
        if b < bucket:
            x = np.concatenate(
                [x, np.zeros((bucket - b, M, L), np.float32)], axis=0)
        result = gen.engines[cluster].run_padded(x, b)
        lbl = (str(cluster), f"{M}x{L}")
        self._m_batches.labels(*lbl).inc()
        self._m_padded.labels(*lbl).inc(bucket - b)
        self._m_fill.labels(*lbl).observe(b / bucket)
        self._m_series.labels(str(cluster)).inc(b * M)
        return result

    def predict(self, x, station=None, cluster=None) -> np.ndarray:
        """x: (b, M, L) for any b (chunked over max_batch) -> (b, M, T),
        served by the routed cluster's model. With the server's per-station
        norm stats loaded (``from_manifest(denormalize=True)``), a
        station-routed ``x`` is RAW: normalized in, forecast rescaled out.
        An explicit ``cluster=`` wins the route AND keeps the request in
        normalized units — station stats apply only to station-routed
        requests."""
        return self._predict(self._gen, x, station=station, cluster=cluster)

    def _predict(self, gen: "_Generation", x, station=None,
                 cluster=None) -> np.ndarray:
        if cluster is not None:
            station = None  # explicit cluster: no station routing, no rescale
        cluster = self._resolve(gen, station=station, cluster=cluster)
        norm = self._norm_for_gen(gen, station)
        if norm is not None:
            mu, sd = norm
            y = self._predict(gen, (np.asarray(x, np.float32) - mu) / sd,
                              cluster=cluster)
            return y * sd + mu
        x = np.asarray(x, np.float32)
        if x.ndim == 2:  # single request (M, L)
            return self._predict(gen, x[None], cluster=cluster)[0]
        look_back = gen.engines[cluster].forecaster.cfg.look_back
        assert x.ndim == 3 and x.shape[-1] == look_back, x.shape
        outs = [self._run_bucket(x[i : i + self.max_batch], cluster, gen)
                for i in range(0, x.shape[0], self.max_batch)]
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)

    def warmup(self, channels: int = 1, buckets: Optional[Sequence[int]] = None,
               gen: Optional["_Generation"] = None):
        """Pre-compile the step for each bucket of EVERY cluster engine
        (compilation off the serving path). ``reload`` passes the NEW
        generation here before publishing it, so a hot swap never pays a
        compile/first-dispatch on the serving path either."""
        gen = gen or self._gen
        for c, eng in gen.engines.items():
            L = eng.forecaster.cfg.look_back
            for b in buckets or self.buckets:
                self._run_bucket(np.zeros((b, channels, L), np.float32), c,
                                 gen)

    # --- micro-batching request queue -------------------------------------
    def start(self):
        """Spawn the coalescing worker; ``submit`` becomes non-blocking."""
        if self._closed:
            raise RuntimeError("ForecastServer is closed")
        if self._worker_thread is not None:
            return
        _gc_watch(self._gc_sink, True)
        self._worker_thread = threading.Thread(target=self._worker, daemon=True)
        self._worker_thread.start()

    def submit(self, x, station=None, cluster=None) -> Future:
        """Enqueue ONE request (M, L); resolves to its (M, T) forecast from
        the routed cluster's model. With the server's per-station norm stats
        loaded (``from_manifest(denormalize=True)``), a station-routed ``x``
        is RAW: normalized before coalescing, and the resolved forecast is
        rescaled to the station's units (``y * sd + mu``). An explicit
        ``cluster=`` wins the route AND keeps the request in normalized units
        (same contract as :meth:`predict`).

        A malformed request (wrong rank or look-back length) or an unroutable
        one (unknown station, cluster without a checkpoint) fails ONLY its
        own future — it never reaches the queue, so the micro-batch it would
        have been coalesced into is unaffected.
        """
        with TraceAnnotation("serve.submit"):
            return self._submit(x, station, cluster)

    def _submit(self, x, station, cluster) -> Future:
        fut: Future = Future()
        gen = self._gen  # ONE snapshot read: route, norm and serve cohere
        try:
            if cluster is not None:
                station = None  # explicit cluster: no station stats
            cluster = self._resolve(gen, station=station, cluster=cluster)
            L = gen.engines[cluster].forecaster.cfg.look_back
            x = np.asarray(x, np.float32)
            if x.ndim != 2 or x.shape[1] != L:
                raise ValueError(
                    f"request must be (M, look_back={L}), got {x.shape}")
            norm = self._norm_for_gen(gen, station)
            if norm is not None:
                x = (x - norm[0]) / norm[1]
        except Exception as exc:  # incl. ragged/non-numeric asarray failures
            kind = "unroutable" if isinstance(exc, KeyError) else "malformed"
            self._m_rejected.labels(kind).inc()
            fut.set_exception(exc)
            return fut
        with self._lifecycle:
            # closed-check and enqueue are ONE atomic step: a request can
            # never slip into the queue between close() draining it and the
            # flag flipping — submit-after-close fails the future promptly
            # instead of leaving a waiter hanging on a dead worker
            if self._closed:
                fut.set_exception(RuntimeError(
                    "ForecastServer is closed; request was not enqueued"))
                return fut
            self._m_requests.labels(str(cluster)).inc()
            if self.metrics is not None:  # stats does not read latency
                lat = self._m_latency.labels(str(cluster))
                t0 = time.perf_counter()
                fut.add_done_callback(
                    lambda f, lat=lat, t0=t0: lat.observe(
                        time.perf_counter() - t0))
            # the queue item CARRIES its generation: a hot swap between
            # enqueue and dispatch must serve this request with the engines
            # it was admitted under (old generations drain, never drop)
            self._queue.put((gen, cluster, x, fut))
        if norm is None:
            return fut
        mu, sd = norm
        outer: Future = Future()

        def _rescale(f, outer=outer, mu=mu, sd=sd):
            if f.cancelled():
                outer.cancel()
                return
            exc = f.exception()
            if exc is not None:
                _safe_set(outer, exc=exc)
            else:
                _safe_set(outer, f.result() * sd + mu)

        fut.add_done_callback(_rescale)
        return outer

    def stop(self):
        """Pause the worker: it drains its current coalescing window, then
        exits; ``start()`` resumes. Requests enqueued while stopped wait in
        the queue (use :meth:`close` to fail them instead)."""
        if self._worker_thread is None:
            return
        self._queue.put(_STOP)
        self._worker_thread.join()
        self._worker_thread = None
        _gc_watch(self._gc_sink, False)

    def close(self):
        """TERMINAL shutdown: stop the worker and fail EVERY still-pending
        future with ``RuntimeError`` — a blocked ``.result(timeout=...)``
        raises promptly instead of hanging forever on a server that will
        never serve it. Requests submitted after close() fail their future
        the same way. Idempotent; ``predict`` (the synchronous direct path)
        keeps working on the restored engines."""
        with self._lifecycle:
            if self._closed:
                return
            self._closed = True
        self.unwatch()
        self.stop()
        # the worker is gone and _closed bars new enqueues, so whatever is
        # left in the queue would hang its waiters forever — fail them all
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is _STOP:
                continue
            _safe_set(item[3], exc=RuntimeError(
                "ForecastServer closed before this request was served"))

    def _run_group(self, items):
        """Serve one coalesced (generation, cluster, shape) group with the
        GENERATION THE REQUESTS WERE ADMITTED UNDER; a failure propagates to
        THIS group's waiters only. Futures are resolved through ``_safe_set``
        so a waiter that cancelled (gateway deadline) can't blow up the
        worker thread."""
        gen, cluster = items[0][0], items[0][1]
        with TraceAnnotation("serve.group", cluster=str(cluster),
                             bucket=self.bucket_for(len(items)),
                             rows=len(items)):
            try:
                with TraceAnnotation("serve.assemble"):
                    x = np.stack([x for _, _, x, _ in items])
                ys = self._predict(gen, x, cluster=cluster)
            except Exception as exc:
                self._m_errors.labels(str(cluster)).inc()
                for _, _, _, fut in items:
                    _safe_set(fut, exc=exc)
                return
            with TraceAnnotation("serve.resolve"):
                for (_, _, _, fut), y in zip(items, ys):
                    _safe_set(fut, y)

    def _worker(self):
        while True:
            with TraceAnnotation("serve.queue_wait"):
                item = self._queue.get()
            if item is _STOP:
                return
            # coalesced requests are heterogeneous in routed cluster AND in
            # (M, L) shape; np.stack over the raw batch would raise and fail
            # EVERY waiter, so the window coalesces per (cluster, shape)
            # GROUP and runs one bucket per group. The max_batch cap bounds
            # the bucket ONE STEP runs, so it too applies per group, not to
            # the window total — a total cap chronically ran half-empty
            # buckets under routed traffic (each step's fixed dispatch cost
            # dominates on small models; ~2.5x routed-queue throughput from
            # this on the 2-cluster bench). A group that fills dispatches
            # IMMEDIATELY while the remaining (e.g. minority-cluster) groups
            # keep coalescing until the deadline or the window cap.
            # Single-model/single-shape traffic degenerates to the seed
            # behavior exactly: one group, dispatched at max_batch. Groups
            # additionally split by GENERATION: a swap mid-window must not
            # stack old- and new-generation requests into one dispatch.
            def key_of(it):
                return (it[0].generation, it[1], it[2].shape)

            groups: dict = {}
            groups.setdefault(key_of(item), []).append(item)
            total = 1
            cap = self.max_batch * max(1, len(self.engines))
            deadline = time.perf_counter() + self.max_wait_ms / 1e3
            stopping = False
            with TraceAnnotation("serve.coalesce"):
                while total < cap:
                    for k in [k for k, v in groups.items()
                              if len(v) >= self.max_batch]:
                        self._run_group(groups.pop(k))
                    left = deadline - time.perf_counter()
                    if left <= 0:
                        break
                    try:
                        nxt = self._queue.get(timeout=left)
                    except queue.Empty:
                        break
                    if nxt is _STOP:
                        stopping = True
                        break
                    groups.setdefault(key_of(nxt), []).append(nxt)
                    total += 1
            for items in groups.values():
                self._run_group(items)
            if stopping:
                return


def serve_requests(server: ForecastServer, requests: int, channels: int,
                   seed: int = 0, use_queue: bool = True,
                   stations: Optional[Sequence[int]] = None) -> dict:
    """Push ``requests`` synthetic (M, L) queries through the server and
    report wall time + forecasts/sec (a forecast = one series' horizon).
    ``stations`` routes request i to ``stations[i % len(stations)]`` (routed
    servers); default is the single-model path."""
    L = server.forecaster.cfg.look_back
    rng = np.random.default_rng(seed)
    xs = rng.standard_normal((requests, channels, L)).astype(np.float32)
    sts = None if stations is None else [int(s) for s in stations]
    if sts is not None and not sts:
        raise ValueError(
            "stations is empty — no routable stations (every cluster in the "
            "manifest skipped or missing a checkpoint?)")
    station_of = (lambda i: None) if sts is None else (lambda i: sts[i % len(sts)])
    server.warmup(channels)
    base = dict(server.stats)  # exclude warmup batches from the report
    t0 = time.perf_counter()
    if use_queue:
        server.start()
        futs = [server.submit(x, station=station_of(i))
                for i, x in enumerate(xs)]
        ys = [f.result(timeout=60) for f in futs]
        server.stop()
    elif sts is None:
        ys = list(server.predict(xs))
    else:
        # direct routed mode: one batched predict per cluster
        ys = [None] * requests
        by_cluster: dict = {}
        for i in range(requests):
            c = server.resolve_cluster(station=station_of(i))
            by_cluster.setdefault(c, []).append(i)
        for c, idxs in by_cluster.items():
            out = server.predict(xs[idxs], cluster=c)
            for i, y in zip(idxs, out):
                ys[i] = y
    secs = time.perf_counter() - t0
    assert len(ys) == requests and ys[0].shape == (
        channels, server.forecaster.cfg.horizon)
    return {
        "requests": requests,
        "channels": channels,
        "seconds": secs,
        "forecasts_per_sec": requests * channels / secs,
        "batches": server.stats["batches"] - base["batches"],
        "padded_slots": server.stats["padded_slots"] - base["padded_slots"],
        "mode": "queue" if use_queue else "direct",
        "routed": sts is not None,
    }


def stream_evaluate(server: ForecastServer, task, series=None,
                    max_windows: Optional[int] = None,
                    timeout: Optional[float] = 120.0,
                    include_metrics: bool = False) -> dict:
    """Streaming/continuous evaluation: replay the task's HELD-OUT test
    windows through the micro-batching queue in arrival order (every
    station's window w before any station's window w+1 — the request pattern
    of a live day) and track per-cluster ONLINE RMSE as the forecasts
    resolve.

    Each window submits its look-back as a single-channel ``(1, L)`` request
    routed by the window's ORIGINAL station id (cleaning drops stations, so
    routing uses ``client_data``'s kept-index map); its horizon is the truth
    the resolved forecast is scored against. Stations whose cluster has no
    checkpoint are counted in ``unroutable`` and excluded from the RMSE;
    any OTHER failure (e.g. a task/checkpoint look-back mismatch) raises.

    ``timeout`` is PER REQUEST: a future that hasn't resolved in time is
    skipped and tallied in ``timed_out`` instead of stalling the whole
    replay on one stuck request (``timeout=None`` waits forever — the old
    behavior). ``include_metrics=True`` attaches the server's Prometheus
    exposition after the replay as ``metrics_text`` — the same body the
    gateway serves at ``GET /metricz``.

    The replay windows come from ``client_data`` already NORMALIZED, so the
    evaluation always runs in normalized units: on a raw-serving server
    (``from_manifest(denormalize=True)``) routable requests are submitted by
    the station's resolved CLUSTER — the route is identical, but the
    station-stats rescale (which would double-normalize these windows) does
    not apply. Same RMSE as the plain server, guarded in
    tests/test_routed_serving.py.

    Returns ``{"overall_rmse", "windows", "unroutable", "timed_out",
    "seconds", "per_cluster": {label: {"rmse", "windows"}}}``.
    """
    from concurrent.futures import TimeoutError as FutTimeout
    if series is None:
        series = task.series()
    tr, va, te, info = task.client_data(series)
    stations = np.asarray(info["kept"])
    L, T = task.look_back, task.horizon
    n_win = te.shape[1] if max_windows is None else min(max_windows, te.shape[1])

    def cluster_of(s: int):
        """The cluster that will actually serve station ``s`` — the server's
        own routing, so RMSE attribution can never drift from it. None for
        unroutable stations (their futures fail and are tallied anyway)."""
        try:
            return server.resolve_cluster(station=s)
        except (KeyError, ValueError):
            return None

    server.warmup(channels=1)  # replay buckets compile OFF the timed path
    running = server._worker_thread is not None
    if not running:
        server.start()
    pending = []  # (cluster, truth, future)
    t0 = time.perf_counter()
    try:
        for w in range(n_win):
            for k, s in enumerate(np.asarray(stations).tolist()):
                x = te[k, w, :L][None].astype(np.float32)      # (1, L)
                c = cluster_of(s)
                # normalized replay windows: on a raw-serving server submit by
                # resolved cluster (same route, no station-stats rescale);
                # unroutable stations (c is None) still go by station so the
                # routing KeyError fails their future and is tallied below
                fut = (server.submit(x, cluster=c)
                       if server.station_norm is not None and c is not None
                       else server.submit(x, station=s))
                pending.append((c, te[k, w, L:], fut))
        sse: dict = {}
        cnt: dict = {}
        unroutable = 0
        timed_out = 0
        for c, y_true, fut in pending:
            try:
                y_hat = fut.result(timeout=timeout)[0]         # (T,)
            except KeyError:      # routing failure ONLY; shape errors raise
                unroutable += 1
                continue
            except FutTimeout:    # one stuck request must not stall the replay
                timed_out += 1
                continue
            err = float(np.sum((np.asarray(y_hat, np.float64)
                                - np.asarray(y_true, np.float64)) ** 2))
            sse[c] = sse.get(c, 0.0) + err
            cnt[c] = cnt.get(c, 0) + 1
    finally:
        if not running:
            server.stop()
    secs = time.perf_counter() - t0
    per_cluster = {c: {"rmse": float(np.sqrt(sse[c] / (cnt[c] * T))),
                       "windows": cnt[c]} for c in sorted(cnt, key=str)}
    total_cnt = sum(cnt.values())
    rep = {
        "overall_rmse": (float(np.sqrt(sum(sse.values()) / (total_cnt * T)))
                         if total_cnt else float("nan")),
        "windows": total_cnt,
        "unroutable": unroutable,
        "timed_out": timed_out,
        "seconds": secs,
        "per_cluster": per_cluster,
    }
    if include_metrics:
        rep["metrics_text"] = server.metrics_text()
    return rep


def main():
    ap = argparse.ArgumentParser(
        description="restore FL forecaster checkpoints and serve them")
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--ckpt-dir", help="single-model checkpoint dir")
    src.add_argument("--manifest",
                     help="experiment root containing routing.json "
                          "(multi-cluster routed serving)")
    ap.add_argument("--policy", default=None,
                    help="grid policy to serve from a multi-policy manifest")
    ap.add_argument("--step", type=int, default=None)
    ap.add_argument("--comm-bits", type=int, default=32, choices=(8, 16, 32),
                    help="16 = bf16-quantized restore, 8 = int8 + per-leaf "
                         "scale restore (FLConfig.comm_bits mirrored on the "
                         "inference side; validated here so a bad width "
                         "fails at the CLI, not deep inside restore)")
    ap.add_argument("--shard-batch", action="store_true",
                    help="shard each bucket's batch axis over local devices")
    ap.add_argument("--denormalize", action="store_true",
                    help="serve station-routed requests in RAW units via the "
                         "manifest's per-station norm stats (--manifest only)")
    ap.add_argument("--process-shard", default=None, metavar="I/N",
                    help="serve shard I of an N-process fleet: restore only "
                         "the clusters at sorted positions i %% N == I "
                         "(--manifest only; e.g. --process-shard 0/2)")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--channels", type=int, default=3)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--queue", action=argparse.BooleanOptionalAction,
                    default=True, help="micro-batching queue vs direct batches")
    args = ap.parse_args()
    enable_compile_cache()

    kw = dict(max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
              shard_batch=args.shard_batch)
    if args.process_shard is not None and not args.manifest:
        ap.error("--process-shard requires --manifest")
    process_shard = None
    if args.process_shard is not None:
        try:
            i, n = args.process_shard.split("/")
            process_shard = (int(i), int(n))
        except ValueError:
            ap.error(f"--process-shard wants I/N, got {args.process_shard!r}")
    if args.manifest:
        server = ForecastServer.from_manifest(
            args.manifest, policy=args.policy, step=args.step,
            comm_bits=args.comm_bits, denormalize=args.denormalize,
            process_shard=process_shard, **kw)
        stations = server.routable_stations()
        print(f"restored {len(server.engines)} cluster models "
              f"({server.forecaster.name}, {server.forecaster.num_params():,} "
              f"params each) from {args.manifest}; routing "
              f"{len(stations)}/{len(server.station_cluster)} stations")
    else:
        server = ForecastServer.from_checkpoint(
            args.ckpt_dir, step=args.step, comm_bits=args.comm_bits, **kw)
        stations = None
        fc = server.forecaster
        print(f"restored {fc.name} ({fc.num_params():,} params) "
              f"from {args.ckpt_dir}")
    rep = serve_requests(server, args.requests, args.channels,
                         use_queue=args.queue, stations=stations)
    print(f"served {rep['requests']} requests x {rep['channels']} series in "
          f"{rep['seconds']:.3f}s -> {rep['forecasts_per_sec']:.0f} "
          f"forecasts/s ({rep['batches']} batches, "
          f"{rep['padded_slots']} padded slots, {rep['mode']}"
          f"{', routed' if rep['routed'] else ''})")


if __name__ == "__main__":
    main()
