"""Streaming AMC inference engines (sync baseline + async serving tier).

Mirrors the accelerator's deployment mode: a continuous stream of I/Q
frames is sigma-delta encoded and classified through the unified
``SNNProgram`` layer graph.  Two engines share one stats/counting core:

* :class:`AMCServeEngine` — the original synchronous per-chunk loop
  (fixed-size batches, numpy encode on the host).  Kept as the serving
  baseline and for callers that want a blocking, single-threaded path.
* :class:`AsyncAMCServeEngine` — the production-style tier: a request
  queue feeds a dynamic micro-batcher (size/timeout flush, tail padded to
  fixed bucket shapes so the jitted program never re-specializes — the
  software form of the paper's fixed iteration schedule); worker loops fan
  batches across devices via ``shard_map`` over a 1-D data mesh; the
  Σ-Δ encoder is traced into the compiled step; and a warmup-race
  autotuner picks the fastest backend for the serving batch shape at bind
  time (``backend="auto"``).

Both engines bind through :func:`repro.plan.compile_plan`, so COO kernels
and schedules come from the content-addressed plan cache — an engine
restart on unchanged weights rebuilds nothing (the software form of the
paper's offline precomputation).  The async tier additionally supports
``backend="per-layer"``: a layer-by-layer backend race whose winning
heterogeneous assignment is served through the fused single-scan
streaming executor.

Both engines report the cost-model counters (accumulations, fetched bits)
that the power model consumes, which backend served each batch, and —
new in the async tier era — per-request latency percentiles, sampled
queue depths, and padded-frame counts.

The async engine serves from a **version table** (label ->
:class:`BoundVersion`, each with its own compiled step and
:class:`ServeStats`): :meth:`~AsyncAMCServeEngine.bind_version` compiles
a new model off the hot path, :meth:`~AsyncAMCServeEngine.swap_to` flips
the primary atomically between micro-batches, and
:meth:`~AsyncAMCServeEngine.set_router` splits traffic across versions —
the hooks :mod:`repro.deploy` (registry / hot-swap / canary monitor)
drives.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.cost_model import bits_fetched, fc_wm_counts, goap_conv_counts
from repro.core.saocds import max_pool_spikes, pad_same, saocds_conv_layer
from repro.core.sparse_format import weight_mask_from_dense
from repro.data.pipeline import sigma_delta_encode_batch, sigma_delta_encode_np
from repro.models.graph import KIND_CONV, compile_snn
from repro.models.snn import SNNConfig, sparsify_params
from repro.plan import compile_plan
from repro.serve.autotune import (
    AutotuneReport,
    PerLayerAutotuneReport,
    autotune_backend,
    autotune_per_layer,
)
from repro.obs.activity import ActivityObserver
from repro.obs.metrics import default_registry
from repro.obs.trace import (
    begin_trace,
    install_process_telemetry,
    span,
    tadd,
    tfinish,
)
from repro.serve.batcher import EngineClosed, MicroBatcher, QueueFull

__all__ = ["AMCServeEngine", "AsyncAMCServeEngine", "ServeStats",
           "BoundVersion", "default_workers"]

#: Serving threads, and so batches in flight, when the step runs on an
#: accelerator: one thread gathers, puts and dispatches the next batch
#: while another waits for the device's work on the current one.
ACCELERATOR_WORKERS = 2


def default_workers(devices) -> int:
    """Serving threads for a step that runs on ``devices``.

    Two on an accelerator, where the step runs asynchronously on hardware
    the host does not compute on, so the next batch's host work can
    overlap the device's; one on the CPU, where the "device" is the
    host's own cores and a second batch only competes for them.
    """
    platforms = {d.platform for d in devices}
    return 1 if platforms <= {"cpu"} else ACCELERATOR_WORKERS


@dataclasses.dataclass
class ServeStats:
    # Sample histories are bounded: a long-lived tier must not leak memory,
    # so percentiles/means are over the most recent MAX_SAMPLES entries.
    MAX_SAMPLES = 65536

    requests: int = 0
    batches: int = 0
    accumulations: int = 0
    fetched_bits: int = 0
    wall_s: float = 0.0
    backend: str = ""
    batch_backends: List[str] = dataclasses.field(default_factory=list)
    backend_batch_totals: Dict[str, int] = dataclasses.field(default_factory=dict)
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    queue_depths: List[int] = dataclasses.field(default_factory=list)
    padded_frames: int = 0

    def record_batch(self, backend: str, queue_depth: Optional[int] = None,
                     padded: int = 0) -> None:
        """Account one served batch (exact totals + bounded history)."""
        self.batches += 1
        self.padded_frames += padded
        self.backend_batch_totals[backend] = (
            self.backend_batch_totals.get(backend, 0) + 1)
        self.batch_backends.append(backend)
        if len(self.batch_backends) > self.MAX_SAMPLES:
            del self.batch_backends[: -self.MAX_SAMPLES]
        if queue_depth is not None:
            self.queue_depths.append(queue_depth)
            if len(self.queue_depths) > self.MAX_SAMPLES:
                del self.queue_depths[: -self.MAX_SAMPLES]

    def record_latencies(self, values) -> None:
        """Append per-request latencies, keeping the window bounded."""
        self.latencies_s.extend(values)
        if len(self.latencies_s) > self.MAX_SAMPLES:
            del self.latencies_s[: -self.MAX_SAMPLES]

    def throughput_samples_per_s(self, frame_len: int = 128) -> float:
        if self.wall_s == 0:
            return 0.0
        return self.requests * frame_len / self.wall_s

    def throughput_fps(self) -> float:
        """Requests (frames) classified per wall second."""
        return self.requests / self.wall_s if self.wall_s else 0.0

    # -- latency percentiles ------------------------------------------------

    def latency_percentile(self, q: float) -> float:
        if not self.latencies_s:
            return 0.0
        return float(np.percentile(self.latencies_s, q))

    @property
    def p50_ms(self) -> float:
        return self.latency_percentile(50.0) * 1e3

    @property
    def p95_ms(self) -> float:
        return self.latency_percentile(95.0) * 1e3

    @property
    def p99_ms(self) -> float:
        return self.latency_percentile(99.0) * 1e3

    def backend_batch_counts(self) -> Dict[str, int]:
        """Exact per-backend batch totals (survive the history trimming)."""
        if self.backend_batch_totals:
            return dict(self.backend_batch_totals)
        return dict(Counter(self.batch_backends))  # directly-built stats

    def mean_queue_depth(self) -> float:
        return float(np.mean(self.queue_depths)) if self.queue_depths else 0.0

    def summary(self) -> dict:
        """JSON-ready digest (what BENCH_serve.json records)."""
        return {
            "requests": self.requests,
            "batches": self.batches,
            "backend": self.backend,
            "backend_batch_counts": self.backend_batch_counts(),
            "throughput_fps": self.throughput_fps(),
            "throughput_samples_per_s": self.throughput_samples_per_s(),
            "p50_ms": self.p50_ms,
            "p95_ms": self.p95_ms,
            "p99_ms": self.p99_ms,
            "mean_queue_depth": self.mean_queue_depth(),
            "padded_frames": self.padded_frames,
            "accumulations": self.accumulations,
            "fetched_bits": self.fetched_bits,
            "wall_s": self.wall_s,
        }


def _fail_future(fut, err: BaseException) -> None:
    """set_exception tolerant of callers that cancelled or already-done."""
    if fut.done():
        return
    try:
        fut.set_exception(err)
    except Exception:  # noqa: BLE001 — lost a cancel race; nothing to do
        pass


def _quant_fn_for(lsq_scales, quant_bits: int, backend=None):
    """Fresh per-bind quant closure for a backend assignment.

    Fixed assignments always get a :class:`repro.fixed.FixedQuantFn`
    (which calibrates per layer when no LSQ state exists) so the integer
    datapath has a step size to fold; float assignments keep the classic
    behavior — trained fake-quant with LSQ state, None without.
    """
    from repro.fixed import serving_quant_fn

    return serving_quant_fn(lsq_scales, quant_bits, assignment=backend)


def _uses_fixed(backend) -> bool:
    from repro.fixed import assignment_uses_fixed

    return assignment_uses_fixed(backend)


def count_batch_activity(stats: ServeStats, sparse, frames: np.ndarray,
                         cfg: SNNConfig) -> None:
    """Exact event counts through the conv stack (cost-model hooks).

    ``frames``: (B, T, IC, L) encoded spikes, **real rows only** — padded
    tail rows must be stripped by the caller so padding never leaks into
    the activity stats.
    """
    # the WM layout depends only on the fixed weights — build it once per
    # batch, not once per frame (counting the dominant FC is enough)
    wm = weight_mask_from_dense(np.asarray(sparse["fc"][0]["w"]))
    for b in range(frames.shape[0]):
        x = frames[b]  # (T, IC, L)
        for layer in sparse["conv"]:
            coo = layer["coo"]
            padded = np.asarray(pad_same(jnp.asarray(x), coo.kw))
            c = goap_conv_counts(padded, coo)
            stats.accumulations += c.accumulations
            stats.fetched_bits += bits_fetched(c)
            # advance the stream (cheap dense emulation for counting)
            out, _ = saocds_conv_layer(jnp.asarray(padded), coo, layer["lif"])
            x = np.asarray(max_pool_spikes(out, cfg.pool))
        c = fc_wm_counts(x.reshape(x.shape[0], -1), wm)
        stats.accumulations += c.accumulations
        stats.fetched_bits += bits_fetched(c)


class AMCServeEngine:
    """Synchronous per-chunk serving loop (the pre-tier baseline)."""

    def __init__(
        self,
        params,
        cfg: SNNConfig,
        masks=None,
        batch_size: int = 32,
        count_activity: bool = False,
        backend: str = "goap",
        lsq_scales=None,
        quant_bits: int = 16,
    ):
        self.cfg = cfg
        self.batch_size = batch_size
        self.count_activity = count_activity
        self.backend = backend
        self.program = compile_snn(cfg)
        # COO form only feeds the activity-counting hooks
        self.sparse = sparsify_params(params, masks) if count_activity else None
        self.stats = ServeStats(backend=backend)
        # precompiled plan: COO/schedule artifacts come from the content-
        # addressed cache, so engine restarts on unchanged weights rebuild
        # nothing (the software form of the paper's offline precomputation)
        self.plan = compile_plan(self.program, params, masks=masks,
                                 quant_fn=_quant_fn_for(lsq_scales,
                                                        quant_bits,
                                                        backend),
                                 assignment=backend)
        self._fwd = jax.jit(self.plan.preferred_batch())

    def _encode(self, chunk: np.ndarray) -> np.ndarray:
        """Host-side Σ-Δ encode; the fixed backend gets the integer path."""
        if _uses_fixed(self.backend):
            from repro.fixed.golden import golden_encode_frames

            return np.moveaxis(
                golden_encode_frames(chunk, self.cfg.timesteps), 0, 1)
        return sigma_delta_encode_np(chunk, self.cfg.timesteps)

    def classify(self, iq: np.ndarray) -> np.ndarray:
        """iq: (N, 2, L) -> predicted class ids (N,). Batches internally."""
        n = iq.shape[0]
        preds = np.empty((n,), dtype=np.int32)
        t0 = time.perf_counter()
        for s in range(0, n, self.batch_size):
            chunk = iq[s : s + self.batch_size]
            pad = self.batch_size - chunk.shape[0]
            if pad:
                chunk = np.concatenate([chunk, np.zeros((pad,) + chunk.shape[1:], chunk.dtype)])
            frames = self._encode(chunk)
            logits = np.asarray(self._fwd(jnp.asarray(frames)))
            n_real = self.batch_size - pad
            preds[s : s + n_real] = logits[:n_real].argmax(-1)
            self.stats.record_batch(self.backend, padded=pad)
            # latency is arrival (classify() start) -> chunk completion,
            # matching the async tier's enqueue->completion semantics so
            # the two engines' percentiles are directly comparable
            self.stats.record_latencies(
                [time.perf_counter() - t0] * n_real)
            if self.count_activity:
                self._count(frames[:n_real])
        self.stats.requests += n
        self.stats.wall_s += time.perf_counter() - t0
        return preds

    def _count(self, frames: np.ndarray) -> None:
        count_batch_activity(self.stats, self.sparse, frames, self.cfg)


@dataclasses.dataclass
class BoundVersion:
    """One bound model version in the async engine's serving table.

    The engine serves from a label -> ``BoundVersion`` table: the primary
    label takes all traffic unless a router (canary / A/B split) is
    installed.  Each version carries its own compiled step, plan, and
    :class:`ServeStats`, so a canary's latency and accuracy are observable
    independently of the production baseline.
    """

    label: str
    backend: str
    step: Any = dataclasses.field(repr=False)
    plan: Any = dataclasses.field(repr=False)
    sparse: Any = dataclasses.field(repr=False)
    stats: ServeStats = dataclasses.field(default_factory=ServeStats)
    # start of *this version's* serving window (earliest enqueue among the
    # requests it served) — a late-bound canary's wall_s/throughput must
    # not be diluted by traffic that predates its bind
    t_first: float = float("inf")
    # live-counter mode: the version's step returns one float32
    # (B, n_classes + n_conv) array, the logits then each conv layer's
    # accumulation counts in ``counter_names`` order, and this
    # ActivityObserver records them; None means the step returns bare
    # logits
    activity: Any = dataclasses.field(default=None, repr=False)
    counter_names: Tuple[str, ...] = ()

    def unpack(self, out: np.ndarray):
        """Host copy of one step's output -> (logits, {conv: counts}).

        The counts are None for a step without live counters; otherwise
        the logits and every count column are views of ``out``.
        """
        if self.activity is None:
            return out, None
        n_classes = out.shape[-1] - len(self.counter_names)
        return out[:, :n_classes], {
            name: out[:, n_classes + i]
            for i, name in enumerate(self.counter_names)}


class AsyncAMCServeEngine:
    """Async sharded serving tier: queue -> micro-batcher -> worker loops.

    Usage::

        engine = AsyncAMCServeEngine(params, cfg, masks=masks,
                                     backend="auto", max_batch=64)
        fut = engine.submit(iq_frame)        # (2, L) -> future
        pred = fut.result()                  # class id
        preds = engine.classify(iq_frames)   # (N, 2, L) convenience wrapper
        engine.close()

    ``backend="auto"`` races the platform's candidate backends on the
    largest bucket shape and pins the winner (``engine.autotune`` keeps the
    full report).  ``backend="per-layer"`` races them **layer by layer**
    (plan cost priors order each race; ``engine.perlayer`` keeps the
    report) and serves the winning heterogeneous assignment through the
    fused single-scan streaming executor (``engine.plan``).  With more
    than one local device (or an explicit ``mesh``) every batch is fanned
    across the mesh's ``data`` axis via ``shard_map``; bucket sizes are
    forced to multiples of the device count so the split is always even.

    ``workers`` serving threads each run the whole loop for one batch at a
    time (gather, put, dispatch, fetch, resolve), so up to ``workers``
    batches are in flight; the batcher lets one form at a time.  ``None``
    takes :func:`default_workers` of the step's devices: two on an
    accelerator, one on the CPU.
    """

    def __init__(
        self,
        params,
        cfg: SNNConfig,
        masks=None,
        *,
        backend: str = "auto",
        max_batch: Optional[int] = None,   # default 64 (or buckets[-1])
        max_delay_ms: float = 5.0,
        buckets: Optional[Sequence[int]] = None,
        workers: Optional[int] = None,
        max_queue: Optional[int] = None,
        pace_ms: float = 0.0,
        priority_weights=None,
        mesh=None,
        count_activity: bool = False,
        warmup: bool = True,
        candidates: Optional[Sequence[str]] = None,
        autotune_reps: int = 2,
        version_label: str = "default",
        lsq_scales=None,
        quant_bits: int = 16,
        name: Optional[str] = None,
        activity_gauges: bool = True,
    ):
        self.cfg = cfg
        self.count_activity = count_activity
        self.quant_bits = quant_bits
        self.program = compile_snn(cfg)
        self.sparse = sparsify_params(params, masks) if count_activity else None
        # observability identity: the {engine=...} label on every serve
        # metric (the fleet factory passes the replica name, so fleet-wide
        # aggregates stay separable per replica)
        self.name = name if name is not None else "engine"
        self.activity_gauges = activity_gauges

        if mesh is None and jax.local_device_count() > 1:
            from repro.distributed.sharding import serve_mesh

            mesh = serve_mesh()
        self.mesh = mesh
        align = int(mesh.shape["data"]) if mesh is not None else 1

        # registry instrumentation: all families are idempotent creates on
        # the process-wide registry, children pre-resolved off the hot path
        install_process_telemetry()
        reg = default_registry()
        eng = self.name
        self._m_requests = reg.counter(
            "repro_serve_requests_total", "Requests served (real frames)",
            ("engine",)).labels(engine=eng)
        self._m_batches = reg.counter(
            "repro_serve_batches_total", "Micro-batches served",
            ("engine", "backend"))
        self._m_overlapped = reg.counter(
            "repro_serve_overlapped_batches_total",
            "Micro-batches dispatched while another batch of the engine "
            "was dispatched and not yet fetched", ("engine",)).labels(engine=eng)
        self._m_transfers = reg.counter(
            "repro_serve_result_transfers_total",
            "Device-to-host copies of a batch's results (one per batch)",
            ("engine",)).labels(engine=eng)
        self._m_padded = reg.counter(
            "repro_serve_padded_frames_total",
            "Zero-padded tail rows shipped in fixed-shape buckets",
            ("engine",)).labels(engine=eng)
        self._m_latency = reg.histogram(
            "repro_serve_request_latency_seconds",
            "Per-request enqueue-to-completion latency",
            ("engine",)).labels(engine=eng)
        self._m_qdepth = reg.gauge(
            "repro_serve_queue_depth",
            "Queue backlog observed at the last batch flush",
            ("engine",)).labels(engine=eng)
        obs_counters = {
            "expired": reg.counter(
                "repro_serve_expired_total",
                "Requests failed fast on a passed deadline",
                ("engine",)).labels(engine=eng),
            "rejected": reg.counter(
                "repro_serve_rejected_total",
                "Submits refused by the max_queue admission bound",
                ("engine",)).labels(engine=eng),
            "cancelled": reg.counter(
                "repro_serve_cancelled_total",
                "Cancelled futures dropped without a batch slot",
                ("engine",)).labels(engine=eng),
        }

        ic0 = cfg.conv_specs[0][1]
        self.batcher = MicroBatcher(
            frame_shape=(ic0, cfg.input_width), max_batch=max_batch,
            max_delay_ms=max_delay_ms, buckets=buckets, align=align,
            max_queue=max_queue, pace_ms=pace_ms,
            priority_weights=priority_weights, obs_counters=obs_counters)

        self.autotune: Optional[AutotuneReport] = None
        self.perlayer: Optional[PerLayerAutotuneReport] = None
        self.plan = None
        self.assignment: Optional[Dict[str, str]] = None
        raced_steps: Dict[str, object] = {}
        if backend == "per-layer":
            # race the candidates layer by layer (plan cost priors order the
            # race) and serve the winning heterogeneous assignment through
            # the fused single-scan streaming executor
            self.perlayer = autotune_per_layer(
                self.program, params, self.batcher.max_batch, masks=masks,
                candidates=candidates, reps=autotune_reps)
            self.assignment = dict(self.perlayer.assignment)
            self.plan = compile_plan(self.program, params, masks=masks,
                                     quant_fn=_quant_fn_for(lsq_scales,
                                                            quant_bits,
                                                            self.assignment),
                                     assignment=self.assignment)
        elif backend == "auto":
            probe_shape = (self.batcher.max_batch, ic0, cfg.input_width)
            if candidates is None and lsq_scales is not None:
                # quantized serving: the integer `fixed` backend competes
                from repro.serve.autotune import default_candidates

                candidates = default_candidates(quantized=True)

            def make_fn(bound):  # memoize so the winner's compile is reused
                fn = self._wrap_bound(bound)
                raced_steps[bound.backend] = fn
                return fn

            # with LSQ state the race binds carry the fake-quant (or, for
            # the fixed candidate, integer) weights so timings measure the
            # quantized serving step that would actually run
            self.autotune = autotune_backend(
                self.program, params, probe_shape, masks=masks,
                quant_fn=_quant_fn_for(lsq_scales, quant_bits),
                candidates=candidates, reps=autotune_reps, make_fn=make_fn)
            backend = self.autotune.choice
        self.backend = backend
        self.stats = ServeStats(backend=backend)
        # live activity gauges need a counter-returning step: single-host
        # only (the shard_map wrapper carries bare logits) and only for
        # assignments whose conv layers count in-graph
        counters_wanted = activity_gauges and mesh is None
        if self.plan is not None:           # per-layer: fused streaming step
            self._step = self._wrap_batch_fn(
                self.plan.batch, int_encode=_uses_fixed(self.assignment))
        elif (backend in raced_steps and lsq_scales is None
              and not (counters_wanted
                       and backend in ("stream", "pallas_fused"))):
            # reuse the race winner's compile (without LSQ state the race
            # bind is the serving bind; with it the winner is only a
            # backend choice — the serving step is rebuilt through the
            # cached plan below so restarts stay near-free)
            self._step = raced_steps[backend]
        else:                               # cached plan bind
            self.plan = compile_plan(self.program, params, masks=masks,
                                     quant_fn=_quant_fn_for(lsq_scales,
                                                            quant_bits,
                                                            backend),
                                     assignment=backend)
            self._step = self._wrap_batch_fn(self.plan.preferred_batch(),
                                             int_encode=_uses_fixed(backend))
        self._activity: Optional[ActivityObserver] = None
        counter_names: Tuple[str, ...] = ()
        if (counters_wanted and self.plan is not None
                and self.plan.supports_live_counters):
            self._step, counter_names = self._wrap_counters(
                self.plan, int_encode=_uses_fixed(self.assignment or backend))
            self._activity = ActivityObserver(self.plan, engine=self.name)

        # readiness: armed by the first successful jitted step (warmup
        # counts), what /readyz keys on — distinct from liveness
        self._ready = threading.Event()
        if warmup:  # pre-compile every bucket shape so serving never stalls
            for b in self.batcher.buckets:
                jax.block_until_ready(
                    self._step(jnp.zeros((b, ic0, cfg.input_width), jnp.float32)))
            self._ready.set()

        # serving table: label -> BoundVersion.  The primary takes all
        # traffic unless a router is installed (deploy.router); hot-swap
        # (deploy.swap) binds a new version off-thread then flips _primary
        # between micro-batches.
        self._versions: Dict[str, BoundVersion] = {
            version_label: BoundVersion(
                label=version_label, backend=self.backend, step=self._step,
                plan=self.plan, sparse=self.sparse,
                stats=ServeStats(backend=self.backend),
                activity=self._activity, counter_names=counter_names),
        }
        self._primary = version_label
        self._router: Optional[Callable[[], str]] = None

        self._lock = threading.Lock()
        self._t_first_enqueue = float("inf")  # start of the serving window
        self._t_started = time.perf_counter()
        self._busy_s = 0.0  # cumulative worker time spent serving batches
        # batches dispatched and not yet fetched, under a lock of its own
        # so that a dispatch never waits on another batch's stats
        self._in_flight = 0
        self._flight_lock = threading.Lock()
        self._stop = threading.Event()
        if workers is None:
            workers = default_workers(
                mesh.devices.flat if mesh is not None else jax.devices()[:1])
        self._threads = [
            threading.Thread(target=self._worker, daemon=True,
                             name=f"amc-serve-worker-{i}")
            for i in range(max(1, workers))
        ]
        for t in self._threads:
            t.start()

    # -- compiled step ------------------------------------------------------

    def _wrap_batch_fn(self, batch_fn, int_encode: bool = False):
        """Fuse Σ-Δ encode + forward (+ shard_map) under one jit.

        ``batch_fn``: (B, T, IC, L) spike frames -> (B, n_classes) logits —
        a bound program's layer-by-layer ``batch`` or an ExecutionPlan's
        fused streaming ``batch``.  ``int_encode`` routes through the
        integer Q0.15 Σ-Δ front end (the fixed tier's encoder).
        """
        osr = self.cfg.timesteps
        if int_encode:
            from repro.fixed import fixed_encode_batch as encode
        else:
            encode = sigma_delta_encode_batch

        def step(iq):  # (B, IC, L) raw I/Q -> (B, n_classes) logits
            return batch_fn(encode(iq, osr))

        if self.mesh is not None:
            from repro.distributed.sharding import shard_serve_fn

            step = shard_serve_fn(step, self.mesh)
        return jax.jit(step)

    def _wrap_counters(self, plan, int_encode: bool = False):
        """The live-counter step, and the conv layer order of its counts.

        Wraps ``plan.batch_counters`` so that the jit returns one float32
        ``(B, n_classes + n_conv)`` array, the logits and then each conv
        layer's per-frame accumulation counts, and a batch's results come
        back in one device-to-host copy.  Counts are float32-exact below
        2**24 per frame.  The logits are never cast: a plan whose logits
        or counts are not float32 raises TypeError here, at bind time.
        """
        probe = jax.ShapeDtypeStruct(
            (1, self.cfg.conv_specs[0][1], self.cfg.input_width),
            jnp.float32)
        logits, accs = jax.eval_shape(
            self._wrap_batch_fn(plan.batch_counters, int_encode), probe)
        dtypes = {"logits": logits.dtype,
                  **{k: v.dtype for k, v in accs.items()}}
        if any(d != jnp.float32 for d in dtypes.values()):
            raise TypeError(
                "live counters travel with the logits as one float32 "
                f"array; the plan's step returns {dtypes}")

        names = tuple(lp.spec.name for lp in plan.layers
                      if lp.spec.kind == KIND_CONV and lp.spec.name in accs)

        def packed(frames_b):
            logits, accs = plan.batch_counters(frames_b)
            return jnp.concatenate(
                [logits, jnp.stack([accs[k] for k in names], -1)], -1)

        return self._wrap_batch_fn(packed, int_encode), names

    def _wrap_bound(self, bound):
        return self._wrap_batch_fn(bound.batch,
                                   int_encode=_uses_fixed(bound.backend))

    # -- worker loop --------------------------------------------------------

    def _route(self) -> BoundVersion:
        """Pick the version serving the next batch (router, else primary).

        A router naming a label that was removed mid-flight falls back to
        the primary — routing can degrade, never crash the worker loop.
        The table read happens under the engine lock so it can never
        interleave with a swap_to/remove_version pair: the invariant that
        the primary is always in the table holds while the lock is held.
        """
        label: Optional[str] = None
        router = self._router
        if router is not None:
            try:
                label = router()
            except Exception:  # noqa: BLE001 — a broken router must not
                label = None   # take the serving loop down with it
        with self._lock:
            ver = self._versions.get(label) if label is not None else None
            return ver if ver is not None else self._versions[self._primary]

    @contextlib.contextmanager
    def _on_device(self):
        """A batch from its dispatch to its fetch: counts the batches
        dispatched while another was still in flight."""
        with self._flight_lock:
            overlapped = self._in_flight > 0
            self._in_flight += 1
        if overlapped:
            self._m_overlapped.inc()
        try:
            yield
        finally:
            with self._flight_lock:
                self._in_flight -= 1

    def _worker(self) -> None:
        # one profiler span per phase of each batch, tiling the loop:
        # engine.turn (the batcher's forming turn; another serving thread
        # may hold it), engine.gather (batcher.form inside it), engine.put,
        # engine.dispatch (to the step's asynchronous return), engine.fetch
        # (the wait for the device and the one copy back: a batch's logits
        # and live counters arrive in a single array), engine.counters
        # (live activity counters only; host-side accounting on that copy,
        # no transfer), engine.resolve.  The input array is freed right
        # after dispatch and a batch's outputs stay bound until the next
        # batch rebinds them: freeing a device array releases the
        # interpreter lock, which right after the futures resolve the
        # client would take for its whole refill.  Each serving thread runs
        # this loop; what they share (the engine lock's stats, _busy_s,
        # the in-flight count, the registry, the activity observer) is
        # locked, and everything of one batch stays on its thread.
        while not self._stop.is_set():
            # the wait for another thread's forming turn has a span of its
            # own: engine.gather holds this thread's gathering alone
            with span("engine.turn"):
                turn = self.batcher.turn.acquire(timeout=0.1)
            if not turn:
                continue
            try:
                with span("engine.gather"):
                    batch = self.batcher.get_batch(timeout=0.1)
            finally:
                self.batcher.turn.release()
            if batch is None:
                continue
            t_busy0 = time.perf_counter()
            try:
                # the version is pinned *per batch*: a hot-swap flipping
                # the primary mid-service never retargets an in-flight
                # batch, so its futures complete on the plan that started
                # them.  Routing runs inside the covered block: if it ever
                # raises, the batch's futures fail instead of stranding.
                ver = self._route()
                t_step0 = time.perf_counter()
                with span("engine.put"):
                    x = jnp.asarray(batch.frames)
                with self._on_device():
                    with span("engine.dispatch", bucket=batch.bucket,
                              n_real=batch.n_real, backend=ver.backend):
                        out = ver.step(x)
                        del x   # now, not at the next batch's put
                    with span("engine.fetch", arrays=1):
                        host = np.asarray(out)
                t_step1 = time.perf_counter()
                self._m_transfers.inc()
                self._ready.set()  # first successful jit step: /readyz 200
                logits, accs = ver.unpack(host)
                preds = logits.argmax(-1).astype(np.int32)
                n_real = batch.n_real
                if accs is not None:
                    with span("engine.counters"):
                        ver.activity.observe(accs, n_real)
                with span("engine.resolve"):
                    # activity counting is an expensive diagnostics mode; it
                    # runs outside the lock (workers stay parallel) but before
                    # the futures resolve, so a caller that reads ``stats``
                    # right after its results always sees them counted
                    counted: Optional[ServeStats] = None
                    if self.count_activity and ver.sparse is not None:
                        counted = ServeStats()
                        frames = sigma_delta_encode_np(
                            batch.frames[:n_real], self.cfg.timesteps)
                        count_batch_activity(counted, ver.sparse, frames,
                                             self.cfg)
                    # completion is stamped after counting: callers' futures
                    # resolve after it, so latencies reflect what they waited
                    t_done = time.perf_counter()
                    with self._lock:
                        # serving window: first enqueue ever -> latest batch
                        # completion.  Correct for both the submit()/future
                        # path and (possibly concurrent) classify() callers.
                        # Each version additionally tracks its own window so a
                        # late-bound canary's throughput is not diluted.
                        batch_first = min(r.t_enqueue for r in batch.requests)
                        self._t_first_enqueue = min(self._t_first_enqueue,
                                                    batch_first)
                        ver.t_first = min(ver.t_first, batch_first)
                        for st, t0 in ((self.stats, self._t_first_enqueue),
                                       (ver.stats, ver.t_first)):
                            st.requests += n_real
                            st.record_batch(ver.backend,
                                            queue_depth=batch.queue_depth,
                                            padded=batch.n_padded)
                            st.record_latencies(
                                t_done - r.t_enqueue for r in batch.requests)
                            # max(): a worker delayed by activity counting must
                            # not shrink a window another worker extended
                            st.wall_s = max(st.wall_s, t_done - t0)
                            if counted is not None:
                                st.accumulations += counted.accumulations
                                st.fetched_bits += counted.fetched_bits
                    # registry mirrors (family-locked; outside the engine lock)
                    self._m_requests.inc(n_real)
                    self._m_batches.labels(engine=self.name,
                                           backend=ver.backend).inc()
                    self._m_padded.inc(batch.n_padded)
                    self._m_qdepth.set(batch.queue_depth)
                    for r in batch.requests:
                        self._m_latency.observe(t_done - r.t_enqueue)
                        if r.trace is not None:
                            # the jitted step is batch-wide: every traced rider
                            # shares the same explicit start/end stamps
                            r.trace.add("jit-step-start", t=t_step0,
                                        version=ver.label, backend=ver.backend)
                            r.trace.add("jit-step-end", t=t_step1)
                    for i, r in enumerate(batch.requests):
                        # transitions PENDING -> RUNNING (after which cancel()
                        # can no longer win the race); False = caller cancelled
                        # while queued — skip, don't poison the batch
                        if r.future.set_running_or_notify_cancel():
                            tadd(r.trace, "complete", pred=int(preds[i]))
                            tfinish(r.trace)
                            r.future.set_result(int(preds[i]))
                        else:
                            tadd(r.trace, "cancelled", at="resolve")
                            tfinish(r.trace)
            except Exception as e:  # noqa: BLE001 — propagate to callers;
                # the whole batch path is covered so a stats/counting error
                # can never strand a future or kill the worker loop
                for r in batch.requests:
                    tadd(r.trace, "error", detail=str(e))
                    tfinish(r.trace)
                    _fail_future(r.future, e)
            finally:
                with self._lock:
                    self._busy_s += time.perf_counter() - t_busy0

    # -- model lifecycle (deploy subsystem hooks) ---------------------------

    @property
    def active_version(self) -> str:
        """Label of the primary (default-traffic) version."""
        return self._primary

    def versions(self) -> Dict[str, BoundVersion]:
        """Snapshot of the serving table (label -> BoundVersion)."""
        with self._lock:
            return dict(self._versions)

    def get_version(self, label: str) -> BoundVersion:
        return self._versions[label]

    def version_stats(self) -> Dict[str, ServeStats]:
        with self._lock:
            return {k: v.stats for k, v in self._versions.items()}

    def bind_version(self, label: str, params, masks=None, *,
                     backend: Optional[str] = None,
                     lsq_scales=None, quant_bits: Optional[int] = None,
                     warmup: bool = True) -> BoundVersion:
        """Compile and register a new model version under ``label``.

        Safe to call from any thread while serving: the compile (plan bind
        + per-bucket warmup) runs in the *caller's* thread against the
        content-addressed plan cache, and only the final table insert
        takes the engine lock — workers keep draining batches on the
        current versions throughout.  The new version takes no traffic
        until :meth:`swap_to` or a router targets it.

        ``backend=None`` inherits the engine's serving backend (including
        a ``per-layer`` heterogeneous assignment); ``backend="auto"``
        re-races the candidates for the new weights.
        """
        if backend is None:
            backend = self.backend
        bits = quant_bits if quant_bits is not None else self.quant_bits
        qfn = _quant_fn_for(lsq_scales, bits, backend)
        plan = None
        if backend == "per-layer":
            if not self.assignment:
                # silently serving a uniform fallback while reporting
                # "per-layer" would misstate what runs; the heterogeneous
                # race only exists on engines constructed with it
                raise ValueError(
                    "backend='per-layer' requires an engine constructed "
                    "with backend='per-layer' (no autotuned assignment to "
                    "inherit); pass an explicit backend instead")
            qfn = _quant_fn_for(lsq_scales, bits, self.assignment)
            plan = compile_plan(self.program, params, masks=masks,
                                quant_fn=qfn, assignment=self.assignment)
            step = self._wrap_batch_fn(
                plan.batch, int_encode=_uses_fixed(self.assignment))
        else:
            if backend == "auto":
                ic0 = self.cfg.conv_specs[0][1]
                probe = (self.batcher.max_batch, ic0, self.cfg.input_width)
                backend = autotune_backend(self.program, params, probe,
                                           masks=masks).choice
                qfn = _quant_fn_for(lsq_scales, bits, backend)
            plan = compile_plan(self.program, params, masks=masks,
                                quant_fn=qfn, assignment=backend)
            step = self._wrap_batch_fn(plan.preferred_batch(),
                                       int_encode=_uses_fixed(backend))
        sparse = sparsify_params(params, masks) if self.count_activity else None
        activity = None
        counter_names: Tuple[str, ...] = ()
        if (self.activity_gauges and self.mesh is None and plan is not None
                and plan.supports_live_counters):
            enc = self.assignment if backend == "per-layer" else backend
            step, counter_names = self._wrap_counters(
                plan, int_encode=_uses_fixed(enc))
            activity = ActivityObserver(plan, engine=self.name)
        if warmup:  # pre-compile every bucket so the flip never stalls
            ic0 = self.cfg.conv_specs[0][1]
            for b in self.batcher.buckets:
                jax.block_until_ready(
                    step(jnp.zeros((b, ic0, self.cfg.input_width),
                                   jnp.float32)))
        ver = BoundVersion(label=label, backend=backend, step=step,
                           plan=plan, sparse=sparse,
                           stats=ServeStats(backend=backend),
                           activity=activity, counter_names=counter_names)
        with self._lock:
            self._versions[label] = ver
        return ver

    def swap_to(self, label: str) -> str:
        """Atomically make ``label`` the primary version; returns the old.

        The flip is a table-pointer update between micro-batches:
        in-flight batches complete on the version that started them, and
        the next batch any worker picks up serves from the new primary —
        no request is dropped or blocked for more than one batch flush.
        """
        with self._lock:
            if label not in self._versions:
                raise KeyError(
                    f"no bound version {label!r} (bound: "
                    f"{sorted(self._versions)})")
            old, self._primary = self._primary, label
            ver = self._versions[label]
            self.backend = ver.backend
            self.plan = ver.plan
            self.stats.backend = ver.backend
        return old

    def remove_version(self, label: str) -> None:
        """Drop a non-primary version from the serving table."""
        with self._lock:
            if label == self._primary:
                raise ValueError(
                    f"cannot remove the primary version {label!r}; "
                    "swap_to another version first")
            self._versions.pop(label, None)

    def set_router(self, router: Optional[Callable[[], str]]) -> None:
        """Install (or clear, with None) the per-batch version router."""
        self._router = router

    # -- fleet-facing signals ----------------------------------------------

    @property
    def n_workers(self) -> int:
        return len(self._threads)

    @property
    def busy_s(self) -> float:
        """Cumulative worker seconds spent serving batches."""
        with self._lock:
            return self._busy_s

    def utilization(self) -> float:
        """Busy fraction of total worker capacity since construction.

        The autoscaler prefers *windowed* utilization (deltas of
        ``busy_s`` between control ticks); this cumulative form is the
        zero-state fallback and what ``export_stats`` reports.
        """
        elapsed = time.perf_counter() - self._t_started
        if elapsed <= 0:
            return 0.0
        return min(1.0, self.busy_s / (elapsed * self.n_workers))

    def recent_latencies(self, k: int = 256) -> List[float]:
        """Last ``k`` served-request latencies (seconds), oldest first."""
        with self._lock:
            return list(self.stats.latencies_s[-k:])

    def export_stats(self) -> dict:
        """Per-replica control-plane snapshot (what the fleet aggregates).

        Extends ``stats.summary()`` with the live queue/admission signals
        the router and autoscaler act on: current queue depth, expired /
        rejected / cancelled totals from the batcher, and worker
        utilization.
        """
        s = self.stats.summary()
        s.update({
            "queue_depth": self.batcher.qsize(),
            "queue_depths_by_priority": self.batcher.qsizes(),
            "n_expired": self.batcher.n_expired,
            "n_rejected": self.batcher.n_rejected,
            "n_cancelled": self.batcher.n_cancelled,
            "workers": self.n_workers,
            "busy_s": self.busy_s,
            "utilization": self.utilization(),
            "active_version": self.active_version,
        })
        return s

    # -- public API ---------------------------------------------------------

    def submit(self, iq: np.ndarray, *, deadline_ms: Optional[float] = None,
               priority: str = "realtime", trace=None):
        """Enqueue one (2, L) frame; returns a ``ServeFuture``.

        ``deadline_ms`` is a relative latency budget: a request still
        queued when it expires fails fast with ``DeadlineExceeded``
        instead of occupying a micro-batch slot.  ``priority`` picks the
        dequeue class (``realtime`` > ``bulk``, weighted).

        ``trace=None`` starts a fresh request trace when tracing is
        enabled; a caller that already owns one (the fleet router) passes
        it through and keeps responsibility for its failure terminals.
        """
        deadline = (None if deadline_ms is None
                    else self.batcher.now() + deadline_ms / 1e3)
        owned = False
        if trace is None:
            trace = begin_trace()
            owned = trace is not None
            tadd(trace, "submit", engine=self.name, priority=priority)
        try:
            return self.batcher.submit(iq, deadline=deadline,
                                       priority=priority, trace=trace)
        except (QueueFull, EngineClosed) as e:
            if owned:  # a router-owned trace may retry another replica
                tadd(trace, "reject", reason=type(e).__name__)
                tfinish(trace)
            raise

    def classify(self, iq: np.ndarray, timeout: float = 300.0, *,
                 deadline_ms: Optional[float] = None,
                 priority: str = "realtime") -> np.ndarray:
        """Blocking convenience wrapper: (N, 2, L) -> class ids (N,).

        ``stats.wall_s`` is maintained by the worker loop as the serving
        window (first enqueue -> latest completion), so it is consistent
        whether requests arrive through here or through ``submit()``.

        On timeout (or any per-request failure) the outstanding futures
        are cancelled before the error propagates — an abandoned classify
        call never leaks still-pending requests into the batcher (the
        dequeue path drops cancelled futures without giving them a batch
        slot).  Requests already inside an in-flight batch complete
        normally; their results are simply discarded.
        """
        futures = [self.submit(iq[i], deadline_ms=deadline_ms,
                               priority=priority)
                   for i in range(iq.shape[0])]
        out = np.empty((len(futures),), dtype=np.int32)
        try:
            for i, f in enumerate(futures):
                out[i] = f.result(timeout=timeout)
        except BaseException:
            for f in futures:
                f.cancel()  # no-op for done/running futures
            raise
        return out

    def is_ready(self) -> bool:
        """True once the first jitted step succeeded (and not closed)."""
        return self._ready.is_set() and not self._stop.is_set()

    @property
    def closed(self) -> bool:
        return self._stop.is_set()

    def close(self) -> None:
        """Stop the workers; no future is ever left unresolved.

        In-flight batches finish (every serving thread joins after the
        batch it holds); requests still queued are drained and their
        futures failed with a ``RuntimeError`` so blocked callers wake
        instead of hanging.
        """
        self._stop.set()
        self.batcher.close()
        for t in self._threads:
            t.join(timeout=5.0)
        err = RuntimeError("AsyncAMCServeEngine closed before serving "
                           "this request")
        for r in self.batcher.drain():
            tadd(r.trace, "cancelled", at="close")
            tfinish(r.trace)
            _fail_future(r.future, err)

    def __enter__(self) -> "AsyncAMCServeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
