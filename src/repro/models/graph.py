"""Declarative SNN layer graph with pluggable execution backends.

The paper's core claim is that one fixed SNN can be executed through very
different dataflows — dense sliding-window baseline vs. the sparsity-aware
GOAP/SAOCDS streaming pipeline — with identical numerics but very different
cost (paper §III, Tables I/III).  This module makes that claim structural:

* ``build_layer_graph(cfg)`` derives a tuple of :class:`LayerSpec` nodes
  (``Conv1dLIF`` / ``MaxPool`` / ``FCLIF`` / ``Readout``) from an
  :class:`~repro.models.snn.SNNConfig` — the *model definition*;
* :class:`SNNProgram` compiles the graph once and ``apply(params, frames,
  backend=...)`` dispatches per-layer to registered backends — the
  *execution strategy*;
* backends register via :func:`register_backend(name, layer_kind, fn)` so
  future execution strategies (sharded, batched-async, quantized) plug in
  without touching the model.

Backend factories return per-timestep :class:`LayerCell` objects —
``step(state, x_t) -> (state, y_t)`` plus an explicit ``init_state`` — not
whole-sequence stages.  One generic driver (:func:`run_cell`) scans a cell
over time for the layer-by-layer path, and the same cells are threaded
through a *single* scan over timesteps by the fused inter-layer executor
(:mod:`repro.plan.streaming`) — the software analogue of the paper's
control-free inter-layer pipeline.

Built-in backends:

============  ==============================================================
name          per-layer implementation
============  ==============================================================
dense         im2col matmul oracle (differentiable; masks + LSQ quant)
goap          packed COO one-to-all product (Algorithm 1 as one fused
              gather + contraction per timestep)
pallas        static block-sparse TPU kernel (interpret mode on the CPU)
pallas_fused  same per-layer cells, plus kernel-ready operands for the
              whole-network multi-layer streaming kernel
              (:mod:`repro.kernels.stream_fused`) — the fused executor
              runs the entire forward in one launch
stream        faithful Algorithm-2 schedule interpreter; also returns the
              compute/extra/empty iteration counters of paper Tables I/III
============  ==============================================================

``dense`` binds with pure-jax ops and may be traced (jit/grad/vmap over
params).  ``goap``/``pallas``/``stream`` precompute numpy artifacts (COO
kernels, static schedules, block-sparse tilings) at bind time and therefore
need **concrete** weights — bind outside jit, then jit the bound program.
With concrete weights, prefer :func:`repro.plan.compile_plan`: it derives
each layer's artifacts once into a content-hashed, disk-cached
``ExecutionPlan`` (repeated binds are near-free) and supports per-layer
backend assignment plus the fused streaming executor.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.goap import conv1d_dense_oracle, goap_conv_packed, goap_pack
from repro.core.lif import lif_step
from repro.core.saocds import make_schedule_step, max_pool_spikes, pad_same
from repro.core.sparse_format import (
    CooKernel,
    block_sparse_from_dense,
    build_schedule,
    coo_from_dense,
)
from repro.models.snn import SNNConfig

__all__ = [
    "LayerSpec",
    "Conv1dLIF",
    "MaxPool",
    "FCLIF",
    "Readout",
    "build_layer_graph",
    "register_backend",
    "available_backends",
    "get_backend",
    "LayerCell",
    "run_cell",
    "artifact_build_count",
    "SNNProgram",
    "BoundProgram",
    "compile_snn",
    "stream_totals",
]

# Layer kinds understood by the executor.
KIND_CONV = "conv_lif"
KIND_POOL = "maxpool"
KIND_FC = "fc_lif"
KIND_READOUT = "readout"


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One node of the layer graph (pure metadata, no parameters)."""

    kind: str
    name: str
    index: int = 0        # position within its param group (conv i / fc i)
    # conv_lif
    kw: int = 0
    ic: int = 0
    oc: int = 0
    # maxpool
    pool: int = 0
    # fc_lif
    din: int = 0
    dout: int = 0
    # readout
    mode: str = ""


def Conv1dLIF(index: int, kw: int, ic: int, oc: int, name: str = "") -> LayerSpec:
    return LayerSpec(kind=KIND_CONV, name=name or f"conv{index + 1}",
                     index=index, kw=kw, ic=ic, oc=oc)


def MaxPool(pool: int, name: str = "") -> LayerSpec:
    return LayerSpec(kind=KIND_POOL, name=name or "pool", pool=pool)


def FCLIF(index: int, din: int, dout: int, name: str = "") -> LayerSpec:
    return LayerSpec(kind=KIND_FC, name=name or f"fc{index + 1}",
                     index=index, din=din, dout=dout)


def Readout(mode: str) -> LayerSpec:
    return LayerSpec(kind=KIND_READOUT, name="readout", mode=mode)


def validate_unique_names(specs: Sequence[LayerSpec]) -> None:
    """Weighted-layer names key the counters dict and plan assignments —
    two same-named conv/FC layers would silently overwrite each other's
    Tables I/III counts, so collisions fail loudly here instead.  Pool and
    readout layers never key anything and may share names (hand-built
    graphs often repeat the default ``MaxPool`` name)."""
    seen: Dict[str, str] = {}
    for s in specs:
        if s.kind not in (KIND_CONV, KIND_FC):
            continue
        if s.name in seen:
            raise ValueError(
                f"duplicate layer name {s.name!r} ({seen[s.name]} and "
                f"{s.kind}): layer names key per-layer counters and "
                "backend assignments; give each layer a unique name")
        seen[s.name] = s.kind


def build_layer_graph(cfg: SNNConfig) -> Tuple[LayerSpec, ...]:
    """Derive the declarative layer graph from an ``SNNConfig``."""
    cfg.validate()
    layers: List[LayerSpec] = []
    for i, (kw, ic, oc) in enumerate(cfg.conv_specs):
        layers.append(Conv1dLIF(i, kw, ic, oc))
        layers.append(MaxPool(cfg.pool, name=f"pool{i + 1}"))
    for i, (din, dout) in enumerate(cfg.fc_specs):
        layers.append(FCLIF(i, din, dout))
    layers.append(Readout(cfg.readout))
    validate_unique_names(layers)
    return tuple(layers)


# ---------------------------------------------------------------------------
# The cell protocol.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerCell:
    """Per-timestep execution of one layer.

    * ``init_state(x_t)`` — build the carried state from a per-timestep
      input *template* (anything with ``.shape``/``.dtype`` leaves, e.g. a
      ``jax.ShapeDtypeStruct``): conv/FC membrane potentials, counter
      accumulators, ``()`` for stateless layers.
    * ``step(state, x_t) -> (state, y_t)`` — advance one timestep.
    * ``finalize(state)`` — optional; extract the layer's terminal value
      (readout logits, stream iteration counters) after the last timestep.

    The same cell serves both executors: the layer-by-layer path scans it
    over time in isolation (:func:`run_cell`), the fused streaming executor
    threads every layer's state through one scan over timesteps.

    ``seq`` is an optional whole-sequence fast path ``seq(xs) -> ys`` for
    the layer-by-layer executor only (e.g. the pallas FC's single batched
    (T, IN) matmul + fused-LIF kernel, or vectorized pooling); it must be
    numerically equivalent to scanning ``step`` and is only valid for
    cells without a ``finalize``.

    ``fused`` optionally carries the layer's kernel-ready operands for the
    whole-network multi-layer Pallas kernel (a
    :class:`repro.kernels.stream_fused.FusedConv`/``FusedFC``); when every
    weighted layer of a plan provides one, the streaming executor collapses
    the entire forward into a single kernel launch.
    """

    init_state: Callable[[Any], Any]
    step: Callable[[Any, Any], Tuple[Any, Any]]
    finalize: Optional[Callable[[Any], Any]] = None
    seq: Optional[Callable[[Any], Any]] = None
    fused: Any = None


def timestep_template(xs):
    """Per-timestep ShapeDtypeStruct template of a (T, ...) sequence."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), xs)


def run_cell(cell: LayerCell, xs):
    """Drive one cell over a (T, ...) sequence (the layer-by-layer path).

    Returns ``(ys, final_state, aux)`` where ``aux`` is the cell's
    finalized value (None for cells without a ``finalize``).
    """
    if cell.seq is not None:
        return cell.seq(xs), None, None
    state = cell.init_state(timestep_template(xs))
    state, ys = jax.lax.scan(cell.step, state, xs)
    aux = cell.finalize(state) if cell.finalize is not None else None
    return ys, state, aux


def _spikes_of(x_t):
    """Input spikes of a per-timestep value (FC cells emit (spikes, currents))."""
    return x_t[0] if isinstance(x_t, tuple) else x_t


# ---------------------------------------------------------------------------
# Backend registry.
# ---------------------------------------------------------------------------

# A backend factory takes (spec, layer_params, cfg=, mask=, quant_fn=) and
# returns the layer's LayerCell.  Per-timestep contracts:
#   conv_lif: step(v, x_t (IC, W))        -> (v, spikes_t (OC, W))
#   maxpool:  step((), x_t)               -> ((), pooled x_t)
#   fc_lif:   step(v, x_t)                -> (v, (spikes_t (OUT,), currents_t))
#   readout:  step(acc, (s_t, c_t))       -> (acc + ..., s_t); finalize -> logits
# Factories may additionally accept an ``artifacts`` dict (see
# repro.plan.compile): precomputed entries are consumed instead of rebuilt,
# and fresh derivations are recorded into it for caching.
BackendFactory = Callable[..., LayerCell]

# Backends shared by every execution strategy (pooling and readout carry no
# weights, so there is nothing dataflow-specific about them) register under
# this pseudo-name; named backends may still override per layer kind.
COMMON = "common"

_REGISTRY: Dict[Tuple[str, str], BackendFactory] = {}

# Backends that live in optional subpackages register on first use instead
# of at import time (keeps repro.models.graph dependency-light).
_LAZY_BACKENDS: Dict[str, str] = {"fixed": "repro.fixed.backend"}


def _ensure_registered(name: Optional[str] = None) -> None:
    import importlib

    for lazy, module in _LAZY_BACKENDS.items():
        if (name is None or name == lazy) and not any(
                n == lazy for n, _ in _REGISTRY):
            importlib.import_module(module)


def register_backend(name: str, layer_kind: str, fn: BackendFactory) -> BackendFactory:
    """Register ``fn`` as backend ``name``'s implementation of ``layer_kind``."""
    _REGISTRY[(name, layer_kind)] = fn
    return fn


def available_backends() -> Tuple[str, ...]:
    """Names of all registered (non-common) backends."""
    _ensure_registered()
    return tuple(sorted({n for n, _ in _REGISTRY if n != COMMON}))


def get_backend(name: str, layer_kind: str) -> BackendFactory:
    """Resolve ``(name, layer_kind)``, falling back to the common pool."""
    _ensure_registered(name)
    if name not in {n for n, _ in _REGISTRY}:
        raise ValueError(
            f"unknown backend {name!r}; registered backends: "
            f"{list(available_backends())}"
        )
    fn = _REGISTRY.get((name, layer_kind)) or _REGISTRY.get((COMMON, layer_kind))
    if fn is None:
        raise ValueError(
            f"backend {name!r} has no implementation for layer kind "
            f"{layer_kind!r}"
        )
    return fn


# ---------------------------------------------------------------------------
# Bind-time helpers (artifact derivation + build accounting).
# ---------------------------------------------------------------------------

# Counts every *derivation* of an expensive bind-time artifact (COO kernels,
# Algorithm-2 schedules, block-sparse tilings).  The plan cache's whole job
# is to keep these from re-running — tests and benchmarks assert on it.
ARTIFACT_BUILDS: collections.Counter = collections.Counter()


def artifact_build_count() -> int:
    """Total expensive artifact derivations since process start."""
    return sum(ARTIFACT_BUILDS.values())


def _artifact(artifacts: Optional[dict], key: str, build: Callable[[], Any]):
    """Fetch ``key`` from the artifacts dict or build (and record) it."""
    if artifacts is not None and artifacts.get(key) is not None:
        return artifacts[key]
    ARTIFACT_BUILDS[key] += 1
    val = build()
    if artifacts is not None:
        artifacts[key] = val
    return val


def _effective_weight(layer_params, mask, quant_fn):
    w = layer_params["w"]
    if mask is not None:
        w = w * mask
    if quant_fn is not None:
        w = quant_fn(w)
    return w


def _weight(layer_params, mask, quant_fn, artifacts) -> jax.Array:
    """Effective (masked+quantized) weight, honoring a precomputed one."""
    if artifacts is not None and artifacts.get("w_eff") is not None:
        return jnp.asarray(artifacts["w_eff"])
    return _effective_weight(layer_params, mask, quant_fn)


def _concrete_weight(spec: LayerSpec, layer_params, mask, quant_fn,
                     artifacts=None) -> np.ndarray:
    """Numpy weights for backends that precompute sparse artifacts."""
    if artifacts is not None and artifacts.get("w_eff") is not None:
        return np.asarray(artifacts["w_eff"])
    try:
        return np.asarray(_effective_weight(layer_params, mask, quant_fn))
    except jax.errors.TracerArrayConversionError as e:
        raise ValueError(
            f"layer {spec.name!r}: this backend precomputes a sparse layout "
            "from concrete weights and cannot bind under jit/vmap/grad — "
            "bind the program outside the traced region (the 'dense' "
            "backend is fully traceable)"
        ) from e


def _layer_coo(spec: LayerSpec, layer_params, mask, quant_fn,
               artifacts=None) -> CooKernel:
    # accept pre-sparsified params ({"coo": ...}) as produced by
    # ``sparsify_params`` as well as raw dense params ({"w": ...})
    if "coo" in layer_params:
        return layer_params["coo"]
    return _artifact(artifacts, "coo", lambda: coo_from_dense(
        _concrete_weight(spec, layer_params, mask, quant_fn, artifacts)))


# ---------------------------------------------------------------------------
# Common (backend-independent) cells.
# ---------------------------------------------------------------------------

def _common_maxpool(spec: LayerSpec, layer_params, *, cfg, mask=None,
                    quant_fn=None, artifacts=None) -> LayerCell:
    def step(state, x_t):
        return state, max_pool_spikes(x_t, spec.pool)

    # pooling acts on trailing dims only, so the whole (T, C, W) sequence
    # pools in one vectorized op on the layer-by-layer path
    return LayerCell(init_state=lambda x_t: (), step=step,
                     seq=lambda xs: max_pool_spikes(xs, spec.pool))


def _common_readout(spec: LayerSpec, layer_params, *, cfg, mask=None,
                    quant_fn=None, artifacts=None) -> LayerCell:
    use_current = spec.mode == "current_sum"

    def init_state(x_t):
        src = x_t[1] if use_current else x_t[0]
        return jnp.zeros(src.shape, src.dtype)

    def step(acc, x_t):
        spikes_t, currents_t = x_t
        return acc + (currents_t if use_current else spikes_t), spikes_t

    return LayerCell(init_state=init_state, step=step,
                     finalize=lambda acc: acc)


register_backend(COMMON, KIND_POOL, _common_maxpool)
register_backend(COMMON, KIND_READOUT, _common_readout)


# ---------------------------------------------------------------------------
# Conv/FC cell builders shared by the backends (the old per-factory scan
# boilerplate, written exactly once).
# ---------------------------------------------------------------------------

def _conv_cell(kw: int, oc: int, lif, current_fn, dtype) -> LayerCell:
    """LIF conv cell: pad the frame, compute currents, advance the LIF."""

    def init_state(x_t):
        return jnp.zeros((oc, x_t.shape[-1]), dtype)

    def step(v, x_t):
        return lif_step(v, current_fn(pad_same(_spikes_of(x_t), kw)), lif)

    return LayerCell(init_state=init_state, step=step)


def _fc_cell(w: jax.Array, lif, current_fn=None) -> LayerCell:
    """LIF FC cell; emits (spikes_t, currents_t) for the readout."""
    if current_fn is None:
        current_fn = lambda s: s.astype(w.dtype) @ w

    def init_state(x_t):
        return jnp.zeros((w.shape[1],), w.dtype)

    def step(v, x_t):
        cur = current_fn(_spikes_of(x_t).reshape(-1))
        v_next, out = lif_step(v, cur, lif)
        return v_next, (out, cur)

    return LayerCell(init_state=init_state, step=step)


# ---------------------------------------------------------------------------
# dense backend — im2col oracle, differentiable (training path).
# ---------------------------------------------------------------------------

def _dense_conv(spec: LayerSpec, layer_params, *, cfg, mask=None,
                quant_fn=None, artifacts=None) -> LayerCell:
    w = _weight(layer_params, mask, quant_fn, artifacts)
    return _conv_cell(spec.kw, spec.oc, layer_params["lif"],
                      lambda ifm: conv1d_dense_oracle(ifm, w), w.dtype)


def _dense_fc(spec: LayerSpec, layer_params, *, cfg, mask=None,
              quant_fn=None, artifacts=None) -> LayerCell:
    w = _weight(layer_params, mask, quant_fn, artifacts)
    return _fc_cell(w, layer_params["lif"])


register_backend("dense", KIND_CONV, _dense_conv)
register_backend("dense", KIND_FC, _dense_fc)


# ---------------------------------------------------------------------------
# goap backend — COO weight-priority iteration (vectorized Algorithm 1).
# ---------------------------------------------------------------------------

def _goap_pack_of(coo: CooKernel, artifacts: Optional[dict]):
    """Padded per-output-channel layout of a COO kernel (cached, uncounted).

    Cached in the layer's artifact entry like COO/schedule, but *not*
    recorded in ``ARTIFACT_BUILDS``: packing is a microsecond reshuffle of
    the already-derived COO, and counting it would double-charge the
    one-rebuild-per-weight-update invariant the cache tests pin.
    """
    if artifacts is not None and artifacts.get("goap_pack") is not None:
        return artifacts["goap_pack"]
    pack = goap_pack(coo)
    if artifacts is not None:
        artifacts["goap_pack"] = pack
    return pack


def _goap_conv(spec: LayerSpec, layer_params, *, cfg, mask=None,
               quant_fn=None, artifacts=None) -> LayerCell:
    coo = _layer_coo(spec, layer_params, mask, quant_fn, artifacts)
    pack = _goap_pack_of(coo, artifacts)
    return _conv_cell(coo.kw, coo.oc, layer_params["lif"],
                      lambda ifm: goap_conv_packed(ifm, pack), jnp.float32)


register_backend("goap", KIND_CONV, _goap_conv)
# FC layers use the weight-mask method (paper §III-B): zeros kept in the
# matrix *are* the mask, so the dense FC cell is numerically the WM cell.
register_backend("goap", KIND_FC, _dense_fc)


# ---------------------------------------------------------------------------
# pallas backend — static block-sparse TPU kernel (compiled on a TPU,
# interpret mode on the CPU: repro.kernels.platform).
# ---------------------------------------------------------------------------

PALLAS_BLOCK_OC = 8
PALLAS_BLOCK_K = 32


def _pallas_conv(spec: LayerSpec, layer_params, *, cfg, mask=None,
                 quant_fn=None, artifacts=None) -> LayerCell:
    def build_bs():
        # the Pallas path needs the dense layout to re-block; recover it
        # from a pre-sparsified COO kernel if that is all we were given
        if "coo" in layer_params:
            from repro.core.sparse_format import coo_to_dense
            w = coo_to_dense(layer_params["coo"]).astype(np.float32)
        else:
            w = _concrete_weight(spec, layer_params, mask, quant_fn, artifacts)
        return block_sparse_from_dense(
            w, block_oc=PALLAS_BLOCK_OC, block_k=PALLAS_BLOCK_K)

    bs = _artifact(artifacts, "block_sparse", build_bs)

    from repro.kernels.ops import goap_conv_op

    return _conv_cell(bs.kw, bs.oc, layer_params["lif"],
                      lambda ifm: goap_conv_op(ifm, bs), jnp.float32)


def _pallas_fc(spec: LayerSpec, layer_params, *, cfg, mask=None,
               quant_fn=None, artifacts=None) -> LayerCell:
    w = jnp.asarray(_weight(layer_params, mask, quant_fn, artifacts))
    lif = layer_params["lif"]

    from repro.kernels.ops import lif_op, wm_fc_op

    cell = _fc_cell(w, lif, current_fn=lambda s: wm_fc_op(s.astype(w.dtype), w))

    def seq(xs):
        # FC currents are memoryless in T: one batched (T, IN) WM matmul,
        # then the fused LIF kernel integrates over time — one kernel
        # launch each instead of T per-row launches
        x = _spikes_of(xs)
        x = x.reshape(x.shape[0], -1)
        currents = wm_fc_op(x.astype(w.dtype), w)
        spikes, _ = lif_op(currents, lif)
        return spikes, currents

    return dataclasses.replace(cell, seq=seq)


register_backend("pallas", KIND_CONV, _pallas_conv)
register_backend("pallas", KIND_FC, _pallas_fc)


# ---------------------------------------------------------------------------
# pallas_fused backend — per-layer pallas cells + operands for the
# single-launch multi-layer streaming kernel (repro.kernels.stream_fused).
# ---------------------------------------------------------------------------

def _pallas_fused_conv(spec: LayerSpec, layer_params, *, cfg, mask=None,
                       quant_fn=None, artifacts=None) -> LayerCell:
    cell = _pallas_conv(spec, layer_params, cfg=cfg, mask=mask,
                        quant_fn=quant_fn, artifacts=artifacts)
    coo = _layer_coo(spec, layer_params, mask, quant_fn, artifacts)
    sched = _artifact(artifacts, "schedule", lambda: build_schedule(coo))
    from repro.kernels.stream_fused import fused_conv_info

    return dataclasses.replace(
        cell, fused=fused_conv_info(spec.name, coo, layer_params["lif"],
                                    sched))


def _pallas_fused_fc(spec: LayerSpec, layer_params, *, cfg, mask=None,
                     quant_fn=None, artifacts=None) -> LayerCell:
    cell = _pallas_fc(spec, layer_params, cfg=cfg, mask=mask,
                      quant_fn=quant_fn, artifacts=artifacts)
    w = _concrete_weight(spec, layer_params, mask, quant_fn, artifacts)
    from repro.kernels.stream_fused import fused_fc_info

    return dataclasses.replace(
        cell, fused=fused_fc_info(spec.name, w, layer_params["lif"]))


register_backend("pallas_fused", KIND_CONV, _pallas_fused_conv)
register_backend("pallas_fused", KIND_FC, _pallas_fused_fc)


# ---------------------------------------------------------------------------
# stream backend — faithful Algorithm-2 emulator with Tables I/III counters.
# ---------------------------------------------------------------------------

def _stream_conv(spec: LayerSpec, layer_params, *, cfg, mask=None,
                 quant_fn=None, artifacts=None) -> LayerCell:
    coo = _layer_coo(spec, layer_params, mask, quant_fn, artifacts)
    sched = _artifact(artifacts, "schedule", lambda: build_schedule(coo))
    one_timestep = make_schedule_step(sched, layer_params["lif"], coo.oc)
    static_counts = {
        "reps_per_timestep": sched.reps,
        "compute_iters": sched.n_compute,
        "extra_iters": sched.n_extra,
        "empty_iters": sched.n_empty,
    }

    def init_state(x_t):
        v0 = jnp.zeros((coo.oc, x_t.shape[-1]), jnp.float32)
        return v0, jnp.float32(0.0), jnp.int32(0)

    def step(carry, x_t):
        v, acc, t = carry
        v_next, (out, a) = one_timestep(v, pad_same(_spikes_of(x_t), coo.kw))
        return (v_next, acc + a, t + 1), out

    def finalize(carry):
        _, acc, t = carry
        return {**static_counts, "accumulations": acc, "timesteps": t}

    return LayerCell(init_state=init_state, step=step, finalize=finalize)


register_backend("stream", KIND_CONV, _stream_conv)
register_backend("stream", KIND_FC, _dense_fc)  # WM method, see goap above


def stream_totals(counters: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    """Aggregate per-layer stream counters into whole-network totals."""
    totals = {"compute_iters": 0, "extra_iters": 0, "empty_iters": 0,
              "reps_per_timestep": 0, "accumulations": 0.0}
    for counts in counters.values():
        totals["compute_iters"] += counts["compute_iters"]
        totals["extra_iters"] += counts["extra_iters"]
        totals["empty_iters"] += counts["empty_iters"]
        totals["reps_per_timestep"] += counts["reps_per_timestep"]
        totals["accumulations"] = totals["accumulations"] + counts["accumulations"]
    return totals


# ---------------------------------------------------------------------------
# The compiled program.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BoundProgram:
    """A layer graph bound to parameters: one cell per layer.

    ``run`` executes layer by layer (every cell scanned over T in turn);
    the fused single-scan alternative over the same cells lives in
    :func:`repro.plan.streaming.run_streaming`.
    """

    backend: str
    stages: Tuple[Tuple[LayerSpec, LayerCell], ...]

    def run(self, frames: jax.Array) -> Tuple[jax.Array, Dict[str, Dict]]:
        """(T, IC0, W) frames -> (logits, per-conv-layer counters)."""
        x = frames
        logits = None
        counters: Dict[str, Dict] = {}
        for spec, cell in self.stages:
            ys, _, aux = run_cell(cell, x)
            if spec.kind == KIND_READOUT:
                logits = aux
            elif aux is not None:
                counters[spec.name] = aux
            x = ys
        return (logits if logits is not None else x), counters

    def __call__(self, frames: jax.Array) -> jax.Array:
        return self.run(frames)[0]

    def batch(self, frames_b: jax.Array) -> jax.Array:
        """(B, T, IC0, W) -> (B, n_classes)."""
        return jax.vmap(lambda f: self.run(f)[0])(frames_b)


def _contains_tracer(*trees) -> bool:
    return any(
        isinstance(leaf, jax.core.Tracer)
        for tree in trees
        for leaf in jax.tree_util.tree_leaves(tree)
    )


@dataclasses.dataclass(frozen=True)
class SNNProgram:
    """An ``SNNConfig`` compiled into an executable layer graph."""

    cfg: SNNConfig
    layers: Tuple[LayerSpec, ...]

    @classmethod
    def from_config(cls, cfg: SNNConfig) -> "SNNProgram":
        return cls(cfg=cfg, layers=build_layer_graph(cfg))

    # -- binding / execution ------------------------------------------------

    def _bind(self, params, backend: str = "dense", *, masks=None,
              quant_fn=None, layers: Optional[Sequence[LayerSpec]] = None) -> BoundProgram:
        """Resolve every layer against ``backend`` and close over params.

        This is the raw (uncached) binding primitive: artifacts are derived
        from scratch on every call.  Traceable (dense) binds belong here;
        concrete-weight callers should go through
        :func:`repro.plan.compile_plan` instead.
        """
        specs = self.layers if layers is None else tuple(layers)
        validate_unique_names(specs)
        stages = []
        for spec in specs:
            factory = get_backend(backend, spec.kind)
            lp, m = self._layer_params(spec, params, masks)
            stages.append((spec, factory(spec, lp, cfg=self.cfg, mask=m,
                                         quant_fn=quant_fn)))
        return BoundProgram(backend=backend, stages=tuple(stages))

    def bind(self, params, backend: str = "dense", *, masks=None,
             quant_fn=None, layers: Optional[Sequence[LayerSpec]] = None) -> BoundProgram:
        """Deprecated: use :func:`repro.plan.compile_plan` (cached
        artifacts, per-layer assignment, fused streaming executor) for
        concrete weights, or :meth:`apply`/:meth:`apply_batch` for traced
        execution."""
        warnings.warn(
            "SNNProgram.bind is deprecated; use repro.plan.compile_plan "
            "(cached ExecutionPlans, per-layer backend assignment, fused "
            "streaming) or SNNProgram.apply for traced execution",
            DeprecationWarning, stacklevel=2)
        return self._bind(params, backend, masks=masks, quant_fn=quant_fn,
                          layers=layers)

    def _cached_plan(self, params, backend, masks, quant_fn):
        """A cached ExecutionPlan for concrete params, else None.

        Repeated ``apply`` calls on unchanged weights (trainer eval loops,
        notebook sessions) hit the content-addressed plan cache instead of
        re-deriving COO kernels and schedules.  Traced params (under
        jit/vmap/grad) cannot be hashed and fall back to a direct bind.
        """
        if _contains_tracer(params, masks):
            return None
        try:
            from repro.plan import compile_plan

            return compile_plan(self, params, masks=masks, quant_fn=quant_fn,
                                assignment=backend)
        except jax.errors.TracerArrayConversionError:
            # concrete params but a quant_fn closing over traced scales
            return None

    def apply(self, params, frames: jax.Array, backend: str = "dense", *,
              masks=None, quant_fn=None, return_counters: bool = False):
        """One sample (T, IC0, W) -> logits (n_classes,).

        With ``return_counters=True`` also returns the per-conv-layer
        iteration counters (populated by the ``stream`` backend: the
        compute/extra/empty reps and gated accumulation counts of paper
        Tables I/III; empty for the other backends).
        """
        plan = self._cached_plan(params, backend, masks, quant_fn)
        if plan is not None:
            logits, counters = plan.run_layered(frames)
        else:
            logits, counters = self._bind(
                params, backend, masks=masks, quant_fn=quant_fn).run(frames)
        return (logits, counters) if return_counters else logits

    def apply_batch(self, params, frames_b: jax.Array, backend: str = "dense",
                    *, masks=None, quant_fn=None) -> jax.Array:
        """(B, T, IC0, W) -> (B, n_classes)."""
        plan = self._cached_plan(params, backend, masks, quant_fn)
        if plan is not None:
            return plan.bound.batch(frames_b)
        return self._bind(params, backend, masks=masks,
                          quant_fn=quant_fn).batch(frames_b)

    def run_layers(self, layers: Sequence[LayerSpec], params, x: jax.Array,
                   backend: str = "dense", *, masks=None, quant_fn=None):
        """Execute a contiguous slice of the graph (pipeline stages)."""
        return self._bind(params, backend, masks=masks, quant_fn=quant_fn,
                          layers=layers).run(x)[0]

    # -- graph slicing (pipeline-parallel stage construction) ---------------

    def conv_block(self, i: int) -> Tuple[LayerSpec, ...]:
        """The (Conv1dLIF, MaxPool) pair for conv stage ``i``."""
        convs = [j for j, s in enumerate(self.layers) if s.kind == KIND_CONV]
        j = convs[i]
        return self.layers[j:j + 2]

    def head_layers(self) -> Tuple[LayerSpec, ...]:
        """Everything from the first FC layer through the readout."""
        first_fc = next(j for j, s in enumerate(self.layers) if s.kind == KIND_FC)
        return self.layers[first_fc:]

    # -- params plumbing ----------------------------------------------------

    @staticmethod
    def _layer_params(spec: LayerSpec, params, masks):
        if spec.kind == KIND_CONV:
            return params["conv"][spec.index], (
                masks["conv"][spec.index] if masks else None)
        if spec.kind == KIND_FC:
            return params["fc"][spec.index], (
                masks["fc"][spec.index] if masks else None)
        return None, None


@functools.lru_cache(maxsize=None)
def compile_snn(cfg: SNNConfig) -> SNNProgram:
    """Compile (and cache) the layer graph for ``cfg``."""
    return SNNProgram.from_config(cfg)
