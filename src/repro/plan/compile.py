"""Plan compiler: every bind-time artifact derived once, content-hashed.

``compile_plan(program, params, masks=..., quant_fn=..., assignment=...)``
resolves each layer of an :class:`~repro.models.graph.SNNProgram` against
its assigned backend and precomputes the derived artifacts — COO kernels,
Algorithm-2 iteration schedules, block-sparse tile lists, effective
(masked + quantized) weights — plus cost-model priors, into an immutable
:class:`ExecutionPlan`.

Plans are content-hashed on (config, per-layer backend assignment,
effective weight bytes, mask bytes, LIF parameter bytes): two calls with
identical inputs return the *same* plan object from the in-memory cache,
and a fresh process reloads the expensive numpy artifacts from the
on-disk tier instead of rebuilding them.  The
``repro.models.graph.ARTIFACT_BUILDS`` counter records every genuine
derivation, so "the second compile is a cache hit" is testable.

``assignment`` is either one backend name for the whole network or a
mapping ``{layer_name: backend}`` (unlisted layers fall back to
``default_backend``) — the per-layer form is what the serving tier's
layer-by-layer autotuner produces.
"""
from __future__ import annotations

import dataclasses
import hashlib
import inspect
from typing import Any, Callable, Dict, Mapping, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.models.graph import (
    KIND_CONV,
    KIND_FC,
    PALLAS_BLOCK_K,
    PALLAS_BLOCK_OC,
    BoundProgram,
    LayerCell,
    LayerSpec,
    SNNProgram,
    _effective_weight,
    artifact_build_count,
    get_backend,
    validate_unique_names,
)
from repro.models.snn import SNNConfig
from repro.plan.cache import PlanCache, default_cache

__all__ = [
    "LayerPlan",
    "ExecutionPlan",
    "compile_plan",
    "artifact_build_count",
]

# Cache format version: bump whenever an artifact *builder* changes
# semantics (COO sort order, schedule construction, block-sparse tiling,
# hashing rules) — on-disk entries under the old version must never
# satisfy a new build.
_VERSION = b"repro-plan-v1|"


# ---------------------------------------------------------------------------
# Content hashing.
# ---------------------------------------------------------------------------

def _hash_arrays(h, *arrays) -> None:
    for a in arrays:
        a = np.asarray(a)
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(np.ascontiguousarray(a).tobytes())


def _effective_np(layer_params, mask, quant_fn) -> Optional[np.ndarray]:
    """Concrete effective weights, or None for pre-sparsified params.

    Delegates to the factories' own ``_effective_weight`` so the hashed
    bytes always match the derivation semantics the cells execute.
    Raises ``jax.errors.TracerArrayConversionError`` under tracing — the
    caller falls back to a direct (uncached) bind in that case.
    """
    if "coo" in layer_params:
        return None
    return np.asarray(_effective_weight(layer_params, mask, quant_fn))


def _layer_key(spec: LayerSpec, layer_params, mask,
               w_eff: Optional[np.ndarray]) -> str:
    """Artifact-cache key for one layer.

    Deliberately excludes the backend name: COO kernels, schedules and
    block-sparse tilings for the same effective weights live in one entry
    that the goap/stream/pallas backends extend cooperatively.
    """
    h = hashlib.sha256(_VERSION)
    h.update(repr(spec).encode())
    if w_eff is not None:
        _hash_arrays(h, w_eff)
    elif layer_params is not None and "coo" in layer_params:
        coo = layer_params["coo"]
        h.update(f"coo:{coo.kw}:{coo.ic}:{coo.oc}".encode())
        _hash_arrays(h, coo.data, coo.row_idx, coo.col_idx)
    if mask is not None:
        _hash_arrays(h, mask)
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Cost-model priors (per-layer backend race ordering).
# ---------------------------------------------------------------------------

def _conv_dense_of(layer_params, w_eff) -> Optional[np.ndarray]:
    if w_eff is not None:
        return w_eff
    if layer_params is not None and "coo" in layer_params:
        from repro.core.sparse_format import coo_to_dense

        return coo_to_dense(layer_params["coo"])
    return None


def _tile_mults(w: np.ndarray, block_oc: int = PALLAS_BLOCK_OC,
                block_k: int = PALLAS_BLOCK_K) -> int:
    """MACs the static block-sparse layout executes per output position."""
    kw, ic, oc = w.shape
    flat = np.transpose(w, (2, 1, 0)).reshape(oc, ic * kw)
    pad_oc = (-flat.shape[0]) % block_oc
    pad_k = (-flat.shape[1]) % block_k
    flat = np.pad(flat, ((0, pad_oc), (0, pad_k)))
    tiles = flat.reshape(flat.shape[0] // block_oc, block_oc,
                         flat.shape[1] // block_k, block_k)
    nonempty = int((np.abs(tiles).sum(axis=(1, 3)) != 0).sum())
    return max(1, nonempty) * block_oc * block_k


def _layer_cost(spec: LayerSpec, backend: str, layer_params, w_eff,
                artifacts: Optional[dict]) -> Dict[str, Any]:
    """Analytic work predictions per candidate backend (relative units).

    These are *priors*, not measurements: MAC/iteration counts per output
    position derived from the effective weights (``core.cost_model``
    counting rules), used to order candidates in the per-layer autotune
    race and as its choice of last resort.  Deterministic in the call's
    inputs: the exact Algorithm-2 reps are used only when *this* compile
    assigned the ``stream`` backend (which builds the schedule); otherwise
    the nnz + OC estimate applies regardless of what the shared artifact
    cache happens to hold.
    """
    artifacts = artifacts or {}
    if spec.kind == KIND_CONV:
        total = spec.kw * spec.ic * spec.oc
        dense_w = _conv_dense_of(layer_params, w_eff)
        coo = artifacts.get("coo")
        if coo is not None:
            nnz = coo.nnz
        elif dense_w is not None:
            nnz = int((np.asarray(dense_w) != 0).sum())
        else:
            return {}
        sched = artifacts.get("schedule") if backend == "stream" else None
        # reps = nnz + extra + empty (paper Table III); without the built
        # schedule, extra iterations are bounded by OC and empties by IC
        reps = sched.reps if sched is not None else nnz + spec.oc
        priors = {"dense": float(total), "goap": float(reps)}
        if dense_w is not None:
            priors["pallas"] = float(_tile_mults(np.asarray(dense_w)))
        return {"nnz": int(nnz), "density": nnz / max(1, total),
                "reps": int(reps), "backend_priors": priors}
    if spec.kind == KIND_FC:
        total = spec.din * spec.dout
        nnz = int((np.asarray(w_eff) != 0).sum()) if w_eff is not None else total
        # the WM method skips *work*, not slots (paper §V-C.2): every FC
        # backend runs the same matmul shape, so priors tie at the padded
        # matmul size and the conv layers decide heterogeneous splits
        pad = (-spec.dout) % PALLAS_BLOCK_K
        priors = {"dense": float(total), "goap": float(total),
                  "pallas": float(spec.din * (spec.dout + pad))}
        return {"nnz": nnz, "density": nnz / max(1, total),
                "backend_priors": priors}
    return {}


# ---------------------------------------------------------------------------
# The plan.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One layer of an ExecutionPlan: spec + assigned backend + live cell."""

    spec: LayerSpec
    backend: str
    cell: LayerCell
    cost: Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """An immutable, fully-precomputed execution of one SNN.

    * ``run_streaming(frames)`` — all layers fused into a single scan over
      timesteps (the paper's inter-layer pipeline); when every weighted
      layer is assigned ``pallas_fused`` this collapses further into one
      multi-layer Pallas kernel launch
      (:mod:`repro.kernels.stream_fused`);
    * ``run_layered(frames)`` — the layer-by-layer reference path over the
      same cells (used for validation and legacy ``apply`` semantics);
    * ``batch(frames_b)`` — batched fused executor (the multi-layer kernel
      takes the batch into its own grid; other assignments vmap the
      single-sample streaming path).
    """

    cfg: SNNConfig
    assignment: Dict[str, str]
    digest: str
    layers: Tuple[LayerPlan, ...]
    bound: BoundProgram

    def run_streaming(self, frames: jax.Array):
        from repro.plan.streaming import run_streaming

        return run_streaming(self, frames)

    def run_layered(self, frames: jax.Array):
        return self.bound.run(frames)

    def __call__(self, frames: jax.Array) -> jax.Array:
        return self.run_streaming(frames)[0]

    def fused_stack(self):
        """Operands for the single-launch multi-layer kernel, or None
        (None unless every weighted layer is assigned ``pallas_fused``)."""
        from repro.kernels.stream_fused import fused_stack_of

        return fused_stack_of(self)

    def batch(self, frames_b: jax.Array) -> jax.Array:
        """(B, T, IC0, W) -> (B, n_classes) through the fused executor."""
        stack = self.fused_stack()
        if stack is not None:
            from repro.kernels.stream_fused import stream_fused_forward

            return stream_fused_forward(stack, frames_b)[0]
        return jax.vmap(lambda f: self.run_streaming(f)[0])(frames_b)

    def preferred_batch(self):
        """The fastest whole-batch callable this plan offers: the fused
        multi-layer kernel when the assignment provides one, else the
        layer-by-layer bound path (which beats the generic single-scan
        executor on XLA:CPU — see BENCH_fusion.json)."""
        return self.batch if self.fused_stack() is not None else self.bound.batch

    @property
    def supports_live_counters(self) -> bool:
        """True when :meth:`batch_counters` can report per-batch gated
        accumulation counts (Table III) alongside the logits — i.e. the
        assignment's conv layers all count in-graph (``stream`` schedule
        interpreter or the fused multi-layer kernel)."""
        if self.fused_stack() is not None:
            return True
        return all(lp.backend == "stream" for lp in self.layers
                   if lp.spec.kind == KIND_CONV)

    def batch_counters(self, frames_b: jax.Array):
        """(B, T, IC0, W) -> (logits (B, n_classes), {conv_name: (B,) accs}).

        The counter-returning twin of :meth:`batch` — same logits, plus
        per-sample gated accumulation counts for every conv layer.  The
        fused stack already carries the counts in its carry (free); the
        vmapped streaming path extracts only the ``accumulations`` array
        leaf inside the closure so the static int leaves of the counter
        dict never hit vmap.  Counts are float32 throughout; exact below
        2**24 events/frame (paper config peaks at 306721).
        """
        stack = self.fused_stack()
        if stack is not None:
            from repro.kernels.stream_fused import stream_fused_forward

            logits, accs = stream_fused_forward(stack, frames_b)
            return logits, {name: accs[:, i]
                            for i, name in enumerate(stack.conv_names)}

        def one(frames):
            logits, counters = self.run_streaming(frames)
            return logits, {name: jnp.asarray(c["accumulations"], jnp.float32)
                            for name, c in counters.items()
                            if "accumulations" in c}

        return jax.vmap(one)(frames_b)

    def cost_priors(self) -> Dict[str, Dict[str, float]]:
        """Per weighted layer: predicted relative cost per backend."""
        return {lp.spec.name: dict(lp.cost.get("backend_priors", {}))
                for lp in self.layers if lp.cost.get("backend_priors")}

    def summary(self) -> dict:
        return {
            "digest": self.digest,
            "assignment": dict(self.assignment),
            "costs": {lp.spec.name: {k: v for k, v in lp.cost.items()
                                     if k != "backend_priors"}
                      for lp in self.layers if lp.cost},
        }


# ---------------------------------------------------------------------------
# Compilation.
# ---------------------------------------------------------------------------

def _resolve_assignment(specs, assignment: Union[str, Mapping[str, str]],
                        default_backend: str) -> Tuple[Dict[str, str], str]:
    """(per-weighted-layer backend map, backend for common layers)."""
    if isinstance(assignment, str):
        return ({s.name: assignment for s in specs
                 if s.kind in (KIND_CONV, KIND_FC)}, assignment)
    amap = dict(assignment)
    names = {s.name for s in specs}
    unknown = set(amap) - names
    if unknown:
        raise ValueError(
            f"assignment names unknown layers {sorted(unknown)}; graph "
            f"layers are {sorted(names)}")
    weighted = {s.name for s in specs if s.kind in (KIND_CONV, KIND_FC)}
    unweighted = set(amap) - weighted
    if unweighted:
        # silently dropping these would hide both mis-targeted entries and
        # backend typos (they'd never reach get_backend validation)
        raise ValueError(
            f"assignment targets non-weighted layers {sorted(unweighted)}; "
            f"only conv/FC layers take a backend (weighted layers: "
            f"{sorted(weighted)})")
    resolved = {s.name: amap.get(s.name, default_backend) for s in specs
                if s.kind in (KIND_CONV, KIND_FC)}
    return resolved, default_backend


def _call_factory(factory: Callable, spec, lp, cfg, mask, quant_fn,
                  artifacts: Optional[dict]) -> LayerCell:
    """Invoke a backend factory, passing artifacts only if it accepts them
    (third-party factories registered with the plain signature still work —
    they just rebuild from scratch)."""
    try:
        takes_artifacts = "artifacts" in inspect.signature(factory).parameters
    except (TypeError, ValueError):  # builtins / C callables
        takes_artifacts = False
    if takes_artifacts and artifacts is not None:
        return factory(spec, lp, cfg=cfg, mask=mask, quant_fn=quant_fn,
                       artifacts=artifacts)
    return factory(spec, lp, cfg=cfg, mask=mask, quant_fn=quant_fn)


def compile_plan(
    program: SNNProgram,
    params,
    *,
    masks=None,
    quant_fn=None,
    assignment: Union[str, Mapping[str, str]] = "dense",
    default_backend: str = "dense",
    cache: Optional[PlanCache] = None,
) -> ExecutionPlan:
    """Precompute an :class:`ExecutionPlan` (cached by content hash).

    Needs concrete (non-traced) params: artifacts and digests are numpy.
    Under jit/vmap/grad use ``SNNProgram.apply`` (which falls back to a
    direct traceable bind) instead.
    """
    cache = cache if cache is not None else default_cache()
    specs = program.layers
    validate_unique_names(specs)
    resolved, common_backend = _resolve_assignment(specs, assignment,
                                                   default_backend)
    # validate every backend up-front so typos fail before any hashing
    for spec in specs:
        get_backend(resolved.get(spec.name, common_backend), spec.kind)

    # -- content digest -----------------------------------------------------
    h = hashlib.sha256(_VERSION)
    h.update(repr(program.cfg).encode())
    infos = []
    for spec in specs:
        backend = resolved.get(spec.name, common_backend)
        h.update(f"|{spec.name}={backend}|".encode())
        lp, mask = program._layer_params(spec, params, masks)
        if spec.kind in (KIND_CONV, KIND_FC):
            w_eff = _effective_np(lp, mask, quant_fn)
            lkey = _layer_key(spec, lp, mask, w_eff)
            h.update(lkey.encode())
            _hash_arrays(h, *jax.tree_util.tree_leaves(lp["lif"]))
        else:
            w_eff, lkey = None, None
        infos.append((spec, backend, lp, mask, w_eff, lkey))
    digest = h.hexdigest()

    cached = cache.get_plan(digest)
    if cached is not None:
        return cached

    # -- build (or load) per-layer artifacts and cells ----------------------
    lplans = []
    for spec, backend, lp, mask, w_eff, lkey in infos:
        artifacts: Optional[Dict[str, Any]] = None
        before: set = set()
        if lkey is not None:
            artifacts = cache.get_artifacts(lkey)
            if artifacts is None:
                artifacts = {}
            if w_eff is not None and artifacts.get("w_eff") is None:
                artifacts["w_eff"] = w_eff
            before = {k for k, v in artifacts.items() if v is not None}
        factory = get_backend(backend, spec.kind)
        cell = _call_factory(factory, spec, lp, program.cfg, mask, quant_fn,
                             artifacts)
        cost = _layer_cost(spec, backend, lp, w_eff, artifacts) if lkey else {}
        if lkey is not None:
            after = {k for k, v in artifacts.items() if v is not None}
            if after != before:
                cache.put_artifacts(lkey, artifacts)
        lplans.append(LayerPlan(spec=spec, backend=backend, cell=cell,
                                cost=cost))

    label = (assignment if isinstance(assignment, str)
             else "per-layer:" + ",".join(f"{k}={v}" for k, v in
                                          sorted(resolved.items())))
    bound = BoundProgram(backend=label,
                         stages=tuple((lp.spec, lp.cell) for lp in lplans))
    plan = ExecutionPlan(cfg=program.cfg, assignment=resolved, digest=digest,
                         layers=tuple(lplans), bound=bound)
    cache.put_plan(digest, plan)
    return plan
