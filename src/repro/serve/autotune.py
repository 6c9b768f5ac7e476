"""Warmup-race backend autotuner for the serving tier.

The layer-graph API makes every execution dataflow interchangeable
(``dense`` / ``goap`` / ``pallas`` / ``stream`` produce identical logits),
but their *cost* is wildly platform-dependent: the COO gather dataflow that
wins on the paper's accelerator loses to the im2col matmul oracle on a
wide-SIMD CPU, and the Pallas block-sparse kernel only pays off on a real
TPU (on the CPU the kernels run in interpret mode).

So the engine does what the hardware cannot: at bind time it **races** the
candidate backends on the exact batch shape it is about to serve — compile,
warm up, time a few repetitions — and pins the winner for the lifetime of
the binding.  A candidate that raises (missing TPU, unsupported layout,
bind-under-trace error) is recorded and excluded; if every candidate fails
the tuner falls back to ``goap``, the paper's reference dataflow, which
binds from plain numpy artifacts on any host.

Two granularities:

* :func:`autotune_backend` — one winner for the whole network (the
  classic mode);
* :func:`autotune_per_layer` — each conv/FC layer raced independently on
  its own input shape (the plan compiler's cost-model priors are logged
  alongside the measurements), producing a heterogeneous
  ``{layer: backend}`` assignment that :func:`repro.plan.compile_plan`
  turns into a fused streaming plan
  (``AsyncAMCServeEngine(backend="per-layer")``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "AutotuneReport",
    "PerLayerAutotuneReport",
    "default_candidates",
    "autotune_backend",
    "autotune_per_layer",
]

# Pallas kernels run in interpret mode on the CPU (repro.kernels.platform),
# orders of magnitude off the pace: they compete only on a TPU, where they
# compile.  A candidate the chip's compiler refuses lands in
# ``AutotuneReport.errors``, which the serve driver prints.
_CPU_CANDIDATES = ("dense", "goap")
_TPU_CANDIDATES = ("dense", "goap", "pallas", "pallas_fused")

# Backends whose fast path is the whole-network fused kernel, not the
# layer-by-layer bound program: raced through a compiled plan's
# ``preferred_batch`` so the stopwatch times what would actually serve.
_FUSED_BACKENDS = ("pallas_fused",)


@dataclasses.dataclass(frozen=True)
class _FusedBinding:
    """Minimal bound-program stand-in for fused-kernel candidates (the
    engine's ``make_fn`` only touches ``.batch`` and ``.backend``)."""

    backend: str
    batch: Callable


def default_candidates(quantized: bool = False) -> Tuple[str, ...]:
    """Backends worth racing on this host.

    ``quantized=True`` (the engine passes it when LSQ state is present)
    additionally races the integer ``fixed`` backend: quantized serving is
    exactly when integer inference is a like-for-like candidate.
    """
    base = _TPU_CANDIDATES if jax.default_backend() == "tpu" else _CPU_CANDIDATES
    return base + ("fixed",) if quantized else base


@dataclasses.dataclass(frozen=True)
class AutotuneReport:
    """Outcome of one warmup race (kept on the engine for introspection)."""

    choice: str
    timings_ms: Dict[str, float]      # successful candidates -> mean wall ms
    errors: Dict[str, str]            # failed candidates -> error summary
    batch_shape: Tuple[int, ...]
    fell_back: bool = False           # True when every candidate raised

    def summary(self) -> dict:
        return {
            "choice": self.choice,
            "timings_ms": dict(self.timings_ms),
            "errors": dict(self.errors),
            "batch_shape": list(self.batch_shape),
            "fell_back": self.fell_back,
        }


def autotune_backend(
    program,
    params,
    batch_shape: Sequence[int],
    *,
    masks=None,
    quant_fn=None,
    candidates: Optional[Sequence[str]] = None,
    reps: int = 2,
    budget_s: float = 5.0,
    fallback: str = "goap",
    make_fn: Optional[Callable] = None,
) -> AutotuneReport:
    """Race ``candidates`` on ``batch_shape`` and pin the fastest.

    ``make_fn(bound)`` builds the callable to time from a
    :class:`~repro.models.graph.BoundProgram` — the engine passes its full
    fused step (encode + forward + shard_map) so the race measures what
    will actually serve; default is the jitted ``bound.batch``.

    Candidates are always scored on post-warmup (steady-state) runs so a
    slow-to-compile but fast-to-run backend is never penalized for its
    compile time; a candidate whose warmup already exceeded ``budget_s``
    gets a single timed rep instead of ``reps`` (bounds how long a
    genuinely slow candidate can stall engine start-up).
    """
    candidates = tuple(candidates) if candidates is not None else default_candidates()
    timings: Dict[str, float] = {}
    errors: Dict[str, str] = {}
    probe = jnp.zeros(tuple(batch_shape), jnp.float32)
    for name in candidates:
        try:
            if hasattr(quant_fn, "reset"):
                # a candidate that raised mid-bind must not skew the next
                # candidate's layer-order fake-quant index
                quant_fn.reset()
            if name in _FUSED_BACKENDS:
                from repro.plan import compile_plan

                plan = compile_plan(program, params, masks=masks,
                                    quant_fn=quant_fn, assignment=name)
                bound = _FusedBinding(backend=name,
                                      batch=plan.preferred_batch())
            else:
                bound = program._bind(params, name, masks=masks,
                                      quant_fn=quant_fn)
            fn = jax.jit(bound.batch) if make_fn is None else make_fn(bound)
            timings[name] = _time_steady_state(fn, probe, reps, budget_s)
        except Exception as e:  # noqa: BLE001 — any failure disqualifies
            errors[name] = f"{type(e).__name__}: {e}"
    if timings:
        choice, fell_back = min(timings, key=timings.get), False
    else:
        choice, fell_back = fallback, True
    return AutotuneReport(choice=choice, timings_ms=timings, errors=errors,
                          batch_shape=tuple(batch_shape), fell_back=fell_back)


def _time_steady_state(fn, probe, reps: int, budget_s: float) -> float:
    """Mean post-warmup wall ms of ``fn(probe)`` (shared race stopwatch)."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(probe))           # compile + warm up
    warm = time.perf_counter() - t0
    n_reps = 1 if warm > budget_s else max(1, reps)
    t0 = time.perf_counter()
    for _ in range(n_reps):
        jax.block_until_ready(fn(probe))
    return (time.perf_counter() - t0) / n_reps * 1e3


# ---------------------------------------------------------------------------
# Per-layer mode: one race per weighted layer, priors from the plan compiler.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PerLayerAutotuneReport:
    """Outcome of a layer-by-layer backend race."""

    assignment: Dict[str, str]                 # layer name -> winning backend
    timings_ms: Dict[str, Dict[str, float]]    # layer -> candidate -> mean ms
    priors: Dict[str, Dict[str, float]]        # layer -> candidate -> predicted cost
    errors: Dict[str, Dict[str, str]]          # layer -> candidate -> error
    batch: int
    fell_back: Tuple[str, ...] = ()            # layers decided by prior/fallback

    def summary(self) -> dict:
        return {
            "assignment": dict(self.assignment),
            "timings_ms": {k: dict(v) for k, v in self.timings_ms.items()},
            "priors": {k: dict(v) for k, v in self.priors.items()},
            "errors": {k: dict(v) for k, v in self.errors.items()},
            "batch": self.batch,
            "fell_back": list(self.fell_back),
        }


def _layer_probe_shapes(program, batch: int):
    """(spec, probe shape) for every weighted layer, tracking pooling."""
    cfg = program.cfg
    width = cfg.input_width
    shapes = []
    for spec in program.layers:
        if spec.kind == "conv_lif":
            shapes.append((spec, (batch, cfg.timesteps, spec.ic, width)))
        elif spec.kind == "maxpool":
            width //= spec.pool
        elif spec.kind == "fc_lif":
            shapes.append((spec, (batch, cfg.timesteps, spec.din)))
    return shapes


def autotune_per_layer(
    program,
    params,
    batch: int,
    *,
    masks=None,
    quant_fn=None,
    candidates: Optional[Sequence[str]] = None,
    reps: int = 2,
    budget_s: float = 5.0,
    fallback: str = "goap",
    cache=None,
) -> PerLayerAutotuneReport:
    """Race candidate backends **layer by layer** on each layer's own
    input shape, producing a heterogeneous assignment map.

    Every surviving candidate is fully timed and the measured minimum
    wins; the plan compiler's cost-model predictions are advisory —
    they set the race order (cheapest-predicted compiles first) and are
    recorded per layer in the report for offline comparison against the
    measurements.  A layer whose every candidate raises falls back to
    ``fallback``, which the prior plan has already bound successfully on
    this host.  Each candidate's cells come from one cached
    ``compile_plan``, so the race never re-derives COO/schedule/
    block-sparse artifacts the artifact cache already holds.  Feed the
    returned ``assignment`` to :func:`repro.plan.compile_plan`.
    """
    from repro.models.graph import BoundProgram
    from repro.plan import compile_plan

    candidates = tuple(candidates) if candidates is not None else default_candidates()
    cache_kw = {"cache": cache} if cache is not None else {}
    # prior plan: derives each layer's artifacts once (shared with every
    # candidate plan through the artifact cache) and yields cost priors
    prior_plan = compile_plan(program, params, masks=masks, quant_fn=quant_fn,
                              assignment=fallback, **cache_kw)
    priors_all = prior_plan.cost_priors()

    # one (cached) whole-network plan per candidate; its per-layer cells
    # are raced in isolation below.  A candidate whose plan fails to
    # compile is excluded everywhere.
    candidate_plans = {fallback: prior_plan}
    candidate_errors: Dict[str, str] = {}

    def plan_for(cand: str):
        if cand in candidate_errors:
            return None
        if cand not in candidate_plans:
            try:
                candidate_plans[cand] = compile_plan(
                    program, params, masks=masks, quant_fn=quant_fn,
                    assignment=cand, **cache_kw)
            except Exception as e:  # noqa: BLE001 — exclude the candidate
                candidate_errors[cand] = f"{type(e).__name__}: {e}"
                return None
        return candidate_plans[cand]

    rng = np.random.default_rng(0)
    assignment: Dict[str, str] = {}
    timings: Dict[str, Dict[str, float]] = {}
    errors: Dict[str, Dict[str, str]] = {}
    priors: Dict[str, Dict[str, float]] = {}
    fell_back = []
    for spec, shape in _layer_probe_shapes(program, batch):
        prior = priors_all.get(spec.name, {})
        priors[spec.name] = {k: v for k, v in prior.items() if k in candidates}
        order = sorted(candidates, key=lambda c: prior.get(c, float("inf")))
        probe = jnp.asarray((rng.random(shape) < 0.5).astype(np.float32))
        lt: Dict[str, float] = {}
        le: Dict[str, str] = {}
        for cand in order:
            plan_c = plan_for(cand)
            if plan_c is None:
                le[cand] = candidate_errors[cand]
                continue
            cell = next(lp.cell for lp in plan_c.layers
                        if lp.spec.name == spec.name)
            bound = BoundProgram(backend=cand, stages=((spec, cell),))
            try:
                lt[cand] = _time_steady_state(jax.jit(bound.batch), probe,
                                              reps, budget_s)
            except Exception as e:  # noqa: BLE001 — exclude the candidate
                le[cand] = f"{type(e).__name__}: {e}"
        timings[spec.name], errors[spec.name] = lt, le
        if lt:
            assignment[spec.name] = min(lt, key=lt.get)
        else:
            # every candidate raised for this layer: use the fallback
            # backend, which the prior plan above already bound successfully
            # (a failed candidate must never land in the assignment — the
            # engine would re-hit the same error at compile_plan time)
            assignment[spec.name] = fallback
            fell_back.append(spec.name)
    return PerLayerAutotuneReport(
        assignment=assignment, timings_ms=timings, priors=priors,
        errors=errors, batch=batch, fell_back=tuple(fell_back))
