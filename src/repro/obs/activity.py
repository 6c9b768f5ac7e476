"""Live sparsity/activity telemetry: the paper's Tables I/III, per batch.

The paper's central claim is activity-proportional cost: GOAP computes
only the non-zero input x weight intersections, so the iteration schedule
(Table I — reps/compute/extra/empty per conv layer, fixed by the masked
weights) and the gated accumulation counts (Table III — input-dependent)
*are* the cost model.  The ``stream`` and ``pallas_fused`` backends
already produce those counters in-graph; this module surfaces them on
the serving path as live per-batch gauges:

* ``repro_activity_schedule{layer,counter}`` — the static Table I
  geometry (input-independent, set once at bind time);
* ``repro_activity_accumulations_total{engine,layer}`` — cumulative
  gated accumulations over real (non-padded) served frames;
* ``repro_activity_events_per_frame{engine,layer}`` — mean accumulations
  per frame in the last batch;
* ``repro_activity_accum_ratio_vs_dense{engine,layer}`` — last-batch
  events/frame over the dense MAC count (kw*ic*oc*W*T): the
  sparsity-proportionality readout (Table III's ratio);
* ``repro_activity_effective_density{engine,layer}`` — events/frame over
  nnz*W*T: the effective input-activity fraction the schedule saw.

Exactness: counters are carried in float32 on-device; every pinned
golden value (max 306721) is far below 2**24, so the live gauges agree
*bit-exactly* with ``tests/test_stream_golden.py`` literals on the paper
config (asserted in ``tests/test_obs.py``).
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional

import numpy as np

from repro.obs.metrics import MetricsRegistry, default_registry

__all__ = ["SCHEDULE_KEYS", "ActivityObserver", "static_schedule_counts"]

#: Table I keys: fixed by the masked weights, independent of the input.
SCHEDULE_KEYS = ("reps_per_timestep", "compute_iters", "extra_iters",
                 "empty_iters")


def static_schedule_counts(plan) -> Dict[str, Dict[str, int]]:
    """Per-conv-layer Table I schedule geometry of a counter-capable plan.

    The fused multi-layer kernel precomputes these at stack build time;
    the ``stream`` assignment carries them in its schedule interpreter,
    so one eager pass over an all-zero frame (zero input activity — the
    accumulation counters stay 0, the schedule counters are constants)
    reads them out without touching any serving state.
    """
    from repro.kernels.stream_fused import FusedConv

    stack = plan.fused_stack()
    if stack is not None:
        return {layer.name: dict(layer.static_counts)
                for layer in stack.layers if isinstance(layer, FusedConv)}
    import jax.numpy as jnp

    cfg = plan.cfg
    zeros = jnp.zeros((cfg.timesteps, cfg.conv_specs[0][1],
                       cfg.input_width), jnp.float32)
    _, counters = plan.run_streaming(zeros)
    return {name: {k: int(np.asarray(c[k])) for k in SCHEDULE_KEYS}
            for name, c in counters.items()}


def _conv_geometry(plan) -> List[Dict[str, float]]:
    """Per conv layer: name, nnz, input width, T — the gauge denominators.

    Widths walk the layer graph: ``pad_same`` convs preserve width, each
    pool divides it, so layer order (not just conv_specs) decides.
    """
    from repro.models.graph import KIND_CONV, KIND_POOL

    cfg = plan.cfg
    width = cfg.input_width
    out = []
    for lp in plan.layers:
        if lp.spec.kind == KIND_CONV:
            nnz = int(lp.cost.get("nnz", lp.spec.kw * lp.spec.ic * lp.spec.oc))
            out.append({
                "name": lp.spec.name,
                "nnz": nnz,
                "width": width,
                "dense_macs_per_frame":
                    float(lp.spec.kw * lp.spec.ic * lp.spec.oc
                          * width * cfg.timesteps),
                "sparse_macs_per_frame": float(nnz * width * cfg.timesteps),
            })
        elif lp.spec.kind == KIND_POOL:
            width = width // max(1, lp.spec.pool)
    return out


class ActivityObserver:
    """Records one plan's per-batch activity counters into the registry.

    Built once per bound version (bind time, off the hot path); per batch
    the serving worker calls :meth:`observe` with the counter dict the
    plan's ``batch_counters`` step returned — a handful of guarded float
    adds, no device work.
    """

    def __init__(self, plan, registry: Optional[MetricsRegistry] = None,
                 engine: str = "engine"):
        reg = registry if registry is not None else default_registry()
        self.engine = engine
        self.geometry = _conv_geometry(plan)
        self.timesteps = int(plan.cfg.timesteps)

        sched = reg.gauge(
            "repro_activity_schedule",
            "Table I static schedule geometry per conv layer "
            "(reps_per_timestep/compute_iters/extra_iters/empty_iters)",
            ("layer", "counter"))
        for name, counts in static_schedule_counts(plan).items():
            for key, val in counts.items():
                sched.labels(layer=name, counter=key).set(val)

        self._frames = reg.counter(
            "repro_activity_frames_total",
            "Real (non-padded) frames whose activity was counted",
            ("engine",)).labels(engine=engine)
        fam_acc = reg.counter(
            "repro_activity_accumulations_total",
            "Cumulative gated accumulations (Table III) over served frames",
            ("engine", "layer"))
        fam_epf = reg.gauge(
            "repro_activity_events_per_frame",
            "Mean gated accumulations per frame in the last served batch",
            ("engine", "layer"))
        fam_ratio = reg.gauge(
            "repro_activity_accum_ratio_vs_dense",
            "Last-batch events/frame over the dense MAC count "
            "(kw*ic*oc*W*T): the sparsity-proportionality readout",
            ("engine", "layer"))
        fam_dens = reg.gauge(
            "repro_activity_effective_density",
            "Last-batch events/frame over nnz*W*T: effective input-"
            "activity fraction", ("engine", "layer"))
        self._per_layer = {
            g["name"]: {
                "geom": g,
                "acc": fam_acc.labels(engine=engine, layer=g["name"]),
                "epf": fam_epf.labels(engine=engine, layer=g["name"]),
                "ratio": fam_ratio.labels(engine=engine, layer=g["name"]),
                "density": fam_dens.labels(engine=engine, layer=g["name"]),
            }
            for g in self.geometry
        }

    def observe(self, accumulations: Mapping[str, np.ndarray],
                n_real: int) -> None:
        """Account one served batch.

        ``accumulations``: per-conv-layer ``(B,)`` gated accumulation
        counts from the plan's counter-returning batch step.  Only the
        first ``n_real`` rows are real — the batcher pads the tail, and
        padded rows must never leak into activity stats (their all-zero
        frames contribute zero accumulations, but counting their frames
        would still dilute the per-frame gauges).
        """
        if n_real <= 0:
            return
        self._frames.inc(n_real)
        for name, handles in self._per_layer.items():
            acc = accumulations.get(name)
            if acc is None:
                continue
            # sum in float64: a batch total passes 2**24 (the float32
            # exactness limit) on the paper config at batch 64
            total = float(np.asarray(acc)[:n_real].sum(dtype=np.float64))
            per_frame = total / n_real
            g = handles["geom"]
            handles["acc"].inc(total)
            handles["epf"].set(per_frame)
            handles["ratio"].set(per_frame / g["dense_macs_per_frame"])
            handles["density"].set(per_frame / g["sparse_macs_per_frame"])
