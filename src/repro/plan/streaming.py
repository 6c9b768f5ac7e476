"""Fused single-scan inter-layer executor (the paper's streaming pipeline).

The accelerator streams spikes through all layers concurrently with zero
inter-layer buffering of whole timestep sequences (paper §III, Fig. 6).
The jax analogue: instead of one ``lax.scan`` per layer materializing the
full (T, ...) activation sequence before the next layer starts
(``BoundProgram.run``), :func:`run_streaming` threads *every* layer's
carried state — conv/FC membrane potentials, stream-counter accumulators,
the readout sum — through a **single** scan over timesteps.  Per timestep
each frame flows through the whole cell chain, so no intermediate
sequence is ever materialized.

Because every cell is causal per timestep (layer *l*'s output at *t*
depends only on its state and its input at *t*), the fusion is exact:
logits agree with the layer-by-layer path for every backend (validated at
atol <= 1e-5 in ``tests/test_plan.py``), and the ``stream`` backend's
Tables I/III counters come out identical.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax

from repro.models.graph import KIND_READOUT, LayerCell, timestep_template

__all__ = ["init_stream_states", "run_streaming"]


def init_stream_states(cells: Sequence[LayerCell], x0) -> Tuple:
    """Initial carried state of every cell, chained through the graph.

    ``x0`` is the per-timestep input template of the *first* layer; each
    subsequent layer's template is inferred by abstract evaluation of the
    previous cell's ``step`` (no FLOPs run here).
    """
    states = []
    x = x0
    for cell in cells:
        state = cell.init_state(x)
        states.append(state)
        _, x = jax.eval_shape(cell.step, state, x)
    return tuple(states)


def run_streaming(plan, frames: jax.Array):
    """Execute an ExecutionPlan in one fused scan over timesteps.

    frames: (T, IC0, W) binary spike frames.  Returns ``(logits,
    counters)`` with the same contract as ``BoundProgram.run``: counters
    carries the per-conv-layer iteration counts when the ``stream``
    backend is assigned (empty otherwise).

    When every weighted layer is assigned ``pallas_fused`` the scan
    collapses into one multi-layer Pallas kernel launch with all LIF
    state in VMEM (:mod:`repro.kernels.stream_fused`); its counters are
    the same Tables I/III quantities, computed in-kernel.
    """
    from repro.kernels.stream_fused import (
        fused_counters,
        fused_stack_of,
        stream_fused_forward,
    )

    stack = fused_stack_of(plan)
    if stack is not None:
        logits, accs = stream_fused_forward(stack, frames[None])
        return logits[0], fused_counters(stack, accs[0])

    cells = [lp.cell for lp in plan.layers]
    states0 = init_stream_states(cells, timestep_template(frames))

    def step(states, frame_t):
        x = frame_t
        new_states = []
        for cell, state in zip(cells, states):
            state, x = cell.step(state, x)
            new_states.append(state)
        return tuple(new_states), x

    states, ys = jax.lax.scan(step, states0, frames)

    logits = None
    counters = {}
    for lp, state in zip(plan.layers, states):
        if lp.cell.finalize is None:
            continue
        out = lp.cell.finalize(state)
        if lp.spec.kind == KIND_READOUT:
            logits = out
        else:
            counters[lp.spec.name] = out
    return (logits if logits is not None else ys), counters
