"""SPMD pipeline-stage runner: the paper's inter-layer streaming on a mesh.

The SAOCDS accelerator instantiates every SNN layer as its own hardware
stage and streams activations stage-to-stage with no global control logic
(paper §III).  On a TPU mesh the same structure is pipeline parallelism:
each device along a ``stage`` axis holds one stage's params, microbatches
stream through via ``ppermute``, and the schedule is a *fixed-length* tick
loop — ``n_micro + n_stages - 1`` ticks, bubbles included as explicit
no-op slots, exactly the paper's precomputed empty/extra iterations
(DESIGN.md §2).

Because each tick's ``ppermute`` result is only consumed at the *next*
tick, the transfer of tick *t* overlaps the compute of tick *t* (XLA
schedules the send/recv asynchronously on TPU): compute/comm overlap falls
out of the schedule shape rather than handwritten double buffering.

Stages must share one buffer shape; heterogeneous stages (the SNN's
conv/pool widths) embed into the max-shape buffer — the software analogue
of the accelerator's fixed-width inter-layer stream.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["spmd_pipeline", "stack_stage_params"]


def stack_stage_params(per_stage_params) -> Any:
    """[stage0_tree, stage1_tree, ...] -> one tree with leading stage dim."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *per_stage_params
    )


def spmd_pipeline(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    params: Any,                 # pytree, leaves (n_stages, ...), sharded on stage
    microbatches: jax.Array,     # (n_micro, ...) same buffer shape per stage
    mesh: Mesh,
    *,
    stage_axis: str = "stage",
    collect: str = "psum",
) -> jax.Array:
    """Run ``y_mb = stageN(...stage0(x_mb))`` for every microbatch.

    Returns (n_micro, ...) outputs.  ``stage_fn(stage_params, x) -> y``
    must preserve the buffer shape (pad heterogeneous stages up).

    Only ``stage_axis`` is manual; any other mesh axes (data/model) stay
    in auto mode, so the stage body composes with the usual pjit TP/DP
    sharding — pipeline-over-stages x tensor-parallel-within-stage.

    ``collect``: "psum" broadcasts the last stage's outputs to every
    stage (one all-reduce); "stack" returns them stage-local as a
    (n_stages, n_micro, ...) array whose last row is the result — no
    collective (also dodges an XLA-CPU AllReducePromotion crash in
    mixed manual/auto programs).
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = microbatches.shape[0]
    ticks = n_micro + n_stages - 1

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P() if collect == "psum" else P(stage_axis),
        axis_names={stage_axis},
        # scan carries start as unvarying zeros and become stage-varying
        # after the first ppermute; skip the static vma check
        check_vma=False,
    )
    def run(stage_params, mbs):
        stage_params = jax.tree_util.tree_map(lambda x: x[0], stage_params)
        idx = jax.lax.axis_index(stage_axis)
        buf_shape = mbs.shape[1:]

        def tick(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t (clamped no-op slots at the tail)
            inject = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, n_micro - 1), keepdims=False
            )
            x = jnp.where(idx == 0, inject, state)
            y = stage_fn(stage_params, x)
            # the last stage banks microbatch (t - n_stages + 1)
            out_t = t - (n_stages - 1)
            is_out = jnp.logical_and(idx == n_stages - 1, out_t >= 0)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(is_out, y, jax.lax.dynamic_index_in_dim(
                    outputs, jnp.clip(out_t, 0, n_micro - 1), keepdims=False)),
                jnp.clip(out_t, 0, n_micro - 1),
                axis=0,
            )
            # hand y to the next stage (transfer overlaps next tick's compute)
            nxt = jax.lax.ppermute(
                y, stage_axis, [(i, i + 1) for i in range(n_stages - 1)]
            )
            return (nxt, outputs), None

        state0 = jnp.zeros(buf_shape, mbs.dtype)
        outputs0 = jnp.zeros((n_micro,) + buf_shape, mbs.dtype)
        (_, outputs), _ = jax.lax.scan(
            tick, (state0, outputs0), jnp.arange(ticks)
        )
        # outputs live on the last stage only
        if collect == "psum":
            keep = (idx == n_stages - 1).astype(outputs.dtype)
            return jax.lax.psum(outputs * keep, stage_axis)
        return outputs[None]  # (1, n_micro, ...) per stage -> stacked

    out = run(params, microbatches)
    return out if collect == "psum" else out[-1]
