"""Whole-network streaming SNN as a single multi-layer Pallas kernel.

The paper's accelerator (§III, Fig. 6) streams spikes through every layer
concurrently with *no* control flow and *no* DRAM round-trips: each layer's
membrane potentials and the static Algorithm-2 schedule live on-chip and a
timestep flows conv1 -> pool -> ... -> FC -> readout in one pipeline pass.
This module is the TPU analogue: **one** ``pallas_call`` whose grid is
``(batch, timesteps)`` with time minor, keeping

* every conv/FC layer's membrane potential,
* the Σ-Δ encoder state (when encoding is fused in), and
* the readout/counter accumulators

resident in VMEM scratch across all T grid steps of a sample.  HBM traffic
per timestep is exactly one input frame read; weights are loaded once per
sample (constant ``index_map`` keeps their blocks resident); logits and the
Tables I/III accumulation counters are written once at ``t == T-1``.
Compare the generic fused executor (:mod:`repro.plan.streaming`), which
still launches every layer's XLA ops per scan step, and the per-layer
``pallas`` backend, which costs T x L kernel launches per sample.

The conv inside the kernel uses the GOAP shift-buffer identity: the
frame is expanded to X'(KW*IC, W) (rows ordered ci-major) and the layer
current is one ``(OC, KW*IC) @ (KW*IC, W)`` MXU matmul.  The gated-
accumulation counter of the ``stream`` backend is recovered exactly
(integer-valued f32) as ``counts · row_sums(X')`` where ``counts[r]`` is
the number of non-zero weights mapping to shift-buffer row ``r`` —
summing enable maps per non-zero weight and summing row occupancies are
the same double sum.

Everything that moves spikes between lanes and sublanes — the shift
buffer's taps and row placement, and the max-pool's lane pairs — is a
0/1 selection matmul built from ``iota`` in the kernel.  Selecting binary
spikes is exact at any matmul precision, and it keeps every value 2-D
with no lane-splitting reshape, which Mosaic refuses.  The FC layer
after the last pool consumes its ``(C, W')`` input channel by channel
against sublane-aligned row slices of its weight matrix: the flatten
without a reshape.  Dots that carry weights run at
``Precision.HIGHEST``, so the kernel agrees with the float32 reference
run under ``jax.default_matmul_precision("highest")``.

The kernel compiles through Mosaic on a TPU and runs in interpret mode on
the CPU only (:mod:`repro.kernels.platform`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

__all__ = [
    "FusedConv",
    "FusedFC",
    "FusedPool",
    "FusedReadout",
    "FusedStack",
    "fused_conv_info",
    "fused_fc_info",
    "fused_stack_of",
    "stream_fused_forward",
    "fused_counters",
]

# Layer-kind strings of repro.models.graph (string literals keep kernels/
# import-independent of the model layer; graph.py imports *us* lazily).
_KIND_CONV = "conv_lif"
_KIND_POOL = "maxpool"
_KIND_FC = "fc_lif"
_KIND_READOUT = "readout"


def _lif_rows(lif, n: int) -> np.ndarray:
    """LIFParams -> concrete (3, n) f32 rows [alpha, theta, v_th]."""
    def row(a) -> np.ndarray:
        a = np.asarray(a, dtype=np.float32).reshape(-1)
        if a.size == 1:
            a = np.full((n,), float(a[0]), dtype=np.float32)
        if a.size != n:
            raise ValueError(f"LIF param size {a.size} != {n} neurons")
        return a

    # alpha through the same jax sigmoid the float cells use (f32-exact)
    alpha = np.asarray(jax.nn.sigmoid(jnp.asarray(lif.alpha_logit,
                                                  jnp.float32)))
    return np.stack([row(alpha), row(lif.theta), row(lif.v_th)])


@dataclasses.dataclass(frozen=True)
class FusedConv:
    """One conv layer's VMEM-resident operands (ci-major GOAP layout)."""

    name: str
    kw: int
    ic: int
    oc: int
    w_cm: np.ndarray           # (OC, KW*IC) f32; col r = ci*IC + ic
    counts: np.ndarray         # (KW*IC, 1) f32; nnz per shift-buffer row
    lif: np.ndarray            # (OC, 3) f32 columns: alpha, theta, v_th
    static_counts: Dict[str, int]  # Algorithm-2 reps/compute/extra/empty


@dataclasses.dataclass(frozen=True)
class FusedFC:
    name: str
    w: np.ndarray              # (IN, OUT) f32, zeros = weight mask
    lif: np.ndarray            # (3, OUT) f32


@dataclasses.dataclass(frozen=True)
class FusedPool:
    pool: int


@dataclasses.dataclass(frozen=True)
class FusedReadout:
    mode: str                  # "current_sum" | "spikes"


@dataclasses.dataclass(frozen=True)
class FusedStack:
    """The whole network, flattened into kernel-ready operands."""

    layers: Tuple[Any, ...]
    timesteps: int
    in_ic: int
    in_width: int
    n_classes: int

    @property
    def conv_names(self) -> Tuple[str, ...]:
        return tuple(l.name for l in self.layers
                     if isinstance(l, FusedConv))


def fused_conv_info(name: str, coo, lif, sched) -> FusedConv:
    """Build a conv layer's fused operands from its COO kernel + schedule."""
    from repro.core.sparse_format import coo_to_dense

    w = np.asarray(coo_to_dense(coo), dtype=np.float32)   # (KW, IC, OC)
    w_cm = np.transpose(w, (2, 0, 1)).reshape(coo.oc, coo.kw * coo.ic)
    ic_idx = np.asarray(coo.row_idx) % coo.ic
    rows = np.asarray(coo.col_idx) * coo.ic + ic_idx      # ci-major row ids
    counts = np.bincount(rows, minlength=coo.kw * coo.ic) if coo.nnz else \
        np.zeros(coo.kw * coo.ic, dtype=np.int64)
    return FusedConv(
        name=name, kw=coo.kw, ic=coo.ic, oc=coo.oc,
        w_cm=np.ascontiguousarray(w_cm),
        counts=counts.astype(np.float32)[:, None],
        lif=np.ascontiguousarray(_lif_rows(lif, coo.oc).T),
        static_counts={
            "reps_per_timestep": sched.reps,
            "compute_iters": sched.n_compute,
            "extra_iters": sched.n_extra,
            "empty_iters": sched.n_empty,
        })


def fused_fc_info(name: str, w: np.ndarray, lif) -> FusedFC:
    w = np.ascontiguousarray(np.asarray(w, dtype=np.float32))
    return FusedFC(name=name, w=w, lif=_lif_rows(lif, w.shape[1]))


def fused_stack_of(plan) -> Optional[FusedStack]:
    """Assemble a FusedStack from an ExecutionPlan, or None.

    Returns None unless *every* weighted layer is assigned the
    ``pallas_fused`` backend and carries fused operands — a partial
    assignment falls back to the generic streaming executor.
    """
    layers = []
    for lp in plan.layers:
        kind = lp.spec.kind
        if kind in (_KIND_CONV, _KIND_FC):
            if lp.backend != "pallas_fused" or lp.cell.fused is None:
                return None
            layers.append(lp.cell.fused)
        elif kind == _KIND_POOL:
            layers.append(FusedPool(lp.spec.pool))
        elif kind == _KIND_READOUT:
            layers.append(FusedReadout(lp.spec.mode))
        else:
            return None
    cfg = plan.cfg
    return FusedStack(
        layers=tuple(layers),
        timesteps=cfg.timesteps,
        in_ic=cfg.conv_specs[0][1],
        in_width=cfg.input_width,
        n_classes=cfg.fc_specs[-1][1],
    )


# ---------------------------------------------------------------------------
# The kernel.
# ---------------------------------------------------------------------------

_HIGHEST = lax.Precision.HIGHEST


def _select(rows: int, cols: int, fn) -> jax.Array:
    """(rows, cols) 0/1 bf16 matrix with ones where ``fn(i, j)`` holds."""
    i = lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    j = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return fn(i, j).astype(jnp.bfloat16)


def _route(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` where one side is a 0/1 selection matrix and the other
    binary spikes: every output is one spike or 0, exact in bf16.

    The precision is explicit: under ``jax.default_matmul_precision(
    "highest")`` a bf16 dot would otherwise ask Mosaic for an fp32
    contraction, which it refuses for bf16 operands."""
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   precision=lax.Precision.DEFAULT,
                   preferred_element_type=jnp.float32)


def _shift_buffer_cm(x: jax.Array, kw: int) -> jax.Array:
    """(IC, W) spike frame -> X'(KW*IC, W), rows ci-major (r = ci*IC+ic).

    Tap ``ci`` reads ``x[:, j + ci - left]`` ("same" padding, zeros out of
    range) through a (W, W) shift matrix; a (KW*IC, IC) placement matrix
    puts its rows at ``ci*IC ...``.  All matmuls, no pad or concatenate.
    """
    ic, w = x.shape
    left = (kw - 1) // 2
    sb = None
    for ci in range(kw):
        tap = _route(x, _select(w, w, lambda i, j, o=ci - left: i == j + o))
        part = _route(_select(kw * ic, ic,
                              lambda r, c, o=ci * ic: r == c + o), tap)
        sb = part if sb is None else sb + part
    return sb


def _max_pool(x: jax.Array, pool: int) -> jax.Array:
    """(C, W) spikes -> (C, W // pool) max over non-overlapping windows."""
    c, w = x.shape
    wo = w // pool
    out = None
    for k in range(pool):
        pick = _route(x, _select(w, wo, lambda i, j, k=k: i == pool * j + k))
        out = pick if out is None else jnp.maximum(out, pick)
    return out


def _fc_current(x: jax.Array, w_ref) -> jax.Array:
    """(C, W') spikes @ (C*W', OUT) weights -> (1, OUT), flattened c-major.

    ``Σ_c x[c] @ w[c·W':(c+1)·W']``: a flatten without a reshape.
    """
    c, wp = x.shape
    cur = None
    for i in range(c):
        part = jnp.dot(x[i:i + 1, :], w_ref[i * wp:(i + 1) * wp, :],
                       precision=_HIGHEST,
                       preferred_element_type=jnp.float32)
        cur = part if cur is None else cur + part
    return cur


def _total(a: jax.Array) -> jax.Array:
    """Sum of a 2-D array as a (1, 1) array."""
    return jnp.sum(jnp.sum(a, axis=0, keepdims=True), axis=1, keepdims=True)


def _lif_fire(v_acc: jax.Array, theta, v_th) -> Tuple[jax.Array, jax.Array]:
    """Threshold + soft reset (identical to core.lif.lif_step forward)."""
    s = (v_acc > v_th).astype(v_acc.dtype)
    return v_acc - theta * s, s


def stream_fused_forward(
    stack: FusedStack,
    frames: jax.Array,
    *,
    encode: bool = False,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Run the whole network in one multi-layer kernel launch.

    frames: (B, T, IC0, W) binary spike frames — or, with ``encode=True``,
    (B, IC0, W) normalized analog values in [0, 1] that the fused Σ-Δ
    modulator turns into spikes in-kernel (one frame read per *sample*
    instead of per timestep).

    ``interpret=None`` follows :func:`repro.kernels.platform.interpret_mode`;
    ``False`` compiles for a TPU even from a CPU process (ahead-of-time
    compiles against a described chip).

    Returns ``(logits (B, n_classes), conv_accs (B, n_convs))`` where
    ``conv_accs`` are the gated-accumulation counters of paper Tables
    I/III, per sample and conv layer (see :func:`fused_counters`).
    """
    interpret = resolve_interpret(interpret)
    t_steps = stack.timesteps
    if encode:
        b, ic0, w0 = frames.shape
    else:
        b, t_f, ic0, w0 = frames.shape
        if t_f != t_steps:
            raise ValueError(f"frames have T={t_f}, stack expects {t_steps}")
    if (ic0, w0) != (stack.in_ic, stack.in_width):
        raise ValueError(f"frames are ({ic0}, {w0}), stack expects "
                         f"({stack.in_ic}, {stack.in_width})")

    convs = [l for l in stack.layers if isinstance(l, FusedConv)]
    n_convs = max(1, len(convs))

    # -- operands: frames + per-layer constants (all resident via constant
    #    index maps), walking the static width through the graph ------------
    whole = lambda a: pl.BlockSpec(a.shape, lambda bb, tt:
                                   (0,) * a.ndim)  # noqa: E731
    inputs: list = [frames]
    if encode:
        in_specs = [pl.BlockSpec((1, ic0, w0), lambda bb, tt: (bb, 0, 0))]
    else:
        in_specs = [pl.BlockSpec((1, 1, ic0, w0),
                                 lambda bb, tt: (bb, tt, 0, 0))]
    scratch_shapes: list = []
    scratch_dims: list = []           # parallel shapes, for zero-init
    if encode:
        scratch_shapes += [pltpu.VMEM((ic0, w0), jnp.float32)] * 2
        scratch_dims += [(ic0, w0)] * 2
    width = w0
    for layer in stack.layers:
        if isinstance(layer, FusedConv):
            for a in (layer.w_cm, layer.counts, layer.lif):
                arr = jnp.asarray(a)
                inputs.append(arr)
                in_specs.append(whole(arr))
            scratch_shapes.append(pltpu.VMEM((layer.oc, width), jnp.float32))
            scratch_dims.append((layer.oc, width))
        elif isinstance(layer, FusedPool):
            width = (width // layer.pool)
        elif isinstance(layer, FusedFC):
            for a in (layer.w, layer.lif):
                arr = jnp.asarray(a)
                inputs.append(arr)
                in_specs.append(whole(arr))
            dout = layer.w.shape[1]
            scratch_shapes.append(pltpu.VMEM((1, dout), jnp.float32))
            scratch_dims.append((1, dout))

    # a singleton middle axis makes each (1, 1, n) block's last two dims
    # equal the array's, as Mosaic requires for any batch size
    out_shape = [
        jax.ShapeDtypeStruct((b, 1, stack.n_classes), jnp.float32),
        jax.ShapeDtypeStruct((b, 1, n_convs), jnp.float32),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, stack.n_classes), lambda bb, tt: (bb, 0, 0)),
        pl.BlockSpec((1, 1, n_convs), lambda bb, tt: (bb, 0, 0)),
    ]

    def kernel(*refs):
        cursor = 0

        def take(n=1):
            nonlocal cursor
            out = refs[cursor:cursor + n]
            cursor += n
            return out if n > 1 else out[0]

        x_ref = take()
        layer_refs = []
        for layer in stack.layers:
            if isinstance(layer, FusedConv):
                layer_refs.append(take(3))
            elif isinstance(layer, FusedFC):
                layer_refs.append(take(2))
            else:
                layer_refs.append(None)
        logits_ref, accs_ref = take(), take()
        scratch = refs[cursor:]

        t = pl.program_id(1)

        @pl.when(t == 0)
        def _fresh_sample():
            logits_ref[...] = jnp.zeros(logits_ref.shape, jnp.float32)
            accs_ref[...] = jnp.zeros(accs_ref.shape, jnp.float32)
            for ref, dims in zip(scratch, scratch_dims):
                ref[...] = jnp.zeros(dims, jnp.float32)

        sc = 0
        if encode:
            # first-order Σ-Δ: integ += x - y_prev; y = (integ >= 0.5)
            integ_ref, yprev_ref = scratch[sc], scratch[sc + 1]
            sc += 2
            integ = integ_ref[...] + x_ref[0] - yprev_ref[...]
            x = (integ >= 0.5).astype(jnp.float32)
            integ_ref[...] = integ
            yprev_ref[...] = x
        else:
            x = x_ref[0, 0]

        lane = lax.broadcasted_iota(jnp.int32, (1, n_convs), 1)
        accs = jnp.zeros((1, n_convs), jnp.float32)
        i_conv = 0
        last_cur = None
        for layer, lrefs in zip(stack.layers, layer_refs):
            if isinstance(layer, FusedConv):
                w_ref, c_ref, lif_ref = lrefs
                v_ref = scratch[sc]
                sc += 1
                sb = _shift_buffer_cm(x, layer.kw)          # (KW*IC, W)
                cur = jnp.dot(w_ref[...], sb, precision=_HIGHEST,
                              preferred_element_type=jnp.float32)
                occupancy = jnp.sum(sb, axis=1, keepdims=True)
                accs = accs + jnp.where(
                    lane == i_conv, _total(c_ref[...] * occupancy), 0.0)
                i_conv += 1
                lif = lif_ref[...]                          # (OC, 3)
                v_acc = lif[:, 0:1] * v_ref[...] + cur
                v_next, x = _lif_fire(v_acc, lif[:, 1:2], lif[:, 2:3])
                v_ref[...] = v_next
            elif isinstance(layer, FusedPool):
                x = _max_pool(x, layer.pool)
            elif isinstance(layer, FusedFC):
                w_ref, lif_ref = lrefs
                v_ref = scratch[sc]
                sc += 1
                cur = _fc_current(x, w_ref)                 # (1, OUT)
                lif = lif_ref[...]                          # (3, OUT)
                v_acc = lif[0:1, :] * v_ref[...] + cur
                v_next, x = _lif_fire(v_acc, lif[1:2, :], lif[2:3, :])
                v_ref[...] = v_next
                last_cur = cur
            else:  # FusedReadout
                contrib = last_cur if layer.mode == "current_sum" else x
                logits_ref[0] = logits_ref[0] + contrib
        if i_conv:
            accs_ref[0] = accs_ref[0] + accs

    logits, accs = pl.pallas_call(
        kernel,
        grid=(b, t_steps),            # T minor: state persists across T
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        name="stream_fused",
    )(*inputs)
    return logits[:, 0], accs[:, 0]


def fused_counters(stack: FusedStack, accs_row: jax.Array) -> Dict[str, Dict]:
    """Per-conv-layer Tables I/III counters for one sample's ``accs`` row,
    matching the ``stream`` backend's counter dict exactly."""
    out: Dict[str, Dict] = {}
    i = 0
    for layer in stack.layers:
        if isinstance(layer, FusedConv):
            out[layer.name] = {
                **layer.static_counts,
                "accumulations": accs_row[i],
                "timesteps": stack.timesteps,
            }
            i += 1
    return out
