"""Parameter / activation / state sharding rules (DP x TP x SP x EP).

One rule table covers every assigned architecture.  Rules are keyed on the
*path* of each leaf in the parameter pytree (the layer code gives leaves
stable names: ``wq``, ``wd``, ``moe/wg`` ...) and are **divisibility
checked**: a dim is only sharded when the mesh axis divides it, otherwise
that dim falls back to replication.  Stacked-layer leading axes (the
``(L, ...)`` from the scanned stacks) are auto-detected by rule arity and
left unsharded.

Scheme (Megatron-style TP over the ``model`` axis, DP over ``pod x data``):

=================  =======================================  ==============
leaf               shape                                    spec (last dims)
=================  =======================================  ==============
emb.tok            (V, d)                                   (None, model)
emb.unemb          (d, V)                                   (None, model) | (model, None)
attn wq/wk/wv      (d, H*hd)                                (None, model)  [col]
attn wo            (H*hd, d)                                (model, None)  [row]
mlp wg/wu          (d, ff)                                  (None, model)
mlp wd             (ff, d)                                  (model, None)
moe wg/wu          (E, d, f)                                (model, None, None) EP | (None, None, model) TP
moe wd             (E, f, d)                                (model, None, None) EP | (None, model, None) TP
rglru w_x/w_gate   (d, w)                                   (None, model)
rglru w_r/w_i/out  (w, *)                                   (model, None)
ssm (mamba2)       fused in-proj has unaligned segment      replicated (see
                   boundaries under tiling                  DESIGN.md perf log)
norms/bias/scalar  (d,)                                     replicated
=================  =======================================  ==============

Expert-parallel vs expert-TP is decided per config: ``E % model == 0`` ->
EP (llama4-scout, 16e); otherwise TP inside experts (qwen2-moe, 60e).
"""
from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "dp_axes",
    "partition_params",
    "named_tree",
    "train_batch_spec",
    "act_pspec",
    "logits_pspec",
    "decode_state_specs",
    "spec_report",
    "serve_mesh",
    "serve_batch_pspec",
    "shard_serve_fn",
]


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Data-parallel mesh axes: pod composes with data when present."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _check(spec_dims: Sequence, shape: Sequence[int], mesh: Mesh):
    """Replicate any dim whose size the assigned axis does not divide."""
    out = []
    for ax, dim in zip(spec_dims, shape):
        out.append(ax if (ax is not None and dim % _axis_size(mesh, ax) == 0) else None)
    return tuple(out)


# rule: regex over the '/'-joined leaf path -> spec builder for LAST dims
# (leading stack dims auto-padded with None).  First match wins.
_COL = ("col",)   # (None, model): shard output features
_ROW = ("row",)   # (model, None): shard input features (partial-sum out)


def _rules(model: str):
    return [
        # --- embeddings (vocab-parallel; vocab padded to 128-multiples) ---
        (r"(^|/)tok$",          (model, None)),
        (r"(^|/)unemb$",        "unemb"),
        (r"(^|/)pos$",          (None, model)),
        # --- attention (sharded only when shards hold WHOLE heads; a
        # fractured head layout makes XLA partial-compute attention
        # scores and all-reduce them: 5.4 GB x 1024 on whisper prefill.
        # Misaligned archs run sequence-parallel attention instead.) ---
        (r"(^|/)(wq|wk|wv)$",   "attn_col"),
        (r"(^|/)(bq|bk|bv)$",   "attn_bias"),
        (r"(^|/)wo$",           "attn_row"),
        (r"(^|/)(q_norm|k_norm)$", None),
        # --- MoE (must precede generic mlp rules) ---
        (r"moe/(wg|wu)$",       "moe_up"),
        (r"moe/wd$",            "moe_down"),
        (r"(^|/)router$",       None),
        # --- dense MLP (swiglu + whisper mlp) ---
        (r"(^|/)(wg|wu|wi|w1)$", (None, model)),
        (r"(^|/)(wd|w2)$",      (model, None)),
        (r"(^|/)b1$",           (model,)),
        (r"(^|/)dec_pos$",      (None, model)),
        # --- RG-LRU ---
        (r"rglru/(w_x|w_gate)$", (None, model)),
        (r"rglru/conv$",        (None, model)),
        (r"rglru/(w_r|w_i)$",   (model, None, None)),  # block-diag (nb, wb, wb)
        (r"rglru/w_out$",       (model, None)),
        (r"rglru/lam$",         (model,)),
        # --- Mamba-2: fused in-proj segments are not tile-aligned; keep
        # replicated at baseline (perf log tracks the sharded variant) ---
        (r"ssm/",               None),
        # whisper conv-frontend stub / layernorm scale+bias / defaults
        (r".*",                 None),
    ]


def _spec_for(path: str, shape: Tuple[int, ...], mesh: Mesh, model: str,
              n_experts: int, head_dim: int = 0) -> P:
    def _heads_align(dim: int) -> bool:
        # With kv-chunked online-softmax attention (no sharded-dim
        # slicing) a fractured head layout is handled by one reshard, so
        # plain divisibility suffices; whole-head alignment is preferred
        # but not required.  (Replicating misaligned projections instead
        # costs 16x their param/grad/moment memory: +3.5 GB/device on
        # llama4-scout train.)
        n = _axis_size(mesh, model)
        return dim % n == 0

    for pat, rule in _rules(model):
        if re.search(pat, path):
            if rule is None:
                return P()
            if rule == "attn_col":     # (d, H*hd)
                dims = (None, model) if _heads_align(shape[-1]) else (None, None)
            elif rule == "attn_bias":  # (H*hd,)
                dims = (model,) if _heads_align(shape[-1]) else (None,)
            elif rule == "attn_row":   # (H*hd, d)
                dims = (model, None) if _heads_align(shape[-2]) else (None, None)
            elif rule == "unemb":
                # (d, V): prefer vocab-sharded logits; fall back to row
                if shape[-1] % _axis_size(mesh, model) == 0:
                    dims = (None, model)
                else:
                    dims = (model, None)
            elif rule == "moe_up":       # (E, d, f)
                if n_experts and n_experts % _axis_size(mesh, model) == 0:
                    dims = (model, None, None)
                else:
                    dims = (None, None, model)
            elif rule == "moe_down":     # (E, f, d)
                if n_experts and n_experts % _axis_size(mesh, model) == 0:
                    dims = (model, None, None)
                else:
                    dims = (None, model, None)
            else:
                dims = rule
            dims = dims[-len(shape):] if len(dims) > len(shape) else dims
            pad = (None,) * (len(shape) - len(dims))
            return P(*_check(pad + tuple(dims), shape, mesh))
    return P()


def partition_params(shape_tree: Any, mesh: Mesh, *, model_axis: str = "model",
                     n_experts: int = 0, head_dim: int = 0,
                     fsdp_axis: Optional[str] = "data") -> Any:
    """PartitionSpec tree for a parameter (or grad/opt-moment) shape tree.

    ``shape_tree`` leaves need only ``.shape`` (ShapeDtypeStruct or array).

    ``fsdp_axis``: ZeRO-3 / fully-sharded data parallelism — after the TP
    rules assign the ``model`` axis, the largest still-unsharded non-stack
    dim of every >=2-D weight is sharded over the data axis.  Weights are
    all-gathered per layer inside the scan loop (XLA overlaps the gather
    with the previous layer's compute), and gradients reduce-scatter back;
    optimizer moments inherit the same spec, so parameter + moment memory
    drops by the data-axis size.  This is what lets the 100B llama4-scout
    train cell fit 16 GB HBM (75 GB/device with TP-only).  The ``pod``
    axis stays pure DP: params replicate across pods, matching the
    fast-ICI-intra / slow-DCN-inter hierarchy.  Disabled (None) for
    pipeline or inference setups that want weights resident.
    """
    fsdp_n = mesh.shape.get(fsdp_axis, 1) if fsdp_axis else 1

    def visit(path, leaf):
        keys = []
        for p in path:
            if hasattr(p, "key"):
                keys.append(str(p.key))
            elif hasattr(p, "idx"):
                keys.append(str(p.idx))
            elif hasattr(p, "name"):
                keys.append(str(p.name))
        path = "/".join(keys)
        spec = _spec_for(path, tuple(leaf.shape), mesh, model_axis,
                         n_experts, head_dim)
        # FSDP must not fracture attention heads either: for q/k/v (head
        # dim last) and o (head dim second-to-last) only head-aligned
        # sharding is allowed on the head dim (whisper's 20x64 heads were
        # re-fractured over `data` by FSDP after the TP rule declined)
        blocked = set()
        leaf_name = keys[-1] if keys else ""
        if head_dim and leaf_name in ("wq", "wk", "wv", "wo", "bq", "bk", "bv"):
            h_i = len(leaf.shape) - (2 if leaf_name == "wo" else 1)
            if (leaf.shape[h_i] % fsdp_n or
                    (leaf.shape[h_i] // fsdp_n) % head_dim):
                blocked.add(h_i)
        if fsdp_axis and fsdp_n > 1 and len(leaf.shape) >= 2:
            dims = list(spec) + [None] * (len(leaf.shape) - len(spec))
            # never shard dim 0 of rank>=3 leaves (the layer-scan axis);
            # choose the largest unsharded dim divisible by the fsdp axis
            lo = 1 if len(leaf.shape) >= 3 else 0
            cands = [
                (leaf.shape[i], i)
                for i in range(len(leaf.shape) - 1, lo - 1, -1)
                if dims[i] is None and leaf.shape[i] % fsdp_n == 0
                and leaf.shape[i] >= 2 * fsdp_n and i not in blocked
            ]
            if cands:
                _, i = max(cands)
                dims[i] = fsdp_axis
                spec = P(*dims)
        return spec

    return jax.tree_util.tree_map_with_path(visit, shape_tree)


def named_tree(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# activations / batches / decode state
# ---------------------------------------------------------------------------

def train_batch_spec(mesh: Mesh, batch: int, *, rank: int = 2) -> P:
    """(B, S, ...) input batch: B over DP axes when divisible."""
    dp = dp_axes(mesh)
    if batch % _axis_size(mesh, dp) != 0:
        dp = None
    return P(dp, *(None,) * (rank - 1))


def act_pspec(mesh: Mesh, batch: int, seq: int, *, seq_shard: bool = True) -> P:
    """Residual stream (B, S, d): B over DP, S over model (sequence
    parallelism — the activation-memory lever that lets 48L x 4k x 256
    training shapes fit HBM; see DESIGN.md §6)."""
    dp = dp_axes(mesh)
    if batch % _axis_size(mesh, dp) != 0:
        dp = None
    s_ax = "model" if (seq_shard and seq % _axis_size(mesh, "model") == 0) else None
    return P(dp, s_ax, None)


def logits_pspec(mesh: Mesh, batch: int, seq: int, vocab: int) -> P:
    """Logits (B, S, V): vocab-shard when divisible, else sequence-shard
    (keeps the fp32 softmax buffer partitioned either way)."""
    dp = dp_axes(mesh)
    if batch % _axis_size(mesh, dp) != 0:
        dp = None
    if vocab % _axis_size(mesh, "model") == 0:
        return P(dp, None, "model")
    s_ax = "model" if seq % _axis_size(mesh, "model") == 0 else None
    return P(dp, s_ax, None)


def decode_state_specs(state_tree: Any, mesh: Mesh, batch: int) -> Any:
    """Decode-state sharding: KV caches (L, B, CTX, nkv, hd) shard B over
    DP and CTX over model (ring-buffer writes stay local — verified no
    all-gather in the partitioned HLO).  SSM / LRU / conv states shard B
    over DP and the widest trailing dim over model when divisible."""
    dp = dp_axes(mesh)
    if batch % _axis_size(mesh, dp) != 0:
        dp = None
    model_n = _axis_size(mesh, "model")

    def visit(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", ""))) for p in path]
        name = keys[-1] if keys else ""
        shape = tuple(leaf.shape)
        if name == "len" or len(shape) == 0:
            return P()
        if name in ("k", "v", "ck", "cv") and len(shape) >= 4:
            # (..., B, CTX, nkv, hd)
            ctx_ax = "model" if shape[-3] % model_n == 0 else None
            lead = (None,) * (len(shape) - 4)
            return P(*lead, dp if shape[-4] % max(1, _axis_size(mesh, dp)) == 0 and dp else None,
                     ctx_ax, None, None)
        if name in ("k_scale", "v_scale") and len(shape) >= 3:
            # (..., B, CTX, nkv): shard CTX with the int8 cache it scales
            ctx_ax = "model" if shape[-2] % model_n == 0 else None
            lead = (None,) * (len(shape) - 3)
            return P(*lead, dp if shape[-3] % max(1, _axis_size(mesh, dp)) == 0 and dp else None,
                     ctx_ax, None)
        # generic state: (L, B, ...) — shard B over dp, last dim over model
        dims = [None] * len(shape)
        if len(shape) >= 2:
            dims[1] = dp if dp and shape[1] % _axis_size(mesh, dp) == 0 else None
        if shape[-1] % model_n == 0 and len(shape) >= 3:
            dims[-1] = "model"
        return P(*dims)

    return jax.tree_util.tree_map_with_path(visit, state_tree)


def serve_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D ``("data",)`` mesh over local devices for the serving tier.

    Serving is pure data parallelism: the SNN is tiny (fits any single
    device many times over) so the only axis worth sharding is the request
    batch.  A 1-device mesh is valid and keeps the shard_map code path
    identical from laptop to pod.
    """
    from jax.sharding import AxisType

    n = n_devices if n_devices is not None else jax.local_device_count()
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))


def serve_batch_pspec(mesh: Mesh) -> P:
    """Leading-axis batch spec for serve batches on a ``serve_mesh``."""
    return P("data" if "data" in mesh.axis_names else None)


def shard_serve_fn(fn, mesh: Mesh):
    """shard_map-wrap a batched ``(B, ...) -> (B, ...)`` fn over ``data``.

    The per-shard body is embarrassingly parallel (no collectives): each
    device runs the bound program on its slice of the request batch.  The
    micro-batcher guarantees every bucket size is a multiple of the data
    axis, so the split is always even.  Callers still jit the result.
    """
    spec = serve_batch_pspec(mesh)
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


def spec_report(spec_tree: Any, shape_tree: Any) -> str:
    """Human-readable param-spec table (used by dryrun --verbose)."""
    lines = []

    def visit(path, spec, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", ""))) for p in path]
        lines.append(f"  {'/'.join(keys):60s} {str(tuple(leaf.shape)):28s} {spec}")

    jax.tree_util.tree_map_with_path(
        lambda p, s, l: visit(p, s, l), spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    return "\n".join(lines)
