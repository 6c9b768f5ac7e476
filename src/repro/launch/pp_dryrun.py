import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=256")

"""Pipeline-parallel production dry-run: the paper's inter-layer streaming
at pod scale.

The SAOCDS accelerator streams activations layer-to-layer through
per-layer hardware stages (paper §III).  This driver maps the same
structure onto the production pod: llama3-8b's 32 layers become 8
pipeline stages of 4 layers on a (stage=8, data=2, model=16) = 256-chip
mesh — ``spmd_pipeline`` (shard_map + ppermute, fixed tick schedule with
explicit bubble slots) over stages, pjit TP/DP inside each stage.

Usage: PYTHONPATH=src python -m repro.launch.pp_dryrun [--arch llama3-8b]
Writes experiments/dryrun/pp/<arch>__prefill_pp.json.
"""
import argparse
import functools
import json
import pathlib
import sys
import time

__all__ = ["main"]


def run(arch: str = "llama3-8b", n_micro: int = 16, seq: int = 4096,
        batch: int = 32) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.configs.registry import get_config
    from repro.distributed.ctx import activation_constraints
    from repro.distributed.pipeline import spmd_pipeline
    from repro.distributed.sharding import partition_params
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import HW
    from repro.models.config import ArchConfig
    from repro.models.lm import _block_apply, _stack_layout, init_lm
    from repro.models.layers import mask_vocab_pad, rms_norm

    cfg = get_config(arch)
    assert cfg.family == "dense", "PP demo targets the dense decoder archs"
    n_stages = 8
    assert cfg.n_layers % n_stages == 0
    per_stage = cfg.n_layers // n_stages
    mesh = jax.make_mesh((n_stages, 2, 16), ("stage", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    chips = len(mesh.devices.flat)
    mb = batch // n_micro

    # ---- parameter shapes: layer stack regrouped (stages, per_stage, ...)
    shapes = jax.eval_shape(
        functools.partial(init_lm, cfg=cfg, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    (kind, count), = _stack_layout(cfg)
    stack_sd = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((n_stages, per_stage) + x.shape[1:],
                                       x.dtype),
        shapes["stacks"][0])
    # TP specs for the inner (per_stage, ...) tree, then prepend the stage axis
    inner_specs = partition_params(
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), stack_sd),
        mesh, head_dim=cfg.hd)
    stack_specs = jax.tree_util.tree_map(
        lambda s: P("stage", *tuple(s)), inner_specs,
        is_leaf=lambda x: isinstance(x, P))
    emb_sd = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), shapes["emb"])
    emb_specs = partition_params(emb_sd, mesh, head_dim=cfg.hd)
    norm_sd = jax.ShapeDtypeStruct(shapes["final_norm"].shape,
                                   shapes["final_norm"].dtype)

    tokens_sd = jax.ShapeDtypeStruct((n_micro, mb, seq), jnp.int32)

    def stage_fn(p_stage, x):
        def body(h, layer_p):
            out, _ = _block_apply(cfg, kind, layer_p, h, None, 0)
            return out, None

        x, _ = jax.lax.scan(body, x, p_stage)
        return x

    def pp_prefill(stacks, emb, final_norm, tokens):
        x = emb["tok"][tokens]                       # (n_micro, mb, S, d)
        x = x.reshape(n_micro * mb, seq, -1)
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None, "model", None)))
        x = x.reshape(n_micro, mb, seq, -1)
        y = spmd_pipeline(stage_fn, stacks, x, mesh, stage_axis="stage",
                          collect="stack")
        y = rms_norm(y[:, :, -1:], final_norm, cfg.norm_eps)
        logits = mask_vocab_pad(y @ emb["unemb"], cfg)
        return logits                                 # (n_micro, mb, 1, V)

    jitted = jax.jit(
        pp_prefill,
        in_shardings=(
            jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                   stack_specs,
                                   is_leaf=lambda x: isinstance(x, P)),
            jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                   emb_specs,
                                   is_leaf=lambda x: isinstance(x, P)),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P(None, None, None, "model")),
    )

    rec = {"arch": arch, "mesh": {"stage": n_stages, "data": 2, "model": 16},
           "n_micro": n_micro, "microbatch_rows": mb, "seq": seq,
           "ticks": n_micro + n_stages - 1,
           "bubble_fraction": (n_stages - 1) / (n_micro + n_stages - 1)}
    t0 = time.perf_counter()
    with mesh, activation_constraints(
            NamedSharding(mesh, P(None, None, "model", None))):
        lowered = jitted.lower(stack_sd, emb_sd, norm_sd, tokens_sd)
        compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t0, 1)
    ma = compiled.memory_analysis()
    print(ma)
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    rec["memory"] = {"peak_live_bytes": live,
                     "fits_16g_hbm": bool(live < 16 * 1024**3)}
    a = analyze_hlo(compiled.as_text())
    rec["hlo"] = a.summary()
    peak, hbm, ici = HW["peak_flops_bf16"], HW["hbm_bw"], HW["ici_bw"]
    rec["terms_s"] = {
        "compute": a.dot_flops / peak,
        "memory": a.bytes_accessed / hbm,
        "collective": a.collective_bytes / ici,
    }
    rec["ok"] = True
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--n-micro", type=int, default=16)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)
    rec = run(args.arch, n_micro=args.n_micro)
    out = pathlib.Path(args.out) / "pp"
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"{args.arch}__prefill_pp.json"
    path.write_text(json.dumps(rec, indent=1, default=str))
    print(json.dumps({k: rec[k] for k in
                      ("arch", "ok", "ticks", "bubble_fraction", "terms_s")},
                     default=str))
    return 0 if rec.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
