"""Distribution layer tests: sharding rules, pipeline, compression, dryrun.

Multi-device tests run in subprocesses (jax locks the host device count at
first init, and the main pytest process must keep its 1-CPU view).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.registry import get_config
from repro.distributed.compression import (
    compression_ratio,
    dequantize_int8,
    ef_compress,
    quantize_int8,
)
from repro.launch.steps import WHISPER_S_ENC  # noqa: F401 (import check)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, devices: int = 8, timeout: int = 600):
    env = {**os.environ,
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": os.path.join(REPO, "src")}
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=REPO)


# ---------------------------------------------------------------------------
# sharding rules (AbstractMesh: no devices needed)
# ---------------------------------------------------------------------------

def _mesh():
    return AbstractMesh((16, 16), ("data", "model"))


def test_partition_rules_tp_and_fsdp():
    from repro.distributed.sharding import partition_params
    from repro.models.lm import init_lm

    cfg = get_config("llama3-8b")
    shapes = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    specs = partition_params(shapes, _mesh(), n_experts=cfg.n_experts)
    stack = specs["stacks"][0]
    # TP: attention projections column-sharded, out-proj row-sharded
    assert stack["attn"]["wq"][-1] == "model"
    assert stack["attn"]["wo"][-2] == "model"
    assert stack["mlp"]["wg"][-1] == "model"
    assert stack["mlp"]["wd"][-2] == "model"
    # FSDP: the other big dim carries the data axis; scan dim never sharded
    assert "data" in tuple(stack["attn"]["wq"])
    assert tuple(stack["attn"]["wq"])[0] is None
    # embeddings vocab-parallel (padded vocab)
    assert specs["emb"]["tok"][0] == "model"
    # stacked norms: scan dim unsharded (FSDP may take the feature dim)
    assert stack["norm1"][0] is None


def test_partition_rules_moe_ep_vs_tp():
    from repro.distributed.sharding import partition_params
    from repro.models.lm import init_lm

    # llama4-scout: 16 experts % 16 == 0 -> expert parallelism
    cfg = get_config("llama4-scout-17b-a16e")
    shapes = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    specs = partition_params(shapes, _mesh(), n_experts=cfg.n_experts)
    moe = specs["stacks"][0]["moe"]
    assert moe["wg"][1] == "model", "16 experts should be EP-sharded"

    # qwen2-moe: 60 experts % 16 != 0 -> TP inside experts
    cfg = get_config("qwen2-moe-a2.7b")
    shapes = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    specs = partition_params(shapes, _mesh(), n_experts=cfg.n_experts)
    moe = specs["stacks"][0]["moe"]
    assert moe["wg"][1] is None and "model" in tuple(moe["wg"])


def test_divisibility_fallback_never_invalid():
    from repro.distributed.sharding import partition_params
    from repro.models.lm import init_lm

    mesh = _mesh()
    for arch in ("mamba2-780m", "recurrentgemma-9b", "internvl2-1b"):
        cfg = get_config(arch)
        shapes = jax.eval_shape(lambda k: init_lm(k, cfg),
                                jax.random.PRNGKey(0))
        specs = partition_params(shapes, mesh, n_experts=cfg.n_experts)

        def check(path, spec, leaf):
            for ax, dim in zip(tuple(spec), leaf.shape):
                if ax is not None:
                    n = mesh.shape[ax] if isinstance(ax, str) else int(
                        np.prod([mesh.shape[a] for a in ax]))
                    assert dim % n == 0, (arch, path, spec, leaf.shape)

        jax.tree_util.tree_map_with_path(
            check, specs, shapes, is_leaf=lambda x: isinstance(x, P))


def test_decode_state_specs_shard_ctx():
    from repro.distributed.sharding import decode_state_specs
    from repro.models.lm import init_decode_state

    cfg = get_config("llama3-8b")
    state = jax.eval_shape(lambda: init_decode_state(cfg, 128, 32768))
    specs = decode_state_specs(state, _mesh(), 128)
    kv = specs[0]
    assert kv["k"][2] == "model" and kv["k"][1] in ("data", ("data",))
    assert kv["len"] == P()


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_int8_quantization_error_bound():
    rng = np.random.default_rng(0)
    tree = {"a": jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(7,)).astype(np.float32) * 10)}
    q, s = quantize_int8(tree)
    deq = dequantize_int8(q, s)
    for k in tree:
        step = float(jnp.max(jnp.abs(tree[k]))) / 127.0
        assert float(jnp.max(jnp.abs(deq[k] - tree[k]))) <= step * 0.5 + 1e-7
    assert compression_ratio(tree) > 3.9


def test_error_feedback_accumulates_residual():
    """EF invariant: sum of dequantized transmissions + residual == sum of
    raw gradients (no information lost over steps)."""
    rng = np.random.default_rng(1)
    ef = {"w": jnp.zeros((32,), jnp.float32)}
    total_sent = jnp.zeros((32,))
    total_grads = jnp.zeros((32,))
    for i in range(8):
        g = {"w": jnp.asarray(rng.normal(size=(32,)).astype(np.float32) * 1e-3)}
        q, s, ef = ef_compress(g, ef)
        total_sent = total_sent + dequantize_int8(q, s)["w"]
        total_grads = total_grads + g["w"]
    np.testing.assert_allclose(
        np.asarray(total_sent + ef["w"]), np.asarray(total_grads),
        rtol=1e-5, atol=1e-6)


def test_compressed_psum_two_workers():
    res = _run_py("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from repro.distributed.compression import compressed_psum
        mesh = jax.make_mesh((2,), ("dp",), axis_types=(AxisType.Auto,))
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("dp"), P("dp")),
                           out_specs=(P("dp"), P("dp")), check_vma=False)
        def step(g, ef):
            g0 = {"w": g[0]}
            mean, new_ef = compressed_psum(g0, {"w": ef[0]}, "dp")
            return mean["w"][None], new_ef["w"][None]
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(2, 64)).astype(np.float32))
        ef = jnp.zeros((2, 64), jnp.float32)
        mean, ef2 = step(g, ef)
        ref = g.mean(0)
        # both workers agree and approximate the true mean
        np.testing.assert_allclose(np.asarray(mean[0]), np.asarray(mean[1]), atol=0)
        err = float(jnp.abs(mean[0] - ref).max())
        scale = float(jnp.abs(g).max()) / 127.0
        assert err <= scale + 1e-6, (err, scale)
        print("OK")
    """, devices=2)
    assert res.returncode == 0 and "OK" in res.stdout, res.stderr[-2000:]


# ---------------------------------------------------------------------------
# pipeline runner (the paper's inter-layer streaming)
# ---------------------------------------------------------------------------

def test_spmd_pipeline_equals_sequential():
    res = _run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.distributed.pipeline import spmd_pipeline
        mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.normal(size=(4, 16, 16)).astype(np.float32) * 0.3)
        def stage_fn(w, x):
            return jnp.tanh(x @ w)
        mbs = jnp.asarray(rng.normal(size=(6, 8, 16)).astype(np.float32))
        out = spmd_pipeline(stage_fn, ws, mbs, mesh)
        ref = mbs
        for i in range(4):
            ref = jnp.tanh(ref @ ws[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """, devices=4)
    assert res.returncode == 0 and "OK" in res.stdout, res.stderr[-2000:]


# ---------------------------------------------------------------------------
# elastic checkpoint reshard: save on mesh A, restore on mesh B
# ---------------------------------------------------------------------------

def test_elastic_checkpoint_reshard(tmp_path):
    res = _run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax.sharding import AxisType
        from repro.train.checkpoint import CheckpointManager
        meshA = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        meshB = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        xa = jax.device_put(x, NamedSharding(meshA, P("data", "model")))
        ck = CheckpointManager(r"{tmp_path}", keep=2)
        ck.save(1, {{"w": xa}}, extra={{"step": 1}})
        ck.wait()
        # restore onto a DIFFERENT mesh layout
        xb_target = jax.device_put(jnp.zeros((8, 8)), NamedSharding(meshB, P("model", "data")))
        tree, manifest = ck.restore({{"w": xb_target}})
        got = np.asarray(tree["w"])
        np.testing.assert_array_equal(got, np.asarray(x))
        print("OK", manifest["extra"]["step"])
    """, devices=8)
    assert res.returncode == 0 and "OK" in res.stdout, res.stderr[-2000:]


# ---------------------------------------------------------------------------
# dryrun integration (one fast cell on the real 512-device path)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_dryrun_one_cell(tmp_path):
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-780m",
         "--shape", "long_500k", "--mesh", "multi", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-2000:]
    rec = json.loads(
        (tmp_path / "multi" / "mamba2-780m__long_500k.json").read_text())
    assert rec["ok"] and rec["chips"] == 512
    assert rec["roofline"]["terms_s"]["compute"] > 0
    assert rec["memory"]["fits_16g_hbm"]
