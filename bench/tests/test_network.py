"""One network description for chains and residual stages (bench/network.py).

* The existing configurations read as before: sha256 digests of their
  weights and masks, frame pools, reference outputs and cost numbers,
  recorded in ``data/chain_digests.json`` before the stage form existed,
  must hold.  So must the float64 reference's logits and margins of two
  stage networks, ``tiny-resnet`` and the published O'Shea ResNet,
  recorded in ``data/stage_digests.json`` before the reference was made
  to run its blocks on threads.  Regenerate only for a deliberate change
  of those numbers: ``python bench/tests/test_network.py``.
* A chain expands to the names, shapes and order it always had.
* A residual stage does what ``bench/network.py`` says: the shortcut
  alone, checked by hand; float64 NumPy and float32 ``jax.numpy`` agree
  on every frame judged; the weights' fitting pass and the reference run
  the same network; the cost model counts the published ResNet's work.
* ``bench/run.py`` gives the program a stage network's fields where its
  ``SNNConfig`` has them, and is refused naming them where it has not
  (held with a chain-only stand-in, whatever the program has).
* The RadioML 2018.01A class set has its 24 classes.
"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
DIGESTS = os.path.join(DATA, "chain_digests.json")
STAGE_DIGESTS = os.path.join(DATA, "stage_digests.json")
CHAIN_CONFIGS = ["snn-amc-f32-d50", "snn-amc-int8-d50"]
DIGEST_SEEDS = [11, 2 ** 31 + 5]
OSHEA_SEEDS = [12, 13]
OSHEA_FRAMES = 8
POOL = 64
SNR_GRID = np.arange(-20, 19, 2)

if __name__ == "__main__":
    sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import cost  # noqa: E402
import frames  # noqa: E402
import network  # noqa: E402
import reference  # noqa: E402
import weights as weights_mod  # noqa: E402

# O'Shea, Roy & Clancy 2018 (arXiv:1712.04578), Table III: the residual
# network at its published RadioML 2018.01A widths, in the stage form
OSHEA_RESNET = {
    "input_channels": 2, "input_width": 1024, "timesteps": 8,
    "n_classes": 24,
    "stages": [{"channels": 32, "proj_kw": 1, "units": 2, "kw": 3,
                "pool": 2}] * 6,
    "fc_specs": [[512, 128], [128, 128], [128, 24]],
    "readout": "current_sum", "lif_alpha": 0.9, "lif_theta": 1.0,
    "lif_v_th": 1.0,
}
# the LIF constants at which every stage of it stays active (PERF.md 4.1)
OSHEA_ACTIVE = dict(OSHEA_RESNET, lif_theta=0.7, lif_v_th=0.7)


def _cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _tiny_resnet():
    with open(os.path.join(DATA, "tiny-resnet.json")) as f:
        return json.load(f)


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def chain_digests(name: str, seed: int) -> dict:
    """Digests of everything the harness derives from one configuration."""
    cfg = _cfg(name)
    net = cfg["network"]
    iq, labels, snrs = frames.frame_pool(seed, POOL, SNR_GRID,
                                         frame_len=int(net["input_width"]))
    w = weights_mod.to_host(weights_mod.make_weights(seed, cfg, iq, labels))
    leaves = [layer[k] for g in ("conv", "fc") for layer in w[g]
              for k in sorted(layer)]
    logits, margin = reference.float_reference(iq, w, net)
    numbers = [sorted(cost.layer_work(net).items()),
               sorted(cost.layer_work(net, cost.nonzero_counts(w)).items()),
               cost.work_per_frame(net, cost.nonzero_counts(w)),
               cost.weight_bytes(net),
               cost.frame_bytes(net, int(cfg.get("step_counters", 0)))]
    out = {"pool": _digest(iq, labels, snrs), "weights": _digest(*leaves),
           "float_reference": _digest(logits, margin),
           "cost": hashlib.sha256(repr(numbers).encode()).hexdigest()}
    if cfg["check"]["reference"] == "integer":
        out["integer_reference"] = _digest(reference.integer_reference(
            iq, w, net, int(cfg["check"]["bits"])))
    return out


@pytest.mark.parametrize("seed", DIGEST_SEEDS)
@pytest.mark.parametrize("name", CHAIN_CONFIGS)
def test_existing_configurations_read_bit_identically(name, seed):
    with open(DIGESTS) as f:
        recorded = json.load(f)[f"{name}/{seed}"]
    assert chain_digests(name, seed) == recorded


def stage_digest(name: str, seed: int) -> str:
    """Digest of the float64 reference's logits and margins of a stage net.

    ``tiny-resnet`` on its 64-frame test pool; ``oshea`` (the published
    widths, v_th = theta = 0.7) on 8 RadioML 2018.01A frames that its
    readout is also fitted on.
    """
    if name == "tiny-resnet":
        _, net, iq, w = _tiny_resnet_setup(seed)
    else:
        net = OSHEA_ACTIVE
        iq, labels, _ = frames.frame_pool(seed, OSHEA_FRAMES,
                                          np.arange(-20, 31, 2),
                                          frame_len=int(net["input_width"]),
                                          class_set="radioml2018")
        w = weights_mod.to_host(weights_mod.make_weights(
            seed, {"network": net, "density": 0.5}, iq, labels))
    return _digest(*reference.float_reference(iq, w, net))


STAGE_CASES = [("tiny-resnet", s) for s in DIGEST_SEEDS] + \
    [("oshea", s) for s in OSHEA_SEEDS]


@pytest.mark.parametrize("name,seed", STAGE_CASES)
def test_stage_reference_reads_bit_identically(name, seed):
    with open(STAGE_DIGESTS) as f:
        recorded = json.load(f)[f"{name}/{seed}"]
    assert stage_digest(name, seed) == recorded


def test_chain_expands_to_the_layers_it_always_had():
    net = _cfg("snn-amc-f32-d50")["network"]
    layers, ops = network.layers(net)
    assert [l.name for l in layers] == (
        [f"conv{i + 1}" for i in range(len(net["conv_specs"]))]
        + [f"fc{i + 1}" for i in range(len(net["fc_specs"]))])
    assert [(l.kw, l.c_in, l.c_out) for l in layers[:3]] == \
        [tuple(s) for s in net["conv_specs"]]
    assert [(l.c_in, l.c_out) for l in layers[3:]] == \
        [tuple(s) for s in net["fc_specs"]]
    assert [l.width for l in layers] == [128, 64, 32, 1, 1]
    assert [op.kind for op in ops] == ["conv", "pool"] * 3 + \
        ["flatten", "fc", "fc"]
    assert not any(op.shortcut for op in ops)


def test_stage_expansion_orders_projection_then_units():
    layers, ops = network.layers(_tiny_resnet()["network"])
    assert [(l.name, l.kw, l.c_in, l.c_out, l.width) for l in layers] == [
        ("conv1", 1, 2, 4, 16), ("conv2", 3, 4, 4, 16), ("conv3", 3, 4, 4, 16),
        ("conv4", 1, 4, 8, 8), ("conv5", 3, 8, 8, 8), ("conv6", 3, 8, 8, 8),
        ("conv7", 3, 8, 8, 8), ("conv8", 3, 8, 8, 8),
        ("fc1", 1, 32, 16, 1), ("fc2", 1, 16, 5, 1)]
    assert [(op.kind, op.shortcut) for op in ops if op.kind != "fc"] == [
        ("conv", False), ("skip", False), ("conv", False), ("conv", True),
        ("pool", False), ("conv", False)] + [
        ("skip", False), ("conv", False), ("conv", True)] * 2 + [
        ("pool", False), ("flatten", False)]


def _layer(w, alpha, theta, v_th):
    n = w.shape[-1]
    shape = (n, 1) if w.ndim == 3 else (n,)
    logit = np.log(alpha / (1.0 - alpha))
    return {"w": w, "mask": np.ones_like(w),
            "alpha_logit": np.full(shape, logit, np.float32),
            "theta": np.full(shape, theta, np.float32),
            "v_th": np.full(shape, v_th, np.float32)}


def test_a_unit_with_its_second_conv_at_zero_passes_its_input_through_a_lif():
    """r_out = LIF_b(r_in): only the shortcut feeds unit b's LIF."""
    c, width, t = 2, 4, 6
    net = {"input_channels": c, "input_width": width, "timesteps": t,
           "n_classes": c * width,
           "stages": [{"channels": c, "proj_kw": 1, "units": 1, "kw": 3,
                       "pool": 1}],
           "fc_specs": [[c * width, c * width]], "readout": "current_sum",
           "lif_alpha": 0.9, "lif_theta": 1.0, "lif_v_th": 1.0}
    rng = np.random.default_rng(3)
    w = {"conv": [
        # projection: identity, and a threshold that every input spike
        # crosses and nothing else reaches, so r_in is the input spikes
        _layer(np.eye(c, dtype=np.float32)[None], 0.9, 1.0, 0.5),
        _layer(rng.normal(size=(3, c, c)).astype(np.float32), 0.9, 1.0, 1.0),
        _layer(np.zeros((3, c, c), np.float32), 0.9, 1.0, 1.0)],
        # readout: identity, so the logits are r_out's spike counts
        "fc": [_layer(np.eye(c * width, dtype=np.float32), 0.9, 1.0, 1.0)]}
    iq = rng.normal(size=(5, 2, width)).astype(np.float32)
    logits, _ = reference.float_reference(iq, w, net)

    spikes, _ = reference.encode_float(iq, t)        # (N, T, W, C) = r_in
    expect = np.zeros((5, c * width))
    for f in range(5):
        for ch in range(c):
            for pos in range(width):
                v, count = 0.0, 0
                for step in range(t):
                    v = 0.9 * v + spikes[f, step, pos, ch]
                    if v > 1.0:
                        v -= 1.0
                        count += 1
                expect[f, ch * width + pos] = count
    np.testing.assert_allclose(logits, expect, rtol=0, atol=1e-6)
    # by hand: inputs 1,1,1 give v = 1 (no spike, not above 1), 1.9
    # (spike, v 0.9), 1.81 (spike): 2; so some neuron counts 2 of 3
    assert expect.max() >= 1 and expect.sum() > 0


def _tiny_resnet_setup(seed):
    cfg = _tiny_resnet()
    net = cfg["network"]
    iq, labels, _ = frames.frame_pool(seed, 64, [0.0, 10.0, 18.0],
                                      frame_len=int(net["input_width"]))
    w = weights_mod.to_host(weights_mod.make_weights(seed, cfg, iq, labels))
    return cfg, net, iq, w


@pytest.mark.parametrize("seed", [4, 2 ** 31 + 9])
def test_stage_reference_float64_and_float32_jax_agree_where_judged(seed):
    import jax
    import jax.numpy as jnp

    cfg, net, iq, w = _tiny_resnet_setup(seed)
    layers, _ = network.layers(net)
    for group, n in (("conv", 8), ("fc", 2)):
        assert len(w[group]) == n
    for layer, spec in zip(w["conv"] + w["fc"], layers):
        shape = (spec.kw, spec.c_in, spec.c_out) if spec.kind == "conv" \
            else (spec.c_in, spec.c_out)
        assert layer["w"].shape == shape
    logits64, margin = reference.float_reference(iq, w, net)
    with jax.default_matmul_precision("highest"):
        logits32, _ = reference.float_reference(iq, w, net, xp=jnp,
                                                dtype=np.float32)
    judged = margin >= float(cfg["check"]["tie_eps"])
    assert judged.mean() > 0.5
    np.testing.assert_array_equal(logits32[judged].argmax(axis=1),
                                  logits64[judged].argmax(axis=1))
    np.testing.assert_allclose(logits32[judged], logits64[judged],
                               rtol=1e-4, atol=1e-4)


def test_weights_fit_pass_runs_the_reference_network():
    """The readout's features are the reference's spikes into the last FC."""
    import jax.numpy as jnp

    cfg, net, iq, w = _tiny_resnet_setup(5)
    d = net["fc_specs"][-1][0]
    layers = [{k: jnp.asarray(v) for k, v in l.items()}
              for l in w["conv"] + w["fc"]]
    counts = np.asarray(weights_mod._readout_inputs(
        jnp.asarray(iq), layers, net), np.float64)
    probe = dict(net, fc_specs=net["fc_specs"][:-1] + [[d, d]], n_classes=d)
    w_probe = {"conv": w["conv"], "fc": w["fc"][:-1] + [
        _layer(np.eye(d, dtype=np.float32), 0.9, 1.0, 1.0)]}
    logits, _ = reference.float_reference(iq, w_probe, probe)
    # judged by the network's own decisions (the probe's logits tie often)
    _, margin = reference.float_reference(iq, w, net)
    judged = margin >= float(cfg["check"]["tie_eps"])
    assert judged.mean() > 0.5
    np.testing.assert_array_equal(counts[judged], logits[judged])


def test_stage_network_has_no_integer_twin():
    _, net, iq, w = _tiny_resnet_setup(6)
    with pytest.raises(ValueError, match="residual stages"):
        reference.integer_reference(iq, w, net, 8)


def test_program_config_fields_of_a_stage_network(stage_fields):
    import run as bench_run

    net = _tiny_resnet()["network"]
    stages = ((4, 1, 1, 3, 2), (8, 1, 2, 3, 2))
    assert network.program_value("stages", net["stages"]) == stages
    if stage_fields:
        cfg = bench_run.snn_config(net)
        assert cfg.stages == stages and cfg.input_channels == 2
    else:
        with pytest.raises(bench_run.Refused, match="no field input_channels, "
                                                    "stages"):
            bench_run.snn_config(net)
    chain = _cfg("snn-amc-f32-d50")["network"]
    from repro.models.snn import SNNConfig
    assert bench_run.snn_config(chain) == SNNConfig()


def test_a_chain_only_program_refuses_a_stage_network(monkeypatch):
    """The refusal path, with a stand-in that has the chain's fields only."""
    import dataclasses

    import repro.models.snn as snn
    import run as bench_run

    chain_only = dataclasses.make_dataclass(
        "SNNConfig", [(k, object, None) for k in bench_run.CHAIN_CASTS],
        frozen=True)
    monkeypatch.setattr(snn, "SNNConfig", chain_only)
    with pytest.raises(bench_run.Refused, match="no field input_channels, "
                                                "stages"):
        bench_run.snn_config(_tiny_resnet()["network"])
    chain = _cfg("snn-amc-f32-d50")["network"]
    assert bench_run.snn_config(chain).conv_specs == \
        tuple(tuple(s) for s in chain["conv_specs"])


def test_cost_of_the_published_residual_network():
    layers, _ = network.layers(OSHEA_RESNET)
    assert len(layers) == 6 * 5 + 3
    conv_macs = sum(l.n_weights * l.width for l in layers if l.kind == "conv")
    fc_macs = sum(l.n_weights for l in layers if l.kind == "fc")
    assert (conv_macs, fc_macs) == (25_853_952, 84_992)
    work = cost.layer_work(OSHEA_RESNET)
    macs = sum(v for k, v in work.items() if k not in ("lif", "shortcut"))
    assert macs == 415_023_104
    assert work["lif"] == 10_330_112
    assert work["shortcut"] == 1_032_192
    assert cost.work_per_frame(OSHEA_RESNET) == 426_385_408
    assert cost.weight_bytes(OSHEA_RESNET) == 670_496
    assert cost.frame_bytes(OSHEA_RESNET) == 4 * (2 * 1024 + 24)


def test_radioml2018_class_set():
    assert len(frames.MODULATIONS_2018) == len(set(frames.MODULATIONS_2018)) \
        == 24
    iq, labels, _ = frames.frame_pool(7, 96, [-20.0, 30.0], frame_len=1024,
                                      class_set="radioml2018")
    assert iq.shape == (96, 2, 1024) and np.isfinite(iq).all()
    assert labels.min() >= 0 and labels.max() < 24
    assert len(np.unique(labels)) > 16
    for scheme, size in (("32QAM", 32), ("128QAM", 128), ("16APSK", 16),
                         ("32APSK", 32), ("64APSK", 64), ("128APSK", 128)):
        assert len(np.unique(np.round(frames._CONSTELLATIONS[scheme], 9))) \
            == size


if __name__ == "__main__":
    for path, table in (
            (DIGESTS, {f"{n}/{s}": chain_digests(n, s)
                       for n in CHAIN_CONFIGS for s in DIGEST_SEEDS}),
            (STAGE_DIGESTS, {f"{n}/{s}": stage_digest(n, s)
                             for n, s in STAGE_CASES})):
        with open(path, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
            f.write("\n")
        print(json.dumps(table, indent=1))
