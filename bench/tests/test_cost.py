"""Work per frame counted from shapes and masks (bench/cost.py)."""
import json
import os

import pytest

import cost
import frames
import weights as weights_mod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name="snn-amc-f32-d50"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_dense_work_by_layer_matches_the_paper_network():
    work = cost.layer_work(_cfg()["network"])
    # 2 * (kw*ic*oc) * positions * T
    assert work["conv1"] == 2 * 11 * 2 * 16 * 128 * 8 == 720_896
    assert work["conv2"] == 2 * 11 * 16 * 32 * 64 * 8 == 5_767_168
    assert work["conv3"] == 2 * 5 * 32 * 64 * 32 * 8 == 5_242_880
    assert work["fc1"] == 2 * 1024 * 128 * 8 == 2_097_152
    assert work["fc2"] == 2 * 128 * 11 * 8 == 22_528
    macs = sum(v for k, v in work.items() if k != "lif")
    assert macs == pytest.approx(13.85e6, rel=1e-3)
    # LIF on conv1-3 and fc1; fc2's spikes feed nothing under current_sum
    assert work["lif"] == 4 * (16 * 128 + 32 * 64 + 64 * 32 + 128) * 8


@pytest.mark.parametrize("density", [0.25, 0.5, 1.0])
def test_work_scales_with_density(density):
    net = _cfg()["network"]
    dense = cost.layer_work(net)
    nonzero = {f"fc{i + 1}": int(round(din * dout * density))
               for i, (din, dout) in enumerate(net["fc_specs"])}
    sparse = cost.layer_work(net, nonzero)
    for name in nonzero:
        assert sparse[name] == pytest.approx(dense[name] * density, rel=1e-3)
    assert sparse["conv2"] == dense["conv2"]


def test_nonzero_counts_from_masks_match_the_configured_density():
    cfg = _cfg()
    iq, labels, _ = frames.frame_pool(5, 64, [0.0, 10.0])
    w = weights_mod.to_host(weights_mod.make_weights(2 ** 31 + 11, cfg, iq,
                                                     labels))
    counts = cost.nonzero_counts(w)
    net = cfg["network"]
    sizes = {f"conv{i + 1}": kw * ic * oc
             for i, (kw, ic, oc) in enumerate(net["conv_specs"])}
    sizes.update({f"fc{i + 1}": din * dout
                  for i, (din, dout) in enumerate(net["fc_specs"])})
    for name, n in sizes.items():
        assert counts[name] == int(round(n * cfg["density"])), name
    half = cost.work_per_frame(net, counts)
    assert half == pytest.approx(
        0.5 * (cost.work_per_frame(net) - cost.layer_work(net)["lif"])
        + cost.layer_work(net)["lif"], rel=1e-3)


def test_least_time_picks_the_larger_bound():
    t, bound = cost.least_time_s(64, 1, 7e6, 6e5, 1e3, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(64 * 7e6 / 197e12)
    t, bound = cost.least_time_s(1, 1, 7e6, 6e5, 1e3, 197e12, 819e9)
    assert bound == "memory"
