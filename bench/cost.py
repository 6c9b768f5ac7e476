"""Work and bytes of one served frame, counted from shapes and masks.

Work is 2 operations per multiply-accumulate over the **non-zero** weights
at every output position and timestep, plus 4 operations per LIF
neuron-step (decay, accumulate, compare, reset) of every layer whose
spikes feed the output.  Encoder, pooling and the readout sum are left
out: together they are under 1% of the dense count.  The count is the
same whatever implements the network, so a kernel that skips zero weights
or silent inputs is credited in time and never in work.

Bytes are what one call of the serving step must move at least: the
stored weights and LIF constants once per call, and per frame the I/Q
input and the outputs (logits plus any counters).
"""
from __future__ import annotations

from typing import Dict, Mapping, Optional

import numpy as np

LIF_OPS = 4
LIF_CONSTS = 3      # alpha, theta, v_th per neuron (or per channel)


def _conv_widths(net: Mapping) -> list:
    widths, w = [], int(net["input_width"])
    for _ in net["conv_specs"]:
        widths.append(w)
        w //= int(net["pool"])
    return widths


def layer_work(net: Mapping, nonzero: Optional[Mapping[str, int]] = None
               ) -> Dict[str, float]:
    """Operations per frame by layer (``<layer>`` MACs, ``lif`` neurons).

    ``nonzero`` maps ``conv1``... ``fcN`` to the number of non-zero
    weights; a missing layer counts dense.
    """
    nonzero = nonzero or {}
    t = int(net["timesteps"])
    readout = net.get("readout", "current_sum")
    out: Dict[str, float] = {}
    lif_neurons = 0
    for i, ((kw, ic, oc), width) in enumerate(zip(net["conv_specs"],
                                                  _conv_widths(net))):
        name = f"conv{i + 1}"
        out[name] = 2.0 * nonzero.get(name, kw * ic * oc) * width * t
        lif_neurons += oc * width
    n_fc = len(net["fc_specs"])
    for i, (din, dout) in enumerate(net["fc_specs"]):
        name = f"fc{i + 1}"
        out[name] = 2.0 * nonzero.get(name, din * dout) * t
        if i < n_fc - 1 or readout != "current_sum":
            lif_neurons += dout
    out["lif"] = float(LIF_OPS * lif_neurons * t)
    return out


def work_per_frame(net: Mapping, nonzero: Optional[Mapping[str, int]] = None
                   ) -> float:
    return float(sum(layer_work(net, nonzero).values()))


def nonzero_counts(weights: Mapping) -> Dict[str, int]:
    """Non-zero weights per layer, read from the masks."""
    out = {}
    for group in ("conv", "fc"):
        for i, layer in enumerate(weights[group]):
            out[f"{group}{i + 1}"] = int(np.count_nonzero(np.asarray(layer["mask"])))
    return out


def weight_bytes(net: Mapping, bytes_per_weight: int = 4) -> int:
    """Stored weights (dense, as the step holds them) plus LIF constants."""
    n = sum(kw * ic * oc for kw, ic, oc in net["conv_specs"])
    n += sum(din * dout for din, dout in net["fc_specs"])
    consts = sum(oc for _, _, oc in net["conv_specs"])
    consts += sum(dout for _, dout in net["fc_specs"])
    return int(n * bytes_per_weight + LIF_CONSTS * consts * 4)


def frame_bytes(net: Mapping, n_counters: int = 0) -> int:
    """Per frame: float32 I/Q in, int32/float32 logits and counters out."""
    return int(4 * (int(net["conv_specs"][0][1]) * int(net["input_width"])
                    + int(net["n_classes"]) + n_counters))


def least_time_s(frames: float, calls: float, work: float, wbytes: float,
                 fbytes: float, peak_ops: float, peak_bytes: float):
    """The chip's least time for ``calls`` calls serving ``frames`` rows.

    Returns (seconds, "compute" or "memory") — the larger bound and its name.
    """
    compute = frames * work / peak_ops
    memory = (calls * wbytes + frames * fbytes) / peak_bytes
    return (compute, "compute") if compute >= memory else (memory, "memory")
