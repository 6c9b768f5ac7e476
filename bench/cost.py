"""Work and bytes of one served frame, counted from shapes and masks.

Work is 2 operations per multiply-accumulate over the **non-zero** weights
at every output position and timestep, plus 4 operations per LIF
neuron-step (decay, accumulate, compare, reset) of every layer whose
spikes feed the output, plus 1 per element and timestep of a residual
shortcut's add; shapes from :func:`network.layers`.  Encoder, pooling and
the readout sum are left out: together they are under 1% of the dense
count.  The count is the same whatever implements the network, so a
kernel that skips zero weights or silent inputs is credited in time and
never in work.

Bytes are what one call of the serving step must move at least: the
stored weights and LIF constants once per call, and per frame the I/Q
input and the outputs (logits plus any counters).
"""
from __future__ import annotations

from typing import Dict, Mapping, Optional

import numpy as np

import network

LIF_OPS = 4
SHORTCUT_OPS = 1
LIF_CONSTS = 3      # alpha, theta, v_th per neuron (or per channel)


def layer_work(net: Mapping, nonzero: Optional[Mapping[str, int]] = None
               ) -> Dict[str, float]:
    """Operations per frame by layer (``<layer>`` MACs, ``lif`` neurons).

    ``nonzero`` maps ``conv1``... ``fcN`` to the number of non-zero
    weights; a missing layer counts dense.  A network with residual
    stages also has ``shortcut``, its adds.
    """
    nonzero = nonzero or {}
    t = int(net["timesteps"])
    readout = net.get("readout", "current_sum")
    layers, ops = network.layers(net)
    last = len(layers) - 1
    out: Dict[str, float] = {}
    lif_neurons = 0
    for i, layer in enumerate(layers):
        out[layer.name] = (2.0 * nonzero.get(layer.name, layer.n_weights)
                           * layer.width * t)
        if i < last or readout != "current_sum":
            lif_neurons += layer.c_out * layer.width
    out["lif"] = float(LIF_OPS * lif_neurons * t)
    adds = sum(layers[op.layer].c_out * layers[op.layer].width
               for op in ops if op.shortcut)
    if adds:
        out["shortcut"] = float(SHORTCUT_OPS * adds * t)
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
    layers, _ = network.layers(net)
    n = sum(layer.n_weights for layer in layers)
    consts = sum(layer.c_out for layer in layers)
    return int(n * bytes_per_weight + LIF_CONSTS * consts * 4)


def frame_bytes(net: Mapping, n_counters: int = 0) -> int:
    """Per frame: float32 I/Q in, int32/float32 logits and counters out."""
    return int(4 * (network.input_channels(net) * int(net["input_width"])
                    + int(net["n_classes"]) + n_counters))


def least_time_s(frames: float, calls: float, work: float, wbytes: float,
                 fbytes: float, peak_ops: float, peak_bytes: float):
    """The chip's least time for ``calls`` calls serving ``frames`` rows.

    Returns (seconds, "compute" or "memory") — the larger bound and its name.
    """
    compute = frames * work / peak_ops
    memory = (calls * wbytes + frames * fbytes) / peak_bytes
    return (compute, "compute") if compute >= memory else (memory, "memory")
