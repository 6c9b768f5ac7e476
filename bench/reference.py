"""Plain reference of the served SNN classifier, written apart from the program.

Two references, one per kind of configuration:

* :func:`float_reference` runs the float network (Σ-Δ encoder, conv + LIF
  + max-pool stages or residual stages, FC + LIF, current-sum or
  spike-count readout; the ops of :func:`network.layers`) in float64 with
  NumPy.  Besides the logits it returns, per frame, the least distance of
  any decision the network takes from its threshold: the encoder's
  comparator, every LIF membrane whose spikes feed the output, and the gap
  between the two largest logits.  A float32 path that sums in another
  order may decide differently only where that distance is within float32
  rounding, so such a frame is a tie and not an error.
* :func:`integer_reference` runs the integer twin: weights quantised to
  ``bits`` per layer by max-abs calibration, a Q0.15 Σ-Δ front end, int32
  gated accumulation, shift leak, strict threshold, soft reset and a
  saturating int16 membrane.  Its logits are exact integers.  It takes
  chains only.

``weights`` is the pytree of :func:`weights.make_weights` as NumPy arrays;
``net`` the configuration's ``network`` block.  Frames are processed in
blocks so that memory stays small.  Frames are independent, so the
float reference in NumPy runs its blocks on a pool of threads (NumPy's
matmuls and elementwise passes release the interpreter lock), with the
BLAS held to one thread each.  Each frame goes through the operations of
one serial pass; ``bench/tests/test_network.py`` holds the results to
the bits a serial pass gave.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Tuple

import numpy as np
from threadpoolctl import threadpool_limits

import network

BLOCK = 256
ENC_ONE = 1 << 15
ENC_HALF = 1 << 14
TARGET_VTH = 4096


def _sigmoid(x) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64)))


def _pad_same(x, kw: int, xp=np):
    """Zero-pad the width axis (1) of (N, W, C) for a same-size conv."""
    left = (kw - 1) // 2
    return xp.pad(x, ((0, 0), (left, kw - 1 - left), (0, 0)))


def _conv(x, w, dot: Callable, xp=np):
    """(N, W, C) spikes, (KW, C, O) weights -> (N, W, O) currents."""
    n, width, c = x.shape
    kw = w.shape[0]
    xpad = _pad_same(x, kw, xp)
    taps = xp.stack([xpad[:, k:k + width, :] for k in range(kw)], axis=2)
    cur = dot(taps.reshape(n * width, kw * c), w.reshape(kw * c, -1))
    return xp.reshape(cur, (n, width, w.shape[2]))


def _pool(s, p: int):
    n, width, c = s.shape
    w2 = width // p * p
    return s[:, :w2].reshape(n, width // p, p, c).max(axis=2)


def _flatten(x, xp=np):
    """(N, W, C) -> (N, C * W), channel-major like the network's FC1 input."""
    return xp.transpose(x, (0, 2, 1)).reshape(x.shape[0], -1)


# ---------------------------------------------------------------------------
# Float network
# ---------------------------------------------------------------------------

def encode_float(iq: np.ndarray, timesteps: int, dtype=np.float64
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """First-order Σ-Δ: (N, 2, L) -> spikes (N, T, L, 2), margin (N,).

    ``x = (iq / (max|iq| + 1e-8) + 1) / 2``; ``integ += x - y``;
    ``y = integ >= 0.5``.
    """
    x = np.asarray(iq, dtype)
    peak = np.abs(x).max(axis=(1, 2), keepdims=True)
    x = dtype(0.5) * (x / (peak + dtype(1e-8)) + dtype(1.0))
    integ = np.zeros_like(x)
    y = np.zeros_like(x)
    margin = np.full(x.shape[0], np.inf)
    out = np.empty((x.shape[0], timesteps) + x.shape[1:][::-1], dtype)
    for t in range(timesteps):
        integ = integ + x - y
        margin = np.minimum(margin, np.abs(integ - 0.5).min(axis=(1, 2)))
        y = (integ >= 0.5).astype(dtype)
        out[:, t] = np.transpose(y, (0, 2, 1))
    return out, margin


def _float_layers(weights: dict, weight_map: Callable, xp, dtype):
    def lif(layer):
        return tuple(xp.asarray(np.asarray(a, np.float64).reshape(-1), dtype)
                     for a in (_sigmoid(layer["alpha_logit"]), layer["theta"],
                               layer["v_th"]))

    def w(layer):
        masked = np.asarray(layer["w"]) * np.asarray(layer["mask"])
        return xp.asarray(weight_map(masked), dtype)

    return [(w(l), lif(l)) for l in weights["conv"] + weights["fc"]]


def _lif(v, cur, params, margin, n, xp, dtype):
    alpha, theta, v_th = params
    v = alpha * v + cur
    # v > v_th exactly where v - v_th > 0: with gradual underflow the
    # difference of two unequal floats is never 0 and keeps their order
    d = v - v_th
    margin = xp.minimum(margin, xp.abs(d).reshape(n, -1).min(axis=1))
    s = (d > 0).astype(dtype)
    return v - theta * s, s, margin


def _float_block(iq, net, layers, ops, dot, xp, dtype):
    n = iq.shape[0]
    spikes, margin = encode_float(iq, net["timesteps"], dtype)
    spikes, margin = xp.asarray(spikes), xp.asarray(margin, dtype)
    readout = net.get("readout", "current_sum")
    last = len(layers) - 1
    v = [0.0] * len(layers)
    logits = 0.0
    for t in range(net["timesteps"]):
        x = spikes[:, t]
        for op in ops:
            if op.kind == "skip":
                skip = x
                continue
            if op.kind == "pool":
                x = _pool(x, op.size)
                continue
            if op.kind == "flatten":
                x = _flatten(x, xp)
                continue
            w, params = layers[op.layer]
            cur = _conv(x, w, dot, xp) if op.kind == "conv" else dot(x, w)
            if op.shortcut:
                cur = cur + skip
            if op.layer == last and readout == "current_sum":
                logits = logits + cur   # this layer's spikes feed nothing
                continue
            v[op.layer], x, margin = _lif(v[op.layer], cur, params, margin,
                                          n, xp, dtype)
            if op.layer == last:
                logits = logits + x
    top2 = xp.sort(logits, axis=1)[:, -2:]
    margin = xp.minimum(margin, top2[:, 1] - top2[:, 0])
    return np.asarray(logits, np.float64), np.asarray(margin, np.float64)


def float_reference(iq: np.ndarray, weights: dict, net: dict,
                    weight_map: Callable = lambda w: w,
                    dot: Callable = np.matmul, xp=np, dtype=np.float64):
    """Logits (N, classes) and per-frame decision margins (N,).

    By default in float64 with NumPy.  ``weight_map``, ``dot``, ``xp`` and
    ``dtype`` exist for the control, which runs this same network at a
    lower precision (for instance with ``xp=jax.numpy`` on a chip).  Every
    LIF feeds the margin: in a residual stage the projection's and both
    of each unit's.  With ``xp`` NumPy the frames are cut into one block
    per CPU (more where a block would pass :data:`BLOCK` frames), each
    run on a thread; any other ``xp`` runs blocks of :data:`BLOCK` frames
    one after another.
    """
    layers = _float_layers(weights, weight_map, xp, dtype)
    _, ops = network.layers(net)
    n = iq.shape[0]
    cpus = os.cpu_count() or 1
    size = max(1, min(BLOCK, -(-n // cpus))) if xp is np else BLOCK

    def block(start):
        return _float_block(iq[start:start + size], net, layers, ops, dot,
                            xp, dtype)

    starts = range(0, n, size)
    if xp is np:
        with threadpool_limits(limits=1, user_api="blas"), \
                ThreadPoolExecutor(max(1, min(cpus, len(starts)))) as pool:
            parts = list(pool.map(block, starts))
    else:
        parts = [block(s) for s in starts]
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]))


def bf16_3pass_weights(w: np.ndarray) -> np.ndarray:
    """The weights as a three-pass bfloat16 matmul (``Precision.HIGH``) sees them.

    Such a matmul splits each float32 operand into a bfloat16 head and a
    bfloat16 tail and drops the tail-by-tail product.  A spike is exact in
    bfloat16 (its tail is 0), so against spikes the product keeps exactly
    ``head(w) + tail(w)``: the weight to 16 significant bits.
    """
    import ml_dtypes

    w = np.asarray(w, np.float32)
    head = w.astype(ml_dtypes.bfloat16).astype(np.float32)
    tail = (w - head).astype(ml_dtypes.bfloat16).astype(np.float32)
    return head + tail


# ---------------------------------------------------------------------------
# Integer twin
# ---------------------------------------------------------------------------

def quantize_layer(w_masked: np.ndarray, bits: int):
    """Max-abs step in float32 and round-half-even codes, clipped to ``bits``."""
    qmax, qmin = 2 ** (bits - 1) - 1, -(2 ** (bits - 1))
    w32 = np.asarray(w_masked, np.float32)
    peak = float(np.max(np.abs(w32))) if w32.size else 0.0
    step = float(np.float32(max(peak / qmax, 1e-8)))
    codes = np.clip(np.round(w32 / np.float32(step)), qmin, qmax)
    return codes.astype(np.int64), step


def integer_lif(layer: dict, step: float):
    """Per-neuron (leak shift, threshold, reset) and the layer's input shift."""
    one_minus = np.maximum(1.0 - _sigmoid(layer["alpha_logit"]), 2.0 ** -20)
    leak = np.clip(np.round(-np.log2(one_minus)), 0, 15).astype(np.int64)
    vth_units = np.asarray(layer["v_th"], np.float64) / step
    ratio = max(float(np.mean(np.abs(vth_units))), 1.0) / TARGET_VTH
    acc_shift = int(np.clip(np.floor(np.log2(ratio)) if ratio > 1.0 else 0,
                            0, 24))
    scale = float(2 ** acc_shift)
    vth = np.round(vth_units / scale).astype(np.int64)
    theta = np.round(np.asarray(layer["theta"], np.float64) / step
                     / scale).astype(np.int64)
    return (leak.reshape(-1), vth.reshape(-1), theta.reshape(-1), acc_shift)


def encode_integer(iq: np.ndarray, timesteps: int) -> np.ndarray:
    """Float32 max-abs normalisation, then a Q0.15 Σ-Δ: (N, T, L, 2) 0/1."""
    x = np.asarray(iq, np.float32)
    peak = np.abs(x).max(axis=(1, 2), keepdims=True)
    x = np.float32(0.5) * (x / (peak + np.float32(1e-8)) + np.float32(1.0))
    xq = np.round(x * np.float32(ENC_ONE)).astype(np.int64)
    integ = np.zeros_like(xq)
    y = np.zeros_like(xq)
    out = np.empty((x.shape[0], timesteps) + x.shape[1:][::-1], np.int64)
    for t in range(timesteps):
        integ = integ + xq - y * ENC_ONE
        y = (integ >= ENC_HALF).astype(np.int64)
        out[:, t] = np.transpose(y, (0, 2, 1))
    return out


def _int_dot(a, b):
    # integer products summed in float64 are exact far beyond these sizes
    # (|code| <= 2**15, fan-in <= 2**12), and use the BLAS
    return np.rint(np.matmul(np.asarray(a, np.float64),
                             np.asarray(b, np.float64))).astype(np.int64)


def _int_lif(v16, cur, consts):
    leak, vth, theta, acc_shift = consts
    v_acc = v16 - (v16 >> leak) + (cur >> acc_shift)
    s = (v_acc > vth).astype(np.int64)
    return np.clip(v_acc - theta * s, -(2 ** 15), 2 ** 15 - 1), s


def integer_reference(iq: np.ndarray, weights: dict, net: dict,
                      bits: int) -> np.ndarray:
    """Exact integer logits (N, classes) of the integer twin at ``bits``."""
    if not network.is_chain(net):
        raise ValueError("integer_reference: there is no integer twin of a "
                         "network with residual stages; a chain only")
    def layer(l):
        codes, step = quantize_layer(np.asarray(l["w"]) * np.asarray(l["mask"]),
                                     bits)
        return codes, integer_lif(l, step)

    convs = [layer(l) for l in weights["conv"]]
    fcs = [layer(l) for l in weights["fc"]]
    readout = net.get("readout", "current_sum")
    out = []
    for s0 in range(0, iq.shape[0], BLOCK):
        spikes = encode_integer(iq[s0:s0 + BLOCK], net["timesteps"])
        n = spikes.shape[0]
        v_conv = [np.int64(0)] * len(convs)
        v_fc = [np.int64(0)] * len(fcs)
        logits = np.zeros((n, fcs[-1][0].shape[1]), np.int64)
        for t in range(net["timesteps"]):
            x = spikes[:, t]
            for i, (codes, consts) in enumerate(convs):
                v_conv[i], s = _int_lif(v_conv[i], _conv(x, codes, _int_dot),
                                        consts)
                x = _pool(s, net["pool"])
            x = _flatten(x)
            for i, (codes, consts) in enumerate(fcs):
                cur = _int_dot(x, codes)
                last = i == len(fcs) - 1
                v_fc[i], x = _int_lif(v_fc[i], cur, consts)
                if last:
                    logits += cur if readout == "current_sum" else x
        out.append(logits)
    return np.concatenate(out)
