"""Seeded weights, LIF parameters and magnitude masks, made on the device.

One jitted call turns the seed (and the frames to fit on) into every
array of the network in float32, the type the engine serves:

* conv and hidden FC weights: He-normal from the seed;
* the readout layer (the last FC): fitted in closed form, ridge
  regression of the frames' one-hot labels on the frames' spike counts
  at its input, computed by a float32 forward pass (``Precision.HIGHEST``)
  in the same call.  With every weight random, nearly all frames fall to
  one or two classes by wide margins, and no answer depends on rounding;
  a fitted readout spreads the answers over the classes with the small
  margins a trained network has;
* per-neuron LIF parameters at the configuration's constants;
* for each layer a magnitude mask keeping the ``round(n * density)``
  largest ``|w|``.

Shapes and fan-in come from :func:`network.layers`, and the fit's
forward pass walks its ops, so chains and residual stages alike.  The
same arrays feed the system under test and the plain reference.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

import network
import reference

RIDGE = 1e-3     # ridge strength, as a share of the mean feature energy


def layer_names(net: dict) -> List[str]:
    return [layer.name for layer in network.layers(net)[0]]


def densities(cfg: dict) -> Dict[str, float]:
    """Per-layer density from a configuration (a number or a dict)."""
    d = cfg["density"]
    return {name: float(d[name] if isinstance(d, dict) else d)
            for name in layer_names(cfg["network"])}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (all its bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _mask(w, density: float):
    n = int(np.prod(w.shape))
    keep = max(1, int(round(n * density)))
    if keep >= n:
        return jnp.ones(w.shape, jnp.float32)
    thresh = jnp.sort(jnp.abs(w).reshape(-1))[n - keep]
    return (jnp.abs(w) >= thresh).astype(jnp.float32)


def _encode(iq, timesteps: int):
    """Float32 first-order Σ-Δ: (N, 2, L) -> (T, N, L, 2) spikes."""
    peak = jnp.max(jnp.abs(iq), axis=(1, 2), keepdims=True)
    x = jnp.transpose(0.5 * (iq / (peak + 1e-8) + 1.0), (0, 2, 1))
    integ = jnp.zeros_like(x)
    y = jnp.zeros_like(x)
    out = []
    for _ in range(timesteps):
        integ = integ + x - y
        y = (integ >= 0.5).astype(jnp.float32)
        out.append(y)
    return out


def _readout_inputs(iq, layers, net):
    """Spike counts (N, D) at the input of the last FC layer."""
    dot = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    _, ops = network.layers(net)
    last = len(layers) - 1
    v = [0.0] * last
    counts = 0.0
    for x in _encode(iq, int(net["timesteps"])):
        for op in ops:
            if op.kind == "skip":
                skip = x
            elif op.kind == "pool":
                x = reference._pool(x, op.size)
            elif op.kind == "flatten":
                x = reference._flatten(x, jnp)
            elif op.layer < last:
                i = op.layer
                layer = layers[i]
                w = layer["w"] * layer["mask"]
                alpha = jax.nn.sigmoid(layer["alpha_logit"]).reshape(-1)
                if op.kind == "conv":
                    cur = reference._conv(x, w, dot, jnp)
                else:
                    cur = dot(x, w)
                if op.shortcut:
                    cur = cur + skip
                v[i] = alpha * v[i] + cur
                s = (v[i] > layer["v_th"].reshape(-1)).astype(jnp.float32)
                v[i] = v[i] - layer["theta"].reshape(-1) * s
                x = s
        counts = counts + x
    return counts


def make_weights(seed: int, cfg: dict, fit_iq: np.ndarray,
                 fit_labels: np.ndarray):
    """Return ``{"conv": [...], "fc": [...]}`` of per-layer dicts on device.

    Each layer dict holds ``w`` (masked is ``w * mask``), ``mask`` (0/1
    float32), ``alpha_logit``, ``theta`` and ``v_th``.  The last FC layer
    is fitted to ``fit_labels`` on the frames ``fit_iq`` (N, 2, L).
    """
    net = cfg["network"]
    dens = densities(cfg)
    alpha = float(net["lif_alpha"])
    logit = math.log(alpha / (1.0 - alpha))
    specs, _ = network.layers(net)
    names = [layer.name for layer in specs]
    shapes = [("conv", (l.kw, l.c_in, l.c_out), l.kw * l.c_in, (l.c_out, 1))
              if l.kind == "conv" else
              ("fc", (l.c_in, l.c_out), l.c_in, (l.c_out,)) for l in specs]
    n_conv = sum(l.kind == "conv" for l in specs)
    n_classes = int(net["n_classes"])

    @jax.jit
    def gen(key, iq, labels):
        keys = jax.random.split(key, len(shapes))
        layers = []
        for k, name, (_, shape, fan_in, lif_shape) in zip(keys, names,
                                                          shapes):
            w = jax.random.normal(k, shape, jnp.float32) \
                * jnp.float32(math.sqrt(2.0 / fan_in))
            layers.append({
                "w": w, "mask": _mask(w, dens[name]),
                "alpha_logit": jnp.full(lif_shape, logit, jnp.float32),
                "theta": jnp.full(lif_shape, net["lif_theta"], jnp.float32),
                "v_th": jnp.full(lif_shape, net["lif_v_th"], jnp.float32),
            })
        a = _readout_inputs(iq, layers, net)
        y = jax.nn.one_hot(labels, n_classes) - 1.0 / n_classes
        gram = jnp.matmul(a.T, a, precision=jax.lax.Precision.HIGHEST)
        ridge = RIDGE * jnp.trace(gram) / gram.shape[0] + 1e-6
        w_out = jnp.linalg.solve(
            gram + ridge * jnp.eye(gram.shape[0]),
            jnp.matmul(a.T, y, precision=jax.lax.Precision.HIGHEST))
        layers[-1]["w"] = w_out
        layers[-1]["mask"] = _mask(w_out, dens[names[-1]])
        return {"conv": layers[:n_conv], "fc": layers[n_conv:]}

    return gen(seed_key(seed), jnp.asarray(fit_iq, jnp.float32),
               jnp.asarray(fit_labels, jnp.int32))


def to_host(weights) -> dict:
    """The same pytree as NumPy arrays (for the reference)."""
    return jax.tree_util.tree_map(np.asarray, weights)
