"""The benchmark's one description of a network, expanded in one place.

Weights (:mod:`weights`), the plain reference and its control
(:mod:`reference`, :mod:`control`), the comparison (:mod:`check`) and the
cost model (:mod:`cost`) all read :func:`layers`, so a network they can
describe they describe alike.

A configuration's ``network`` takes one of two forms.  Both have
``input_width``, ``timesteps`` (the Σ-Δ oversampling), ``n_classes``,
``fc_specs`` (``[[d_in, d_out], ...]``), ``readout`` (``current_sum`` or
``spike_count``) and the LIF constants ``lif_alpha``, ``lif_theta``,
``lif_v_th``.

* Chain: ``conv_specs`` ``[[kw, c_in, c_out], ...]`` and ``pool``.  Each
  conv runs with its LIF and is followed by a max-pool of ``pool``.
* Stages: ``input_channels`` and ``stages``, a list of
  ``{"channels", "proj_kw", "units", "kw", "pool"}`` (the program takes
  each as a tuple in that order, :data:`STAGE_FIELDS`).

A stage, per timestep, on binary input spikes ``x`` (membranes carry over
timesteps and start at 0 for every frame)::

    r = LIF_p(conv_proj_kw(x))                 # projection
    for each of `units` units:
        h = LIF_a(conv_kw(r))
        r = LIF_b(conv_kw(h) + r)              # identity shortcut
    y = maxpool_pool(r)

The shortcut adds the unit's input spikes of the same timestep to its
second conv's current.  Every conv is "same"-padded with stride 1, as
``reference._conv``.  A LIF is ``v <- alpha v + I; s = [v > v_th];
v <- v - theta s``.  After the last stage (or conv) the spikes are
flattened channel-major into the FC chain, each FC with its LIF; under
``current_sum`` the last FC's currents are summed over timesteps instead.

Departures of the stage form from the residual network of O'Shea, Roy &
Clancy, "Over-the-Air Deep Learning Based Radio Signal Classification"
(IEEE JSTSP 2018, arXiv:1712.04578, Table III, Figs. 4-5):

* LIF neurons in place of ReLU (after a unit's first conv and after the
  add) and of SELU (in the FC layers);
* a LIF after the 1x1 projection, which is linear in the source, so that
  every conv's input is binary;
* Σ-Δ spike input over ``timesteps`` in place of the raw I/Q samples;
* a current-sum readout in place of softmax;
* no normalisation layers;
* convolution width 3 is assumed: the source's table gives output sizes
  only.

Layers are named ``conv1...convN`` in execution order (per stage: the
projection, then each unit's first and second conv) and ``fc1...fcM``.
A chain expands to exactly the names, shapes and order it always had.
"""
from __future__ import annotations

from typing import List, Mapping, NamedTuple, Tuple

STAGE_FIELDS = ("channels", "proj_kw", "units", "kw", "pool")


class Layer(NamedTuple):
    """One weighted layer: ``kind`` ``conv`` or ``fc`` (an FC has kw 1)."""
    name: str
    kind: str
    kw: int
    c_in: int
    c_out: int
    width: int       # output positions it runs at (1 for an FC)

    @property
    def n_weights(self) -> int:
        return self.kw * self.c_in * self.c_out


class Op(NamedTuple):
    """One step of a timestep, in order.

    ``skip``: the current spikes become the shortcut.  ``conv`` / ``fc``:
    the layer ``layer`` (an index into the layers) with its LIF; with
    ``shortcut`` the saved spikes are added to its current before the LIF.
    ``pool``: max-pool by ``size``.  ``flatten``: channel-major.
    """
    kind: str
    layer: int = -1
    size: int = 0
    shortcut: bool = False


def is_chain(net: Mapping) -> bool:
    return "conv_specs" in net


def input_channels(net: Mapping) -> int:
    return int(net["conv_specs"][0][1] if is_chain(net)
               else net["input_channels"])


def stage_tuples(net: Mapping) -> Tuple[Tuple[int, ...], ...]:
    """``stages`` as the program takes them, fields in :data:`STAGE_FIELDS`."""
    return tuple(tuple(int(s[f]) for f in STAGE_FIELDS)
                 for s in net["stages"])


def program_value(key: str, value):
    """A network key as the program's config takes it: nested tuples."""
    if key == "stages":
        return stage_tuples({"stages": value})
    if isinstance(value, list):
        return tuple(program_value(key, v) for v in value)
    return value


def layers(net: Mapping) -> Tuple[List[Layer], List[Op]]:
    """The weighted layers in order and the op sequence of one timestep."""
    out: List[Layer] = []
    ops: List[Op] = []
    width = int(net["input_width"])
    channels = input_channels(net)

    def conv(kw: int, c_out: int, shortcut: bool = False):
        nonlocal channels
        out.append(Layer(f"conv{len(out) + 1}", "conv", int(kw), channels,
                         int(c_out), width))
        ops.append(Op("conv", layer=len(out) - 1, shortcut=shortcut))
        channels = int(c_out)

    def pool(size: int):
        nonlocal width
        ops.append(Op("pool", size=int(size)))
        width //= int(size)

    if is_chain(net):
        for kw, c_in, c_out in net["conv_specs"]:
            if int(c_in) != channels:
                raise ValueError(f"network: conv{len(out) + 1} takes {c_in} "
                                 f"channels, gets {channels}")
            conv(kw, c_out)
            pool(net["pool"])
    else:
        for channels_out, proj_kw, units, kw, size in stage_tuples(net):
            conv(proj_kw, channels_out)
            for _ in range(units):
                ops.append(Op("skip"))
                conv(kw, channels_out)
                conv(kw, channels_out, shortcut=True)
            pool(size)
    ops.append(Op("flatten"))
    n_conv = len(out)
    if int(net["fc_specs"][0][0]) != channels * width:
        raise ValueError(f"network: fc1 takes {net['fc_specs'][0][0]} "
                         f"inputs, the convs give {channels * width}")
    for i, (d_in, d_out) in enumerate(net["fc_specs"]):
        out.append(Layer(f"fc{i + 1}", "fc", 1, int(d_in), int(d_out), 1))
        ops.append(Op("fc", layer=n_conv + i))
    return out, ops
