"""Reduce a JAX profiler trace to device busy time, kernel and step times.

A TPU trace (``<dir>/plugins/profile/<time>/<host>.xplane.pb``) holds one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event
per operation that ran and whose line ``XLA Modules`` has one event per
executed program.  Host planes hold the host threads' events.  Names are
reduced to stable ones: an op ``%stream_fused.1 = (f32[...]) custom-call(...)``
becomes ``stream_fused``, a module ``jit_step(4125161582376499119)``
becomes ``jit_step``.

All times are on the profiler's clock, in nanoseconds, and every sum is
clipped to the window ``[lo, hi]``.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP_N = 10

Interval = Tuple[float, float]


def stable_op_name(name: str) -> str:
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def stable_module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name.strip())


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def complement(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def label_gaps(gaps: Sequence[Interval],
               labels: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Idle nanoseconds by the label that covers most of each gap."""
    starts = [g[0] for g in gaps]
    ends = [g[1] for g in gaps]
    cover = [collections.Counter() for _ in gaps]
    for s, e, name in labels:
        i = bisect.bisect_right(ends, s)
        while i < len(gaps) and starts[i] < e:
            cover[i][name] += min(e, ends[i]) - max(s, starts[i])
            i += 1
    out: Dict[str, float] = collections.Counter()
    for (s, e), c in zip(gaps, cover):
        out[c.most_common(1)[0][0] if c else "unlabelled"] += e - s
    return dict(out)


def device_planes(pd) -> list:
    return sorted((p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)),
                  key=lambda p: p.name)


def _events(plane, line_name: str, lo: float, hi: float):
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            s = ev.start_ns
            e = s + ev.duration_ns
            if e > lo and s < hi:
                yield ev.name, max(s, lo), min(e, hi), s


def find_annotation(pd, name: str) -> Optional[Interval]:
    """(start, end) of the first host event called ``name``."""
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    return None


def reduce_trace(pd, lo: float, hi: float,
                 labels: Sequence[Tuple[float, float, str]] = ()) -> dict:
    """Device time in ``[lo, hi]``, averaged or summed over the chips.

    Returns ``devices``, ``window_s``, ``busy_s`` (union of op intervals,
    mean over chips), ``op_s``/``op_count`` and ``module_s``/``module_count``
    (summed over chips, by stable name; a module is counted where it
    starts), ``top_ops`` and ``idle_gaps`` (``[name, seconds]``, at most
    ``TOP_N`` each; gaps summed over chips by label).
    """
    planes = device_planes(pd)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    op_s = collections.Counter()
    op_count = collections.Counter()
    module_s = collections.Counter()
    module_count = collections.Counter()
    gap_s = collections.Counter()
    busy_total = 0.0
    for plane in planes:
        spans = []
        for name, s, e, _ in _events(plane, OPS_LINE, lo, hi):
            key = stable_op_name(name)
            op_s[key] += (e - s) / 1e9
            op_count[key] += 1
            spans.append((s, e))
        busy = union(spans)
        busy_total += sum(e - s for s, e in busy) / 1e9
        for name, s, e, s0 in _events(plane, MODULES_LINE, lo, hi):
            key = stable_module_name(name)
            module_s[key] += (e - s) / 1e9
            if s0 >= lo:
                module_count[key] += 1
        for name, ns in label_gaps(complement(busy, lo, hi), labels).items():
            gap_s[name] += ns / 1e9
    return {
        "devices": len(planes),
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / len(planes),
        "op_s": dict(op_s), "op_count": dict(op_count),
        "module_s": dict(module_s), "module_count": dict(module_count),
        "top_ops": [[k, v] for k, v in op_s.most_common(TOP_N)],
        "idle_gaps": [[k, v] for k, v in gap_s.most_common(TOP_N)],
    }
