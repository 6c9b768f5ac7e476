#!/usr/bin/env python3
"""The program's own spans in a JAX profiler trace, beside the device's.

The serving engine writes one span per phase of each batch on the
profiler's clock (``repro.obs.trace.span``): ``engine.gather`` (with
``batcher.form`` inside it), ``engine.put``, ``engine.dispatch``,
``engine.fetch``, ``engine.counters`` (live activity counters only) and
``engine.resolve``; the collector writes ``host.gc`` per collection and
the load generator ``loadgen.submit`` per request.  :func:`reduce_spans`
reads them from the host planes, matches each batch to its ``jit_step``
on every chip, and splits the device's idle time by the span the host
was in.

The TPU planes' times are not on the host's clock: on a v5e they run
0.3-1.7 ms behind it (a step "starts" before the host enqueued it).
:func:`clock_offsets` puts each chip back on the host's clock by the
least shift that has no step start before the host's ``DoEnqueueProgram``
for it (paired by ``run_id``); every device time below is shifted so.

``bench/run.py`` hands its metric readers what
``trace_reduce.reduce_trace`` returned; :func:`install` (called by the
readers of these spans as they are loaded) has that result also carry
``spans``, this module's reduction of the same window: a stopgap until
``bench/run.py`` hands its readers this reduction itself.  A program
without the spans (``instrumented`` false) gives its readers nothing.

    python3 bench/spans.py <trace_dir>

prints the reduction of a kept trace (``bench/run.py --trace-dir``) as
JSON, with the metrics these spans give.
"""
from __future__ import annotations

import bisect
import collections
import functools
import heapq
import json
import statistics
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

PROGRAM_PREFIXES = ("engine.", "batcher.", "host.")
LABEL_PREFIXES = PROGRAM_PREFIXES + ("loadgen.",)
STEP_MODULE = "jit_step"
COMPILE_EVENTS = ("backend_compile", "backend_compile_and_load")
ENQUEUE_EVENT = "DoEnqueueProgram"

Span = Tuple[float, float, str]          # (start_ns, end_ns, name)


def host_events(pd, lo: float, hi: float):
    """One pass over the host planes: the spans that overlap ``[lo, hi]``
    (the program's, the load generator's and JAX's backend compiles), one
    list per host thread in start order (times unclipped); and the
    earliest ``DoEnqueueProgram`` start by ``(device ordinal, run_id)``."""
    threads, enqueued = [], {}
    for plane in pd.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                name = ev.name
                if name == ENQUEUE_EVENT:
                    st = dict(ev.stats)
                    if "run_id" in st and "device_ordinal" in st:
                        key = (int(st["device_ordinal"]), int(st["run_id"]))
                        enqueued[key] = min(enqueued.get(key, ev.start_ns),
                                            ev.start_ns)
                elif name.startswith(LABEL_PREFIXES) or name in COMPILE_EVENTS:
                    s = ev.start_ns
                    e = s + ev.duration_ns
                    if e > lo and s < hi:
                        spans.append((s, e, name))
            if spans:
                threads.append(sorted(spans))
    return threads, enqueued


def clock_offsets(pd, enqueued) -> List[Optional[float]]:
    """Per chip, the nanoseconds that put its times on the host's clock:
    the largest host enqueue time minus device start over its programs
    (a lower bound; the device starts a program no earlier than the host
    enqueues it).  None where no program pairs with an enqueue."""
    out = []
    for plane in trace_reduce.device_planes(pd):
        ordinal = int(plane.name[len(trace_reduce.DEVICE_PREFIX):])
        best = None
        for line in plane.lines:
            if line.name != trace_reduce.MODULES_LINE:
                continue
            for ev in line.events:
                run_id = dict(ev.stats).get("run_id")
                t = None if run_id is None \
                    else enqueued.get((ordinal, int(run_id)))
                if t is not None:
                    d = t - ev.start_ns
                    best = d if best is None else max(best, d)
        out.append(best)
    return out


def step_modules(pd, offsets) -> List[List[Tuple[float, float]]]:
    """Each chip's ``jit_step`` executions on the host's clock, in order."""
    chips = []
    for plane, off in zip(trace_reduce.device_planes(pd), offsets):
        runs = []
        for line in plane.lines:
            if line.name != trace_reduce.MODULES_LINE:
                continue
            for ev in line.events:
                if trace_reduce.stable_module_name(ev.name) == STEP_MODULE:
                    s = ev.start_ns + (off or 0.0)
                    runs.append((s, s + ev.duration_ns))
        chips.append(sorted(runs))
    return chips


def batches(threads: Sequence[Sequence[Span]], chips, lo: float,
            hi: float) -> List[dict]:
    """One entry per batch served inside ``[lo, hi]`` (from the start of
    its ``engine.put`` to the end of its ``engine.fetch``), in seconds:

    ``dispatch_host_s``: end of ``engine.dispatch`` minus start of
    ``engine.put``; ``fetch_wake_s``: end of ``engine.fetch`` minus the
    later of its start and the latest end, over chips, of the batch's
    ``jit_step`` — on each chip the first to start after the batch's
    ``engine.dispatch`` began (None where a chip has none);
    ``gather_s``: the ``engine.gather`` that ended right before the put.
    """
    starts = [[s for s, _ in runs] for runs in chips]
    out = []
    for spans in threads:
        gather = cur = None
        for s, e, name in spans:
            if name == "engine.gather":
                gather = (s, e)
            elif name == "engine.put":
                cur = {"put": (s, e), "gather": gather}
                gather = None
            elif cur is not None and name in ("engine.dispatch",
                                              "engine.fetch"):
                cur[name[len("engine."):]] = (s, e)
                if name == "engine.fetch":
                    entry = _batch_entry(cur, chips, starts)
                    if entry is not None and cur["put"][0] >= lo \
                            and cur["fetch"][1] <= hi:
                        out.append(entry)
                    cur = None
    return out


def _batch_entry(b: dict, chips, starts) -> Optional[dict]:
    if "dispatch" not in b:
        return None
    ready = None
    for runs, st in zip(chips, starts):
        i = bisect.bisect_left(st, b["dispatch"][0])
        if i == len(runs):
            ready = None
            break
        ready = runs[i][1] if ready is None else max(ready, runs[i][1])
    fetch_s, fetch_e = b["fetch"]
    return {
        "dispatch_host_s": (b["dispatch"][1] - b["put"][0]) / 1e9,
        "fetch_wake_s": (None if ready is None
                         else (fetch_e - max(fetch_s, ready)) / 1e9),
        "gather_s": (None if b["gather"] is None
                     else (b["gather"][1] - b["gather"][0]) / 1e9),
    }


def innermost(spans: Sequence[Span]) -> List[Span]:
    """Disjoint pieces labelled, at each instant, by the span that began
    last among those open then (over every thread)."""
    spans = sorted(spans)
    points = sorted({t for s, e, _ in spans for t in (s, e)})
    out: List[Span] = []
    heap: list = []                      # (-start, end, name): latest first
    k = 0
    for a, b in zip(points, points[1:]):
        while k < len(spans) and spans[k][0] <= a:
            s, e, name = spans[k]
            heapq.heappush(heap, (-s, e, name))
            k += 1
        # a closed span only matters while it sits on top
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        name = heap[0][2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def split(gaps: Sequence[Tuple[float, float]],
          pieces: Sequence[Span]) -> Dict[str, float]:
    """Nanoseconds of the (sorted, disjoint) ``gaps`` under each label of
    the (sorted, disjoint) ``pieces``; ``unlabelled`` where none."""
    out: Dict[str, float] = collections.Counter()
    ends = [e for _, e, _ in pieces]
    for gs, ge in gaps:
        covered = 0.0
        i = bisect.bisect_right(ends, gs)
        while i < len(pieces) and pieces[i][0] < ge:
            s, e, name = pieces[i]
            part = min(e, ge) - max(s, gs)
            out[name] += part
            covered += part
            i += 1
        if ge - gs > covered:
            out["unlabelled"] += ge - gs - covered
    return dict(out)


def idle_by_span(pd, lo: float, hi: float, offsets,
                 pieces: Sequence[Span]) -> Dict[str, float]:
    """Device-idle seconds in ``[lo, hi]`` (host clock), summed over
    chips, split by the innermost span open at each instant."""
    out: Dict[str, float] = collections.Counter()
    for plane, off in zip(trace_reduce.device_planes(pd), offsets):
        off = off or 0.0
        busy = trace_reduce.union(
            (s + off, e + off) for _, s, e, _ in trace_reduce._events(
                plane, trace_reduce.OPS_LINE, lo - off, hi - off))
        for name, ns in split(trace_reduce.complement(busy, lo, hi),
                              pieces).items():
            out[name] += ns / 1e9
    return dict(out)


def reduce_spans(pd, lo: float, hi: float) -> dict:
    """The spans of ``[lo, hi]``: ``instrumented`` (any engine span at
    all), ``window_s``, ``clock_offset_s`` (per chip), ``batches``
    (:func:`batches`), ``gc_s`` (union of ``host.gc``, clipped),
    ``idle_gaps`` (``[name, seconds]``, largest first) and ``compiles``."""
    threads, enqueued = host_events(pd, lo, hi)
    offsets = clock_offsets(pd, enqueued)
    flat = [sp for t in threads for sp in t]
    gc_s = sum(min(e, hi) - max(s, lo) for s, e in trace_reduce.union(
        (s, e) for s, e, name in flat if name == "host.gc")) / 1e9
    idle = idle_by_span(pd, lo, hi, offsets, innermost(
        [(max(s, lo), min(e, hi), name) for s, e, name in flat]))
    return {
        "instrumented": any(name.startswith("engine.")
                            for _, _, name in flat),
        "window_s": (hi - lo) / 1e9,
        "clock_offset_s": [None if d is None else d / 1e9 for d in offsets],
        "batches": batches(threads, step_modules(pd, offsets), lo, hi),
        "gc_s": gc_s,
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1]),
        "compiles": sum(name in COMPILE_EVENTS and s >= lo
                        for s, _, name in flat),
    }


def of_run(run) -> Optional[dict]:
    """The run's span reduction, or None (untraced, or no program spans)."""
    spans = (run.trace or {}).get("spans")
    return spans if spans and spans["instrumented"] else None


def median_ms(run, key: str) -> Optional[float]:
    """Median of one per-batch quantity over the window, in ms."""
    spans = of_run(run)
    values = [b[key] for b in spans["batches"]
              if b[key] is not None] if spans else []
    return 1e3 * statistics.median(values) if values else None


def install() -> None:
    """Have ``trace_reduce.reduce_trace`` also return key ``spans``."""
    base = trace_reduce.reduce_trace
    if getattr(base, "with_program_spans", False):
        return

    @functools.wraps(base)
    def reduce_trace(pd, lo, hi, labels=()):
        out = base(pd, lo, hi, labels)
        out["spans"] = reduce_spans(pd, lo, hi)
        return out

    reduce_trace.with_program_spans = True
    trace_reduce.reduce_trace = reduce_trace


def main(argv=None) -> int:
    import types

    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    pd = trace_reduce.load(trace_reduce.find_xplane(args[0]))
    window = trace_reduce.find_annotation(pd, "bench.window")
    if window is None:
        print("spans: the trace has no bench.window annotation",
              file=sys.stderr)
        return 1
    spans = reduce_spans(pd, *window)
    run = types.SimpleNamespace(trace={"spans": spans})
    busy = trace_reduce.reduce_trace(pd, *window)["busy_s"]
    gc_share = (100.0 * spans["gc_s"] / spans["window_s"]
                if spans["instrumented"] else None)
    print(json.dumps({
        "window_s": spans["window_s"], "busy_s": busy,
        "batches": len(spans["batches"]),
        "fetch_wake_ms": median_ms(run, "fetch_wake_s"),
        "dispatch_host_ms": median_ms(run, "dispatch_host_s"),
        "gather_wait_ms": median_ms(run, "gather_s"),
        "gc_pause_share": gc_share,
        "compiles": spans["compiles"],
        "clock_offset_s": spans["clock_offset_s"],
        "idle_gaps_spans": spans["idle_gaps"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
