"""The program-span reduction (bench/spans.py) and the metrics that read it.

``data/spans_f32_0p4s.xplane.pb`` is a 0.4-s ``--trace 1`` run of
``f32-d50.saturate`` (seed 2147483801) on one TPU v5 lite, with the
program's spans.  That run printed ``fetch_wake_ms`` 0.8150594999999999
and ``dispatch_host_ms`` 1.5851795000000002; ``python3 bench/spans.py``
on its trace printed ``gather_wait_ms`` 0.5466, ``gc_pause_share``
2.4093754835917407, 52 batches, a clock offset of 0.001439202 s and
0.004482804 s of idle time unlabelled.  A synthetic profile of four chips
checks the matching of each batch to its ``jit_step`` on every chip; the
recorded trace of a program without the spans
(``data/stream_fused_0p3s.xplane.pb``) checks that the readers then read
nothing.
"""
import inspect
import os
from types import SimpleNamespace as NS

import pytest

import spans as S
import trace_reduce as T
from metrics_loader import read_metric

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("fetch_wake_ms", "dispatch_host_ms", "gather_wait_ms",
           "gc_pause_share")


@pytest.fixture(autouse=True)
def original_reduce_trace():
    """``spans.install()`` wraps ``trace_reduce.reduce_trace`` for the
    whole process (loading a reader calls it): each test starts from the
    original and leaves it in place for the tests that follow."""
    original = inspect.unwrap(T.reduce_trace)
    T.reduce_trace = original
    yield
    T.reduce_trace = original


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=line, events=[
            NS(name=ev[0], start_ns=ev[1], duration_ns=ev[2] - ev[1],
               stats=list(ev[3].items()) if len(ev) > 3 else [])
            for ev in events])
        for line, events in lines.items()])


def four_chip_profile(skew=0):
    """Two batches served on four chips in a window [0, 1000] ns.

    On chip c the first batch's step runs 115+c .. 200+20c (chip 3 ends
    last, at 260), the second's 410+c .. 430+c; every chip also ran a
    step at 50..60, before either batch was dispatched, and a copy at
    1000..1010, after the window.  The host enqueues each step as it
    starts; the chips' clocks run ``skew`` ns behind the host's.
    """
    worker = [("engine.gather", 0, 100), ("batcher.form", 90, 100),
              ("engine.put", 100, 110), ("engine.dispatch", 110, 130),
              ("engine.fetch", 130, 300), ("engine.resolve", 300, 320),
              ("engine.gather", 320, 400), ("batcher.form", 395, 400),
              ("engine.put", 400, 405), ("engine.dispatch", 405, 420),
              ("engine.fetch", 420, 600), ("engine.resolve", 600, 610),
              ("engine.gather", 610, 1000)]
    client = [("loadgen.submit", 0, 1000), ("host.gc", 500, 550)]
    runtime = []
    chips = []
    for c in range(4):
        steps = [(50, 60), (115 + c, 200 + 20 * c), (410 + c, 430 + c)]
        runtime += [("DoEnqueueProgram", s, s + 1,
                     {"run_id": k, "device_ordinal": c})
                    for k, (s, _) in enumerate(steps)]
        chips.append(_plane(f"/device:TPU:{c}", {
            "XLA Modules": [(f"jit_step({c})", s - skew, e - skew,
                             {"run_id": k})
                            for k, (s, e) in enumerate(steps)],
            "XLA Ops": [("%stream_fused.1 = f32", s - skew, e - skew)
                        for s, e in steps + [(1000, 1010)]]}))
    host = _plane("/host:CPU", {"python3": worker, "loadgen": client,
                                "runtime": runtime})
    return NS(planes=[host] + chips)


def test_batches_match_the_latest_step_over_chips():
    r = S.reduce_spans(four_chip_profile(), 0, 1000)
    assert r["instrumented"] and r["window_s"] == pytest.approx(1e-6)
    got = [{k: v * 1e9 for k, v in b.items()} for b in r["batches"]]
    assert got == [
        # ready at 260 (chip 3): fetch 130..300 waited, woke 40 ns late
        {"dispatch_host_s": pytest.approx(30), "gather_s": pytest.approx(100),
         "fetch_wake_s": pytest.approx(40)},
        # ready at 433 (chip 3), fetch ended at 600
        {"dispatch_host_s": pytest.approx(20), "gather_s": pytest.approx(80),
         "fetch_wake_s": pytest.approx(167)}]
    assert r["gc_s"] == pytest.approx(50e-9)
    assert r["compiles"] == 0


def test_chips_are_put_back_on_the_host_clock():
    straight = S.reduce_spans(four_chip_profile(), 0, 1000)
    skewed = S.reduce_spans(four_chip_profile(skew=70), 0, 1000)
    assert straight["clock_offset_s"] == [0.0] * 4
    assert skewed["clock_offset_s"] == [pytest.approx(70e-9)] * 4
    for key in ("batches", "idle_gaps", "gc_s"):
        assert skewed[key] == straight[key]


def test_innermost_span_labels_each_instant():
    pieces = S.innermost([(420, 600, "engine.fetch"), (500, 550, "host.gc"),
                          (600, 610, "engine.resolve")])
    assert pieces == [(420, 500, "engine.fetch"), (500, 550, "host.gc"),
                      (550, 600, "engine.fetch"), (600, 610, "engine.resolve")]


def test_idle_time_is_labelled_by_program_spans_over_all_chips():
    pd = four_chip_profile()
    r = S.reduce_spans(pd, 0, 1000)
    idle = dict(r["idle_gaps"])
    busy = T.reduce_trace(pd, 0, 1000)["busy_s"]
    assert sum(idle.values()) == pytest.approx(4 * (1e-6 - busy), rel=1e-9)
    assert "unlabelled" not in idle
    assert r["idle_gaps"][0][0] == "engine.gather"
    # the collection on the client thread began last: its 50 ns on each
    # chip go to host.gc, inside the worker's engine.fetch
    assert idle["host.gc"] == pytest.approx(4 * 50e-9)
    assert S.split([(0, 10), (20, 40)], [(5, 25, "a"), (30, 35, "b")]) \
        == {"unlabelled": 15, "a": 10, "b": 5}
    # a gap no program span covers is unlabelled
    wider = dict(S.reduce_spans(pd, 0, 1100)["idle_gaps"])
    assert wider["unlabelled"] == pytest.approx(4 * 90e-9)


def test_readers_read_the_reduction_through_the_hook():
    S.install()
    S.install()
    assert T.reduce_trace.with_program_spans
    run = NS(trace=T.reduce_trace(four_chip_profile(), 0, 1000))
    assert read_metric("fetch_wake_ms", run) == pytest.approx(103.5e-6)
    assert read_metric("dispatch_host_ms", run) == pytest.approx(25e-6)
    assert read_metric("gather_wait_ms", run) == pytest.approx(90e-6)
    assert read_metric("gc_pause_share", run) == pytest.approx(5.0)


def test_recorded_trace_reduces_to_what_the_run_printed():
    S.install()
    pd = T.load(os.path.join(DATA, "spans_f32_0p4s.xplane.pb"))
    run = NS(trace=T.reduce_trace(pd, *T.find_annotation(pd, "bench.window")))
    printed = {"fetch_wake_ms": 0.8150594999999999,
               "dispatch_host_ms": 1.5851795000000002,
               "gather_wait_ms": 0.5466,
               "gc_pause_share": 2.4093754835917407}
    for name, value in printed.items():
        assert read_metric(name, run) == pytest.approx(value, abs=1e-9)
    spans = run.trace["spans"]
    assert len(spans["batches"]) == 52 and spans["compiles"] == 0
    assert spans["clock_offset_s"] == [pytest.approx(0.001439202, abs=1e-9)]
    idle = dict(spans["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        run.trace["window_s"] - run.trace["busy_s"], rel=1e-6)
    assert idle["unlabelled"] == pytest.approx(0.004482804, abs=1e-9)
    assert idle["unlabelled"] < 0.05 * sum(idle.values())


def test_a_program_without_spans_reads_nothing():
    S.install()
    pd = T.load(os.path.join(DATA, "stream_fused_0p3s.xplane.pb"))
    run = NS(trace=T.reduce_trace(pd, *T.find_annotation(pd, "bench.window")))
    assert run.trace["spans"]["instrumented"] is False
    for name in READERS:
        assert read_metric(name, run) is None
    assert all(read_metric(name, NS(trace=None)) is None for name in READERS)
