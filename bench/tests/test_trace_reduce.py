"""The trace reduction (bench/trace_reduce.py) on a trace recorded on the chip.

``data/stream_fused_0p3s.xplane.pb`` is a 0.3-s ``--trace 1`` run of
``f32-d50.saturate`` on one TPU v5 lite; that run printed
``busy_s`` 0.057612926 and ``window_s`` 0.307788028, and 0.057479555 s
of ``stream_fused``.
"""
import os

import pytest

import trace_reduce as T

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "stream_fused_0p3s.xplane.pb")


@pytest.fixture(scope="module")
def profile():
    return T.load(TRACE)


def test_recorded_trace_reduces_to_what_the_run_printed(profile):
    window = T.find_annotation(profile, "bench.window")
    assert window is not None
    r = T.reduce_trace(profile, *window)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.307788028, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.057612926, abs=1e-9)
    assert r["op_s"]["stream_fused"] == pytest.approx(0.057479555, abs=1e-9)
    # one fused kernel call per serving step, and nearly all busy time
    assert r["op_count"]["stream_fused"] == pytest.approx(
        r["module_count"]["jit_step"], abs=1)
    assert r["op_s"]["stream_fused"] / r["busy_s"] > 0.99
    assert r["top_ops"][0][0] == "stream_fused"
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)


def test_stable_names():
    assert T.stable_op_name(
        "%stream_fused.1 = (f32[64,1,11]) custom-call(f32[64,8,2,128] %b)") \
        == "stream_fused"
    assert T.stable_op_name("%while.9 = (s32[]) while(...)") == "while"
    assert T.stable_module_name("jit_step(4125161582376499119)") == "jit_step"


def test_union_complement_and_labels():
    busy = T.union([(0, 10), (5, 20), (30, 40), (41, 45)])
    assert busy == [(0, 20), (30, 40), (41, 45)]
    gaps = T.complement(busy, -5, 50)
    assert gaps == [(-5, 0), (20, 30), (40, 41), (45, 50)]
    labels = [(19, 28, "engine.resolve"), (28, 31, "engine.gather"),
              (44, 60, "engine.step")]
    assert T.label_gaps(gaps, labels) == {
        "unlabelled": 6, "engine.resolve": 10, "engine.step": 5}
