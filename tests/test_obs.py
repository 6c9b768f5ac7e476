"""Observability tier: metrics registry, request tracing, activity gauges.

Three layers under test:

* :mod:`repro.obs.metrics` — the thread-safe registry and its Prometheus
  0.0.4 text exposition (escaping, cumulative histogram buckets, the
  info-pattern ``set_exclusive``, cross-replica ``merged``);
* :mod:`repro.obs.trace` — per-request span timelines through every
  serving outcome: complete, expired, cancelled, shed — including the
  acceptance check that a full timeline is reconstructible from the
  ``dump()`` artifact on a >=2-replica fleet path;
* :mod:`repro.obs.activity` — the live Tables I/III gauges, which must
  agree **bit-exactly** with the pinned ``test_stream_golden`` literals
  on the paper config (fp32 counters are integral below 2**24).

Tracing is process-global state, so every test runs behind an autouse
fixture that installs a fresh default registry and disables tracing on
the way out — no test can leak observability state into another.
"""
import json
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import SNNConfig, compile_plan, compile_snn, init_snn
from repro.fleet import Autoscaler, FleetRouter, ShedError, engine_factory
from repro.obs import (
    TERMINAL_EVENTS,
    ActivityObserver,
    MetricsRegistry,
    MetricsServer,
    TraceLog,
    begin_trace,
    default_registry,
    disable_tracing,
    enable_tracing,
    get_tracer,
    set_default_registry,
    static_schedule_counts,
)
from repro.plan import PlanCache
from repro.plan.streaming import profile_layer_steps
from repro.serve import AsyncAMCServeEngine, MicroBatcher
from repro.train.pruning import make_mask_pytree

CFG = SNNConfig(
    conv_specs=((3, 2, 4), (3, 4, 8)),
    pool=2,
    fc_specs=((32, 16), (16, 5)),
    input_width=16,
    timesteps=3,
    n_classes=5,
)
FRAME_SHAPE = (2, CFG.input_width)

#: The full success timeline, in order, for a fleet-submitted request.
HAPPY_PATH = ["submit", "admit", "enqueue", "dequeue", "batch-form",
              "jit-step-start", "jit-step-end", "complete"]


@pytest.fixture(autouse=True)
def isolated_obs():
    """Fresh default registry + tracing off, per test, restored after."""
    prev = set_default_registry(MetricsRegistry())
    disable_tracing()
    try:
        yield
    finally:
        disable_tracing()
        set_default_registry(prev)


@pytest.fixture(scope="module")
def weights():
    params = init_snn(jax.random.PRNGKey(0), CFG)
    masks = make_mask_pytree(params, 0.5)
    return params, masks


def _iq(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n,) + FRAME_SHAPE).astype(np.float32)


# ---------------------------------------------------------------------------
# metrics registry: kinds, labels, exposition, merge, thread safety
# ---------------------------------------------------------------------------

def test_registry_basics_and_kind_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "reqs")
    c.inc()
    c.inc(2.5)
    assert reg.value("requests_total") == 3.5
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    assert reg.value("depth") == 5
    # re-declaring the same (name, kind, labels) is idempotent
    assert reg.counter("requests_total", "reqs") is c
    # same name under a different kind or label set must fail loudly
    with pytest.raises(ValueError):
        reg.gauge("requests_total", "now a gauge?")
    with pytest.raises(ValueError):
        reg.counter("requests_total", "reqs", ("engine",))
    with pytest.raises(ValueError):
        c.inc(-1)  # counters only go up


def test_labeled_children_and_prometheus_escaping():
    reg = MetricsRegistry()
    fam = reg.counter("events_total", 'help with "quotes"\nand newline',
                      ("kind",))
    fam.labels(kind='we"ird\n\\value').inc(4)
    assert fam.labels(kind='we"ird\n\\value') is fam.labels(
        kind='we"ird\n\\value')
    with pytest.raises(ValueError):
        fam.labels(wrong="name")
    with pytest.raises(ValueError):
        fam.inc()  # labeled family has no unlabeled child
    text = reg.to_prometheus()
    assert "# TYPE events_total counter" in text
    # HELP escapes newline/backslash but not quotes (format 0.0.4)
    assert '# HELP events_total help with "quotes"\\nand newline' in text
    assert 'events_total{kind="we\\"ird\\n\\\\value"} 4' in text


def test_histogram_exposition_is_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.to_prometheus()
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 3' in text
    assert 'lat_seconds_bucket{le="10"} 4' in text
    assert 'lat_seconds_bucket{le="+Inf"} 5' in text
    assert "lat_seconds_count 5" in text
    assert f"lat_seconds_sum {0.05 + 0.5 + 0.5 + 5.0 + 50.0}" in text


def test_set_exclusive_info_pattern():
    reg = MetricsRegistry()
    fam = reg.gauge("production_info", "who serves", ("version",))
    fam.set_exclusive(version="v1")
    fam.set_exclusive(version="v2")
    assert reg.value("production_info", version="v1") == 0
    assert reg.value("production_info", version="v2") == 1


def test_merged_registries():
    a, b = MetricsRegistry(), MetricsRegistry()
    for reg, n in ((a, 3), (b, 4)):
        reg.counter("reqs_total", "", ("engine",)).labels(
            engine="e").inc(n)
        reg.gauge("depth", "").set(n)
        reg.histogram("lat", "", buckets=(1.0,)).observe(0.5)
    m = MetricsRegistry.merged([a, b])
    assert m.value("reqs_total", engine="e") == 7
    assert m.value("depth") == 7            # same-label gauges add
    assert m.get("lat").labels().count == 2


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("hot_total", "contended")
    n_threads, per = 8, 2000

    def work():
        for _ in range(per):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.value("hot_total") == n_threads * per


# ---------------------------------------------------------------------------
# tracing: sampling, ring bound, and every terminal on the serving path
# ---------------------------------------------------------------------------

def test_tracing_disabled_by_default(weights):
    assert get_tracer() is None and begin_trace() is None
    params, masks = weights
    eng = AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                              buckets=[4], max_delay_ms=5)
    try:
        fut = eng.submit(_iq(1)[0])
        fut.result(timeout=30)
        assert fut.trace is None  # untraced requests carry no timeline
    finally:
        eng.close()


def test_sampling_is_deterministic():
    log = TraceLog(sample_every=3)
    picks = [log.begin() is not None for _ in range(9)]
    assert picks == [True, False, False] * 3
    assert log.n_seen == 9 and log.n_started == 3


def test_ring_buffer_bounds_completed_traces():
    log = TraceLog(capacity=4)
    for i in range(10):
        tr = log.begin()
        tr.add("submit", t=float(i))
        tr.add("complete", t=float(i) + 0.5)
        tr.finish()
        tr.finish()  # idempotent: double-finish records once
    assert log.n_completed == 10
    kept = log.completed()
    assert len(kept) == 4
    assert [tr.events[0].t for tr in kept] == [6.0, 7.0, 8.0, 9.0]


def test_engine_happy_path_timeline(weights):
    params, masks = weights
    enable_tracing(sample_every=1)
    eng = AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                              buckets=[4], max_delay_ms=5)
    try:
        futs = [eng.submit(_iq(4)[i]) for i in range(4)]
        for f in futs:
            f.result(timeout=30)
    finally:
        eng.close()
    for f in futs:
        tr = f.trace
        assert tr is not None
        names = [ev.name for ev in tr.events]
        # the engine path is the fleet path minus the admission hop
        assert names == [n for n in HAPPY_PATH if n != "admit"]
        ts = [ev.t for ev in tr.events]
        assert ts == sorted(ts), "span timestamps must be monotonic"
        assert tr.terminal() == "complete"
        step_events = {ev.name: ev for ev in tr.events}
        assert "pred" in step_events["complete"].attrs
        assert step_events["jit-step-start"].attrs["backend"] == "dense"


def test_expired_request_trace():
    enable_tracing(sample_every=1)
    b = MicroBatcher(FRAME_SHAPE, buckets=[4], max_delay_ms=1)
    tr = begin_trace()
    tr.add("submit")
    fut = b.submit(_iq(1)[0], deadline=b.now() - 1.0, trace=tr)
    assert b.get_batch(timeout=0.2) is None  # expired, never batched
    with pytest.raises(Exception):
        fut.result(timeout=1)
    assert tr.terminal() == "expired"
    assert tr in get_tracer().completed()


def test_cancelled_request_trace():
    enable_tracing(sample_every=1)
    b = MicroBatcher(FRAME_SHAPE, buckets=[4], max_delay_ms=1)
    tr = begin_trace()
    tr.add("submit")
    fut = b.submit(_iq(1)[0], trace=tr)
    assert fut.cancel()
    assert b.get_batch(timeout=0.2) is None  # cancelled, never batched
    assert tr.terminal() == "cancelled"
    assert get_tracer().n_completed == 1


def test_shed_request_trace(weights):
    """Admission refusal at the fleet door records the shed terminal —
    after a per-replica ``replica-full`` hop for every replica tried."""
    params, masks = weights
    enable_tracing(sample_every=1)
    fleet = FleetRouter(
        engine_factory(params, CFG, masks=masks, backend="dense",
                       buckets=[2], max_delay_ms=50, pace_ms=500.0,
                       max_queue=2),
        replicas=1)
    try:
        sheds = 0
        for i in range(12):
            try:
                fleet.submit(_iq(12)[i])
            except ShedError:
                sheds += 1
        assert sheds > 0
        shed_traces = [tr for tr in get_tracer().completed()
                       if tr.terminal() == "shed"]
        assert len(shed_traces) == sheds
        names = [ev.name for ev in shed_traces[0].events]
        assert names[0] == "submit"
        assert "replica-full" in names and names[-1] == "shed"
        assert default_registry().value(
            "repro_fleet_shed_total", reason="queue",
            priority="realtime") == sheds
    finally:
        fleet.close()


def test_fleet_two_replica_timeline_from_dump(weights):
    """Acceptance: full span timelines reconstructible from the trace-dump
    artifact, on the fleet path, with >=2 replicas."""
    params, masks = weights
    enable_tracing(sample_every=1)
    fleet = FleetRouter(
        engine_factory(params, CFG, masks=masks, backend="dense",
                       buckets=[4], max_delay_ms=5),
        replicas=2)
    try:
        preds = fleet.classify(_iq(12), timeout=60)
        assert preds.shape == (12,)
    finally:
        fleet.close()
    dump = json.loads(json.dumps(get_tracer().dump()))  # JSON round-trip
    assert dump["n_seen"] == 12 and dump["n_completed"] == 12
    replicas_seen = set()
    for rec in dump["traces"]:
        assert rec["terminal"] == "complete"
        assert [ev["name"] for ev in rec["events"]] == HAPPY_PATH
        admit = rec["events"][1]
        replicas_seen.add(admit["replica"])
        # spans are reconstructible and non-negative end to end
        assert len(rec["spans"]) == len(HAPPY_PATH) - 1
        assert all(s["seconds"] >= 0 for s in rec["spans"])
        assert rec["total_s"] >= 0
    assert len(replicas_seen) == 2, "JSQ must have used both replicas"
    assert default_registry().value("repro_fleet_submitted_total") == 12


def test_trace_sampling_through_engine(weights):
    params, masks = weights
    enable_tracing(sample_every=4)
    eng = AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                              buckets=[4], max_delay_ms=5)
    try:
        futs = [eng.submit(_iq(8)[i]) for i in range(8)]
        for f in futs:
            f.result(timeout=30)
    finally:
        eng.close()
    traced = [f for f in futs if f.trace is not None]
    assert len(traced) == 2  # ceil(8/4): submissions 0 and 4
    assert get_tracer().n_completed == 2


# ---------------------------------------------------------------------------
# activity gauges: bit-exact against the pinned Tables I/III literals
# ---------------------------------------------------------------------------

def _golden_setup():
    from test_stream_golden import DENSITY as G_DENSITY
    from test_stream_golden import GOLDEN_LAYERS

    from repro.configs.saocds_amc import CONFIG

    program = compile_snn(CONFIG)
    params = init_snn(jax.random.PRNGKey(0), CONFIG)
    masks = make_mask_pytree(params, G_DENSITY)
    rng = np.random.default_rng(0)
    frames = jnp.asarray(
        (rng.random((1, CONFIG.timesteps, CONFIG.conv_specs[0][1],
                     CONFIG.input_width)) < 0.5).astype(np.float32))
    return CONFIG, program, params, masks, frames, GOLDEN_LAYERS


def test_activity_gauges_match_stream_goldens():
    cfg, program, params, masks, frames, golden = _golden_setup()
    plan = compile_plan(program, params, masks=masks, assignment="stream",
                        cache=PlanCache(disk_dir=""))
    assert plan.supports_live_counters
    # static Table I geometry, read without serving anything
    sched = static_schedule_counts(plan)
    for name, want in golden.items():
        for key in ("reps_per_timestep", "compute_iters", "extra_iters",
                    "empty_iters"):
            assert sched[name][key] == want[key]

    logits, accs = plan.batch_counters(frames)
    reg = MetricsRegistry()
    obs = ActivityObserver(plan, registry=reg, engine="golden")
    obs.observe({k: np.asarray(v) for k, v in accs.items()}, n_real=1)
    for name, want in golden.items():
        got = reg.value("repro_activity_accumulations_total",
                        engine="golden", layer=name)
        assert got == want["accumulations"], (
            f"{name}: live gauge {got} != golden {want['accumulations']}")
        assert reg.value("repro_activity_schedule", layer=name,
                         counter="reps_per_timestep") == \
            want["reps_per_timestep"]
    assert reg.value("repro_activity_frames_total", engine="golden") == 1
    # and the logits came from the same step — not a side computation
    assert np.asarray(logits).shape[0] == 1


def test_activity_observer_totals_exact_past_float32(weights):
    """A batch whose summed counts pass 2**24 must still total exactly:
    a float32 sum of 64 paper-config frames (~262k events each) rounds."""
    params, masks = weights
    plan = compile_plan(compile_snn(CFG), params, masks=masks,
                        assignment="stream", cache=PlanCache(disk_dir=""))
    counts = (262144 + np.random.default_rng(0).integers(0, 4000, 64)
              ).astype(np.float32)
    want = int(counts.astype(np.int64).sum())
    assert int(counts.sum()) != want          # the float32 sum rounds here
    reg = MetricsRegistry()
    obs = ActivityObserver(plan, registry=reg, engine="wide")
    obs.observe({"conv1": counts}, n_real=64)
    assert reg.value("repro_activity_accumulations_total", engine="wide",
                     layer="conv1") == want


def test_batch_counters_fused_matches_stream(weights):
    """The fused stack's per-row counters agree with the interpreter's."""
    params, masks = weights
    program = compile_snn(CFG)
    frames = jnp.asarray((np.random.default_rng(3).random(
        (3, CFG.timesteps, 2, CFG.input_width)) < 0.5).astype(np.float32))
    plans = {
        a: compile_plan(program, params, masks=masks, assignment=a,
                        cache=PlanCache(disk_dir=""))
        for a in ("stream", "pallas_fused")
    }
    outs = {}
    for a, plan in plans.items():
        assert plan.supports_live_counters
        logits, accs = plan.batch_counters(frames)
        outs[a] = {k: np.asarray(v) for k, v in accs.items()}
        assert set(outs[a]) == {"conv1", "conv2"}
    for name in outs["stream"]:
        np.testing.assert_array_equal(outs["stream"][name],
                                      outs["pallas_fused"][name])
    assert static_schedule_counts(plans["pallas_fused"]) == \
        static_schedule_counts(plans["stream"])


def test_engine_live_activity_gauges(weights):
    params, masks = weights
    eng = AsyncAMCServeEngine(params, CFG, masks=masks, backend="stream",
                              buckets=[4], max_delay_ms=5, name="live")
    try:
        eng.classify(_iq(8), timeout=60)
    finally:
        eng.close()
    reg = default_registry()
    assert reg.value("repro_activity_frames_total", engine="live") == 8
    for layer in ("conv1", "conv2"):
        acc = reg.value("repro_activity_accumulations_total",
                        engine="live", layer=layer)
        assert acc > 0 and acc == int(acc)  # fp32-exact integer counts
        assert 0 < reg.value("repro_activity_effective_density",
                             engine="live", layer=layer) <= 1.0
    # serving mirrors landed too, under the engine's name label
    assert reg.value("repro_serve_requests_total", engine="live") == 8
    assert reg.get("repro_serve_request_latency_seconds").labels(
        engine="live").count == 8


# ---------------------------------------------------------------------------
# control-plane metric emission: autoscaler / canary / swap
# ---------------------------------------------------------------------------

class _FakeFleet:
    def __init__(self):
        self.t = 0.0
        self.sig = dict(p99_ms=0.0, queue_depth=0, n_replicas=1,
                        shed=0, expired=0, workers=1, busy_s=0.0)
        self.ups = 0

    def signals(self):
        self.t += 1.0
        return dict(self.sig, t=self.t)

    def scale_up(self):
        self.ups += 1
        self.sig["n_replicas"] += 1
        return f"r{self.sig['n_replicas']}"

    def scale_down(self):
        return None


def test_autoscaler_emits_tick_metrics():
    fleet = _FakeFleet()
    scaler = Autoscaler(fleet, target_p99_ms=10.0, up_patience=1,
                        cooldown_ticks=0, clock=lambda: fleet.t)
    scaler.step()                       # p99 0 -> hold
    fleet.sig["p99_ms"] = 50.0
    scaler.step()                       # breach -> scale-up
    reg = default_registry()
    assert reg.value("repro_autoscale_ticks_total", action="hold") == 1
    assert reg.value("repro_autoscale_ticks_total", action="scale-up") == 1
    assert reg.value("repro_autoscale_p99_ms") == 50.0
    assert reg.value("repro_autoscale_replicas") == 1  # count at tick time
    assert fleet.ups == 1


def test_swap_and_canary_metrics(weights):
    from repro.deploy import hot_swap
    from repro.deploy.monitor import CanaryMonitor, MonitorConfig

    params, masks = weights
    eng = AsyncAMCServeEngine(params, CFG, masks=masks, backend="dense",
                              buckets=[4], max_delay_ms=5)
    reg = default_registry()
    try:
        report = hot_swap(eng, params, masks, label="v2", warmup=False)
        assert report.drained
        assert reg.value("repro_deploy_swaps_total", outcome="drained") == 1
        assert reg.value("repro_deploy_production_info", version="v2") == 1
        assert reg.get("repro_deploy_bind_seconds") is not None

        def frames(seed, n, snr):
            iq = _iq(n, seed=seed % (2**31))
            return iq, np.zeros((n,), dtype=np.int64)

        mon = CanaryMonitor(
            eng, baseline="default", canary="v2",
            config=MonitorConfig(snr_bins=(0.0,), frames_per_bin=4,
                                 min_rounds=1, promote_after=2,
                                 score="agreement"),
            frame_source=frames)
        assert mon.run(max_rounds=4) == "promote"
        assert reg.value("repro_canary_rounds_total", canary="v2") >= 2
        assert reg.value("repro_canary_decisions_total",
                         decision="promote", canary="v2") == 1
        # promote advanced the production info marker exclusively
        assert reg.value("repro_deploy_production_info", version="v2") == 1
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# exporters: the /metrics endpoint and the per-layer step profiler
# ---------------------------------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.headers.get("Content-Type"), resp.read()


def test_metrics_server_endpoints():
    reg = default_registry()
    reg.counter("smoke_total", "smoke").inc(3)
    with MetricsServer(port=0) as srv:
        status, ctype, body = _get(srv.url("/metrics"))
        assert status == 200
        assert ctype.startswith("text/plain; version=0.0.4")
        assert b"smoke_total 3" in body
        status, ctype, body = _get(srv.url("/healthz"))
        assert status == 200 and json.loads(body)["status"] == "ok"
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv.url("/trace"))     # tracing disabled -> 404
        assert e.value.code == 404
        enable_tracing(sample_every=1)
        tr = begin_trace()
        tr.add("submit")
        tr.add("complete")
        tr.finish()
        status, _, body = _get(srv.url("/trace"))
        assert status == 200
        assert json.loads(body)["n_completed"] == 1


def test_profile_layer_steps_sets_gauges(weights):
    params, masks = weights
    program = compile_snn(CFG)
    plan = compile_plan(program, params, masks=masks, assignment="stream",
                        cache=PlanCache(disk_dir=""))
    frames = jnp.zeros((CFG.timesteps, 2, CFG.input_width), jnp.float32)
    ms = profile_layer_steps(plan, frames, reps=1)
    assert set(ms) == {lp.spec.name for lp in plan.layers}
    assert all(v > 0 for v in ms.values())
    reg = default_registry()
    backends = {lp.spec.name: lp.backend for lp in plan.layers}
    for name, got_ms in ms.items():
        assert reg.value("repro_plan_layer_step_ms", layer=name,
                         backend=backends[name]) == got_ms
