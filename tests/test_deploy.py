"""Model lifecycle subsystem: registry round-trips, zero-downtime hot-swap
under concurrent load, canary routing proportions, monitor auto-rollback /
auto-promote, and the batcher drain barrier.

Tiny reduced config throughout (same as test_serve) so binds stay cheap.
"""
import dataclasses
import tempfile
import threading
import time

import numpy as np
import pytest

import jax

from repro.api import SNNConfig, init_snn
from repro.deploy import (
    CanaryMonitor,
    ModelRegistry,
    MonitorConfig,
    WeightedRouter,
    canary_router,
    hot_swap,
    hot_swap_async,
    hot_swap_from_registry,
    publish_from_checkpoint,
    publish_from_trainer,
)
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


@pytest.fixture(scope="module")
def models():
    p1 = init_snn(jax.random.PRNGKey(0), CFG)
    p2 = init_snn(jax.random.PRNGKey(1), CFG)
    m1 = make_mask_pytree(p1, 0.5)
    return p1, m1, p2


@pytest.fixture
def registry(tmp_path):
    return ModelRegistry(str(tmp_path / "registry"))


def _iq(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n,) + FRAME_SHAPE).astype(np.float32)


def _permuted_head(params):
    """Injected regression: rolling the last FC's output columns shifts
    every logit by one class, so the canary's argmax disagrees with the
    source model on (nearly) every frame."""
    w = np.roll(np.asarray(params["fc"][1]["w"]), 1, axis=1)
    return {"conv": params["conv"],
            "fc": [params["fc"][0], dict(params["fc"][1], w=w)]}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_publish_load_roundtrip(registry, models):
    p1, m1, _ = models
    v = registry.publish("amc", p1, CFG, masks=m1, assignment="dense",
                         metrics={"acc": 0.9}, alias="production")
    assert v.version == 1 and v.spec == "amc@1"
    assert v.plan_digest  # plan compiled + cache warmed at publish time
    loaded = registry.load("amc@production")
    assert loaded.cfg == CFG
    assert loaded.version.metrics["acc"] == 0.9
    for a, b in zip(jax.tree_util.tree_leaves(loaded.params),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(loaded.masks),
                    jax.tree_util.tree_leaves(m1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_registry_content_addressing_dedups(registry, models):
    p1, m1, p2 = models
    v1 = registry.publish("amc", p1, CFG, masks=m1)
    again = registry.publish("amc", p1, CFG, masks=m1)
    assert again.version == v1.version  # identical content -> same version
    v2 = registry.publish("amc", p2, CFG)
    assert v2.version == v1.version + 1
    assert registry.versions("amc") == [1, 2]


def test_registry_aliases_and_resolve(registry, models):
    p1, m1, p2 = models
    registry.publish("amc", p1, CFG, alias="production")
    registry.publish("amc", p2, CFG, alias="staging")
    assert registry.resolve("amc") == ("amc", 1)          # production alias
    assert registry.resolve("amc@staging") == ("amc", 2)
    assert registry.resolve("amc@2") == ("amc", 2)
    assert registry.resolve("amc@v2") == ("amc", 2)
    registry.set_alias("amc", "production", 2)
    assert registry.resolve("amc") == ("amc", 2)
    with pytest.raises(KeyError):
        registry.resolve("amc@nope")
    with pytest.raises(KeyError):
        registry.resolve("amc@7")
    with pytest.raises(KeyError):
        registry.set_alias("amc", "production", 7)
    # version-shaped aliases would shadow resolve()'s numeric forms
    with pytest.raises(ValueError):
        registry.set_alias("amc", "v2", 1)
    with pytest.raises(ValueError):
        registry.set_alias("amc", "2", 1)


def test_registry_resolve_without_alias_uses_latest(registry, models):
    p1, _, p2 = models
    registry.publish("amc", p1, CFG)
    registry.publish("amc", p2, CFG)
    assert registry.resolve("amc") == ("amc", 2)


def test_checkpoint_to_registry_to_serve_roundtrip(registry):
    """The full bridge: train -> checkpoint -> publish -> load -> serve."""
    from repro.train.trainer import SNNTrainer, TrainerConfig

    with tempfile.TemporaryDirectory() as ckpt_dir:
        tcfg = TrainerConfig(total_steps=4, batch_size=8, seed=0,
                             final_density=0.5, ckpt_dir=ckpt_dir,
                             ckpt_every=2)
        trainer = SNNTrainer(CFG, tcfg)
        trainer.run()
        v = publish_from_checkpoint(registry, "amc", CFG, tcfg,
                                    assignment="dense", alias="production")
        assert v.metrics["source_step"] == trainer.step
        assert v.has_masks
        loaded = registry.load("amc@production")
        for a, b in zip(jax.tree_util.tree_leaves(loaded.params),
                        jax.tree_util.tree_leaves(trainer.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # live-trainer publish of identical state dedups to the same version
        assert publish_from_trainer(registry, "amc",
                                    trainer).version == v.version
    with AsyncAMCServeEngine(loaded.params, loaded.cfg, masks=loaded.masks,
                             backend="dense", max_batch=8, max_delay_ms=2.0,
                             version_label=v.spec) as engine:
        preds = engine.classify(_iq(12))
    assert preds.shape == (12,) and engine.stats.requests == 12


def test_lsq_state_round_trips_to_serving(registry, models):
    """LSQ scales published to the registry must reach the served step:
    the engine's logits match the fake-quant reference, and the plan
    digest differs from the unquantized bind (the quant was applied)."""
    import jax.numpy as jnp

    from repro.api import compile_snn
    from repro.data.pipeline import sigma_delta_encode_np
    from repro.train.lsq import init_lsq_scales, make_serving_quant_fn

    p1, m1, _ = models
    lsq = init_lsq_scales(p1, bits=8)
    v = registry.publish("amc", p1, CFG, masks=m1, lsq_scales=lsq,
                         quant_bits=8, assignment="dense")
    assert v.has_lsq and v.quant_bits == 8
    loaded = registry.load("amc@1")

    iq = _iq(8, seed=3)
    frames = jnp.asarray(sigma_delta_encode_np(iq, CFG.timesteps))
    program = compile_snn(CFG)
    ref = np.asarray(program.apply_batch(
        p1, frames, "dense", masks=m1,
        quant_fn=make_serving_quant_fn(lsq, 8)))
    with AsyncAMCServeEngine(loaded.params, CFG, masks=loaded.masks,
                             backend="dense", max_batch=8,
                             lsq_scales=loaded.lsq_scales,
                             quant_bits=loaded.version.quant_bits) as eng:
        quant_digest = eng.plan.digest
        preds = eng.classify(iq)
    np.testing.assert_array_equal(preds, ref.argmax(-1))
    with AsyncAMCServeEngine(loaded.params, CFG, masks=loaded.masks,
                             backend="dense", max_batch=8) as eng:
        assert eng.plan.digest != quant_digest


# ---------------------------------------------------------------------------
# hot swap
# ---------------------------------------------------------------------------

def test_hot_swap_under_concurrent_load_zero_failures(models):
    """Acceptance bar: live hot-swap with zero dropped/failed requests."""
    p1, m1, p2 = models
    engine = AsyncAMCServeEngine(p1, CFG, masks=m1, backend="dense",
                                 max_batch=8, max_delay_ms=1.0,
                                 version_label="v1")
    futures, stop = [], threading.Event()
    lock = threading.Lock()

    def pump(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            f = engine.submit(
                rng.normal(size=FRAME_SHAPE).astype(np.float32))
            with lock:
                futures.append(f)
            # pace the offered load below serving capacity: unpaced tight
            # loops on a 1-core host grow the backlog without bound, and
            # the post-flip drain can then never finish inside its budget
            time.sleep(0.001)

    threads = [threading.Thread(target=pump, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    try:
        # ensure in-flight traffic at the flip AND that v1 has actually
        # served (a busy-spin here starves the worker on 1-core hosts,
        # letting the flip land before v1's first batch completes)
        deadline = time.perf_counter() + 60.0
        while ((len(futures) < 64 or engine.stats.requests == 0)
               and time.perf_counter() < deadline):
            time.sleep(0.001)
        assert engine.stats.requests > 0, "v1 never served before the flip"
        report = hot_swap(engine, p2, label="v2", backend="dense",
                          drain_timeout=30.0)
        # keep traffic flowing until the new primary has demonstrably
        # served (the barrier just drained the backlog, so stopping the
        # producers at the flip can leave v2 with zero requests)
        deadline = time.perf_counter() + 60.0
        while (engine.version_stats()["v2"].requests == 0
               and time.perf_counter() < deadline):
            time.sleep(0.001)
    finally:
        stop.set()
        for t in threads:
            t.join()
    results = [f.result(timeout=60.0) for f in futures]  # raises on failure

    assert report.old_label == "v1" and report.new_label == "v2"
    assert report.drained
    assert engine.active_version == "v2"
    assert len(results) == len(futures)
    stats = engine.version_stats()
    # both versions actually served traffic around the flip
    assert stats["v1"].requests > 0 and stats["v2"].requests > 0
    assert stats["v1"].requests + stats["v2"].requests == \
        engine.stats.requests
    engine.close()


def test_hot_swap_changes_served_predictions(models):
    p1, m1, p2 = models
    iq = _iq(16, seed=7)
    with AsyncAMCServeEngine(p1, CFG, masks=m1, backend="dense",
                             max_batch=8, version_label="v1") as engine:
        before = engine.classify(iq)
        hot_swap(engine, p2, label="v2", backend="dense")
        after = engine.classify(iq)
        # reference: the new params served directly
    with AsyncAMCServeEngine(p2, CFG, backend="dense", max_batch=8,
                             version_label="ref") as ref_engine:
        ref = ref_engine.classify(iq)
    np.testing.assert_array_equal(after, ref)
    assert before.shape == after.shape


def test_hot_swap_async_and_registry_path(registry, models):
    p1, m1, p2 = models
    registry.publish("amc", p1, CFG, masks=m1, assignment="dense",
                     alias="production")
    registry.publish("amc", p2, CFG, assignment="dense", alias="staging")
    loaded = registry.load("amc@production")
    with AsyncAMCServeEngine(loaded.params, CFG, masks=loaded.masks,
                             backend="dense", max_batch=8,
                             version_label="amc@1") as engine:
        report = hot_swap_from_registry(engine, registry, "amc@staging")
        assert report.new_label == "amc@2"
        assert engine.active_version == "amc@2"
        # async flavor: returns a future resolving to the report
        fut = hot_swap_async(engine, p1, masks=m1, label="v1-again",
                             backend="dense")
        assert fut.result(timeout=60.0).new_label == "v1-again"
        assert engine.active_version == "v1-again"


def test_hot_swap_rejects_duplicate_label_and_config_drift(registry, models):
    p1, m1, p2 = models
    other_cfg = dataclasses.replace(CFG, timesteps=4)
    registry.publish("amc", p2, other_cfg, assignment="dense")
    with AsyncAMCServeEngine(p1, CFG, masks=m1, backend="dense",
                             max_batch=8, version_label="v1") as engine:
        with pytest.raises(ValueError, match="already bound"):
            hot_swap(engine, p2, label="v1", backend="dense")
        with pytest.raises(ValueError, match="SNNConfig"):
            hot_swap_from_registry(engine, registry, "amc@1")


def test_remove_and_swap_guards(models):
    p1, m1, p2 = models
    with AsyncAMCServeEngine(p1, CFG, masks=m1, backend="dense",
                             max_batch=8, version_label="v1") as engine:
        with pytest.raises(KeyError):
            engine.swap_to("nope")
        with pytest.raises(ValueError, match="primary"):
            engine.remove_version("v1")
        # no autotuned assignment to inherit -> explicit error, not a
        # silent uniform fallback mislabeled "per-layer"
        with pytest.raises(ValueError, match="per-layer"):
            engine.bind_version("v3", p2, backend="per-layer")
        engine.bind_version("v2", p2, backend="dense")
        engine.swap_to("v2")
        engine.remove_version("v1")
        assert set(engine.versions()) == {"v2"}


# ---------------------------------------------------------------------------
# drain barrier
# ---------------------------------------------------------------------------

def test_drain_barrier_waits_for_preexisting_backlog():
    b = MicroBatcher(frame_shape=FRAME_SHAPE, max_batch=4, max_delay_ms=1.0)
    for _ in range(6):
        b.submit(np.zeros(FRAME_SHAPE, np.float32))
    assert not b.drain_barrier(timeout=0.05)  # nothing consumed yet
    assert b.get_batch(timeout=1.0) is not None  # 4 of 6
    assert not b.drain_barrier(timeout=0.05)
    assert b.get_batch(timeout=1.0) is not None  # remaining 2
    assert b.drain_barrier(timeout=1.0)
    # trivially true when nothing is pending
    assert b.drain_barrier(timeout=0.05)


def test_drain_barrier_released_by_close_drain():
    b = MicroBatcher(frame_shape=FRAME_SHAPE, max_batch=4, max_delay_ms=1.0)
    futs = [b.submit(np.zeros(FRAME_SHAPE, np.float32)) for _ in range(3)]
    released = threading.Event()

    def wait():
        if b.drain_barrier(timeout=10.0):
            released.set()

    t = threading.Thread(target=wait)
    t.start()
    b.close()
    drained = b.drain()
    assert len(drained) == 3
    t.join(timeout=5.0)
    assert released.is_set()
    del futs


# ---------------------------------------------------------------------------
# canary routing
# ---------------------------------------------------------------------------

def test_weighted_router_exact_proportions():
    r = WeightedRouter({"a": 75.0, "b": 25.0})
    picks = [r() for _ in range(100)]
    assert picks.count("a") == 75 and picks.count("b") == 25
    # smooth: the 25% label is interleaved, not bursty
    assert all("b" in picks[i:i + 4] for i in range(0, 100, 4))
    assert r.fractions() == {"a": 0.75, "b": 0.25}


def test_canary_router_edges():
    assert canary_router("p", "c", 0.0) is None
    assert canary_router("p", "c", 100.0)() == "c"
    with pytest.raises(ValueError):
        canary_router("p", "c", 150.0)


def test_engine_routes_canary_fraction(models):
    p1, m1, p2 = models
    with AsyncAMCServeEngine(p1, CFG, masks=m1, backend="dense",
                             max_batch=4, max_delay_ms=1.0,
                             version_label="prod") as engine:
        engine.bind_version("canary", p2, backend="dense")
        router = canary_router("prod", "canary", 25.0)
        engine.set_router(router)
        engine.classify(_iq(64))
        stats = engine.version_stats()
        assert stats["canary"].batches > 0 and stats["prod"].batches > 0
        total = stats["canary"].batches + stats["prod"].batches
        assert stats["canary"].batches == pytest.approx(0.25 * total,
                                                        abs=1.0)
        # a router naming a missing label degrades to the primary
        engine.set_router(lambda: "gone")
        preds = engine.classify(_iq(8))
        assert preds.shape == (8,)


# ---------------------------------------------------------------------------
# canary monitor
# ---------------------------------------------------------------------------

def _monitor_cfg(**kw):
    base = dict(snr_bins=(0.0, 10.0), frames_per_bin=8, window=3,
                min_rounds=2, promote_after=3, score="agreement")
    base.update(kw)
    return MonitorConfig(**base)


def test_monitor_rolls_back_injected_accuracy_regression(models):
    """Acceptance bar: auto-rollback on a per-SNR accuracy regression."""
    p1, m1, _ = models
    with AsyncAMCServeEngine(p1, CFG, masks=m1, backend="dense",
                             max_batch=8, version_label="prod") as engine:
        engine.bind_version("canary", _permuted_head(p1), backend="dense")
        engine.set_router(canary_router("prod", "canary", 25.0))
        mon = CanaryMonitor(engine, baseline="prod", canary="canary",
                            config=_monitor_cfg())
        decision = mon.run(max_rounds=8)
        assert decision == "rollback"
        assert "regression" in mon.reason
        assert "canary" not in engine.versions()     # canary evicted
        assert engine.active_version == "prod"       # production untouched
        assert engine._router is None                # traffic restored
        # post-rollback the engine still serves
        assert engine.classify(_iq(8)).shape == (8,)


def test_monitor_scores_a_live_counter_version_on_its_logits(models):
    """A version whose step also carries activity counters is scored on
    its logits alone: against the engine's own served predictions, both
    versions of the same weights score 1.0."""
    p1, m1, _ = models
    with AsyncAMCServeEngine(p1, CFG, masks=m1, backend="stream",
                             max_batch=8, version_label="prod") as engine:
        engine.bind_version("canary", p1, masks=m1)
        assert all(v.activity is not None
                   for v in engine.versions().values())

        def source(seed, n, snr):
            iq = _iq(n, seed=seed)
            return iq, engine.classify(iq)

        mon = CanaryMonitor(engine, baseline="prod", canary="canary",
                            config=_monitor_cfg(score="labels"),
                            frame_source=source)
        res = mon.evaluate_round()
    assert all(v == 1.0 for v in res.baseline_acc.values())
    assert all(v == 1.0 for v in res.canary_acc.values())


def test_monitor_rollback_in_labels_mode(models):
    """Same regression, scored against ground-truth labels: the frame
    source labels frames with production's own predictions (a replay
    buffer distilled from the fleet baseline), so the baseline scores
    1.0 and the permuted canary scores ~0."""
    p1, m1, _ = models
    with AsyncAMCServeEngine(p1, CFG, masks=m1, backend="dense",
                             max_batch=8, version_label="prod") as engine:
        engine.bind_version("canary", _permuted_head(p1), backend="dense")
        prod = engine.get_version("prod")

        def source(seed, n, snr):
            rng = np.random.default_rng(seed)
            iq = rng.normal(size=(n,) + FRAME_SHAPE).astype(np.float32)
            import jax.numpy as jnp

            labels = np.asarray(prod.step(jnp.asarray(iq))).argmax(-1)
            return iq, labels

        mon = CanaryMonitor(engine, baseline="prod", canary="canary",
                            config=_monitor_cfg(score="labels"),
                            frame_source=source)
        assert mon.run(max_rounds=8) == "rollback"
        h = mon.history[-1]
        assert all(v == 1.0 for v in h.baseline_acc.values())
        s = mon.summary()
        assert any(s["windowed_canary"][snr]
                   < s["windowed_baseline"][snr] - 0.05
                   for snr in s["windowed_baseline"])


def test_monitor_promotes_clean_canary_and_advances_alias(registry, models):
    p1, m1, _ = models
    registry.publish("amc", p1, CFG, masks=m1, alias="production",
                     assignment="dense")
    # the canary: identical weights, no masks — a distinct registry
    # version whose predictions match the (unmasked) baseline exactly
    p_can = jax.tree_util.tree_map(lambda x: np.asarray(x), p1)
    registry.publish("amc", p_can, CFG, assignment="dense", alias="staging")
    with AsyncAMCServeEngine(p1, CFG, backend="dense",
                             max_batch=8, version_label="amc@1") as engine:
        engine.bind_version("amc@2", p_can, backend="dense")
        engine.set_router(canary_router("amc@1", "amc@2", 25.0))
        mon = CanaryMonitor(engine, baseline="amc@1", canary="amc@2",
                            config=_monitor_cfg(min_rounds=1,
                                                promote_after=2),
                            registry=registry, canary_spec="amc@2")
        assert mon.run(max_rounds=8) == "promote"
        assert engine.active_version == "amc@2"
        assert engine._router is None
    assert registry.resolve("amc") == ("amc", 2)  # production advanced


def test_monitor_rolls_back_latency_regression(models):
    p1, m1, p2 = models
    with AsyncAMCServeEngine(p1, CFG, masks=m1, backend="dense",
                             max_batch=8, version_label="prod") as engine:
        engine.bind_version("canary", p2, backend="dense")
        stats = engine.version_stats()
        stats["prod"].record_latencies([0.001] * 64)
        stats["canary"].record_latencies([0.050] * 64)
        mon = CanaryMonitor(
            engine, baseline="prod", canary="canary",
            config=_monitor_cfg(acc_drop_tol=1.1, min_rounds=1,
                                p99_factor=2.0))
        assert mon.run(max_rounds=4) == "rollback"
        assert "latency" in mon.reason


def test_monitor_fails_fast_on_unbound_labels(models):
    p1, m1, _ = models
    with AsyncAMCServeEngine(p1, CFG, masks=m1, backend="dense",
                             max_batch=8, version_label="prod") as engine:
        with pytest.raises(KeyError):
            CanaryMonitor(engine, baseline="prod", canary="missing")
