#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's
root: the cell names a configuration (``bench/configs/<config>.json``)
and a traffic mix (``bench/traffic/<traffic>.json``), and each metric is
read by ``bench/metrics/<metric>.py``, whose ``read(run)`` returns a
number or None (nothing to read: the metric is left out).  With
``--trace 0`` the cell's end-to-end metrics are reported, with
``--trace 1`` its per-layer metrics, from a run traced by the JAX
profiler.

A run: check the device (a TPU, as many chips as the cell asks for,
listed in ``bench/peaks.json``), make the weights and masks from the seed
on the device, the frame pool from the seed on the host, build the
serving engine (which compiles and warms every bucket), then drive
``AsyncAMCServeEngine.submit`` with the traffic for ``--seconds``.  After
the window every sent request must resolve; the peak device memory is
read, the engine is closed, and the answers are held to the plain
reference (``bench/check.py``).  The last line of standard output is one
JSON object; the numbers compared, with their limits, close it (key
``check``) and are also the last lines of standard error.

``--rehearse`` runs on the CPU (``JAX_PLATFORMS=cpu``; kernels in
interpret mode) for checking the harness: it prints which metrics were
read, never their values.

The ``run`` object a metric reader gets has: ``cfg``, ``traffic``,
``cell``, ``chips``, ``seconds``, ``setup_s``, ``log`` (the load
generator's :class:`loadgen.Log`), ``t0``/``t1`` (window, seconds on
``loadgen.CLOCK``), ``counters`` (engine registry deltas over the window:
``requests``, ``padded``, ``batches``), ``request_traces`` (sampled engine
request timelines: lists of ``(event, t)``), ``trace`` (the
:func:`trace_reduce.reduce_trace` result, or None), ``work_per_frame``,
``weight_bytes``, ``frame_bytes`` and ``peak`` (``ops`` and
``bytes_per_s`` of the configuration's peak on this device).
"""
from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

import check  # noqa: E402
import cost  # noqa: E402
import loadgen  # noqa: E402

CACHE_DIR = ROOT / ".bench_cache" / "jax"
OUT_DIR = ROOT / ".bench_cache" / "traces"
REQUEST_TRACE_EVERY = 8      # engine request timelines: every 8th request
ENGINE_NAME = "bench"


class Refused(SystemExit):
    """No result: the machine or the files cannot run this cell."""


def process_age_s() -> float:
    """Seconds since this process started (from ``/proc``)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_IMPORT


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def entry(items: list, name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise Refused(f"bench: no {what} named {name!r} in BENCHMARK.json")


def metrics_for(spec: dict, cell: str, key: str) -> list:
    return [m for m in spec[key] if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def devices_or_refuse(chips: int, rehearse: bool):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        raise Refused(f"bench: no TPU (JAX found {platform}); a cell runs "
                      "on the chip only")
    if len(devices) != chips:
        raise Refused(f"bench: the cell asks for {chips} chip(s), JAX sees "
                      f"{len(devices)}")
    return devices


def peak_for(cfg: dict, kind: str, rehearse: bool) -> dict:
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        if rehearse:
            return {"ops": float("nan"), "bytes_per_s": float("nan")}
        raise Refused(f"bench: device kind {kind!r} is not in peaks.json")
    return {"ops": float(peaks[kind][cfg["peak"]]),
            "bytes_per_s": float(peaks[kind]["hbm_bytes_per_s"])}


def _int_tuples(specs) -> tuple:
    return tuple(tuple(int(v) for v in s) for s in specs)


# the network keys the program has always taken, cast as it takes them
CHAIN_CASTS = {"conv_specs": _int_tuples, "pool": int, "fc_specs": _int_tuples,
               "input_width": int, "timesteps": int, "n_classes": int,
               "readout": str, "lif_alpha": float, "lif_theta": float,
               "lif_v_th": float}


def snn_config(net: dict):
    """The program's ``SNNConfig`` for a network; Refused where it has none.

    Keys beyond the chain's go as nested tuples in the field order of
    ``bench/network.py`` (``stages`` as ``((channels, proj_kw, units,
    kw, pool), ...)``).
    """
    import dataclasses

    import network
    from repro.models.snn import SNNConfig

    kwargs = {k: CHAIN_CASTS[k](v) if k in CHAIN_CASTS
              else network.program_value(k, v) for k, v in net.items()}
    try:
        return SNNConfig(**kwargs)
    except TypeError as e:
        fields = {f.name for f in dataclasses.fields(SNNConfig)}
        lacking = ", ".join(sorted(set(kwargs) - fields)) or "?"
        raise Refused(f"bench: the program cannot run this network: its "
                      f"SNNConfig has no field {lacking} ({e})") from None


def make_pool(seed: int, cfg: dict, traffic: dict):
    """The traffic's frame pool and its labels, from the seed (host)."""
    import frames

    spec = traffic["frames"]
    lo, hi, step = spec["snr_db"]
    iq, labels, _ = frames.frame_pool(seed, int(spec["pool"]),
                                      np.arange(lo, hi + step / 2, step),
                                      frame_len=int(cfg["network"]["input_width"]),
                                      class_set=spec.get("classes",
                                                         "radioml2016"))
    return iq, labels


def make_weights(seed: int, cfg: dict, pool, labels):
    """The network from the seed, its readout fitted on the pool's head."""
    import weights as weights_mod

    n = int(cfg["readout_fit_frames"])
    return weights_mod.make_weights(seed, cfg, pool[:n], labels[:n])


def build_engine(cfg: dict, weights, chips: int):
    """The system under test, built from the benchmark's own weights."""
    from repro.core.lif import LIFParams
    from repro.plan import PlanCache, set_default_cache
    from repro.serve import AsyncAMCServeEngine

    set_default_cache(PlanCache(disk_dir=""))   # nothing written to $HOME
    params = {g: [{"w": l["w"], "lif": LIFParams(l["alpha_logit"], l["theta"],
                                                 l["v_th"])}
                  for l in weights[g]] for g in ("conv", "fc")}
    masks = {g: [l["mask"] for l in weights[g]] for g in ("conv", "fc")}
    return AsyncAMCServeEngine(
        params, snn_config(cfg["network"]), masks=masks,
        backend=cfg["backend"], quant_bits=int(cfg.get("quant_bits") or 16),
        max_batch=int(cfg["max_batch_per_chip"]) * chips,
        max_delay_ms=float(cfg["max_delay_ms"]), warmup=True,
        name=ENGINE_NAME)


def engine_counters(backend: str) -> dict:
    from repro.obs.metrics import default_registry

    reg = default_registry()
    return {
        "requests": reg.value("repro_serve_requests_total", engine=ENGINE_NAME),
        "padded": reg.value("repro_serve_padded_frames_total",
                            engine=ENGINE_NAME),
        "batches": reg.value("repro_serve_batches_total", engine=ENGINE_NAME,
                             backend=backend),
    }


def memory_peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


def engine_phases(request_traces, to_ns) -> list:
    """Host intervals of the engine's worker, labelled, from request traces.

    Per served batch: ``engine.prepare`` (batch formed -> step called),
    ``engine.step`` (the jitted step, transfers and result fetch),
    ``engine.resolve`` (predictions, stats and futures); between batches
    ``engine.gather`` (the worker back in the batcher).
    """
    batches = {}
    for events in request_traces:
        ev = dict(events)
        if not {"batch-form", "jit-step-start", "jit-step-end"} <= ev.keys():
            continue
        key = ev["jit-step-start"]
        b = batches.setdefault(key, [ev["batch-form"], key,
                                     ev["jit-step-end"], ev["jit-step-end"]])
        b[3] = max(b[3], ev.get("complete", b[3]))
    out, prev_end = [], None
    for form, s0, s1, done in sorted(batches.values(), key=lambda b: b[1]):
        if prev_end is not None and form > prev_end:
            out.append((to_ns(prev_end), to_ns(form), "engine.gather"))
        out += [(to_ns(form), to_ns(s0), "engine.prepare"),
                (to_ns(s0), to_ns(s1), "engine.step"),
                (to_ns(s1), to_ns(done), "engine.resolve")]
        prev_end = done
    return out


def run_cell(args) -> tuple:
    """One run: (the result line's object, what the comparison also saw)."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cell = entry(spec["workloads"], args.workload, "workload")
    cfg = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    chips = int(cell["chips"])
    key = "per_layer" if args.trace else "end_to_end"
    wanted = metrics_for(spec, cell["name"], key)
    readers = {m["name"]: load_reader(m["name"]) for m in wanted}

    # libtpu would otherwise write its logs to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = devices_or_refuse(chips, args.rehearse)
    kind = devices[0].device_kind
    peak = peak_for(cfg, kind, args.rehearse)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))

    import weights as weights_mod

    pool, labels = make_pool(args.seed, cfg, traffic)
    weights = make_weights(args.seed, cfg, pool, labels)
    engine = build_engine(cfg, weights, chips)
    priority = traffic.get("priority", "realtime")
    deadline_ms = traffic.get("deadline_ms")

    def submit(k):
        return engine.submit(pool[k], priority=priority,
                             deadline_ms=deadline_ms)

    # every run opens its window with the collector in the same state
    gc.collect()
    state: dict = {}
    trace_dir = None
    if args.trace:
        from repro.obs.trace import enable_tracing

        tracer = enable_tracing(sample_every=REQUEST_TRACE_EVERY,
                                capacity=1 << 18)
        trace_dir = Path(args.trace_dir or OUT_DIR / f"run-{os.getpid()}")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)

    def on_start(t0):
        state["setup_s"] = process_age_s()
        state["before"] = engine_counters(engine.backend)
        if args.trace:
            state["annotation"] = jax.profiler.TraceAnnotation("bench.window")
            state["annotation"].__enter__()
        state["p0"] = loadgen.CLOCK()

    def on_stop(t1):
        state["p1"] = loadgen.CLOCK()
        if args.trace:
            state["annotation"].__exit__(None, None, None)
        state["after"] = engine_counters(engine.backend)

    span = jax.profiler.TraceAnnotation if args.trace else None
    try:
        log, t0, t1 = loadgen.run(submit, pool.shape[0], traffic, args.seconds,
                                  args.seed, span=span, on_start=on_start,
                                  on_stop=on_stop)
        mem = memory_peak_bytes(devices)
    finally:
        engine.close()
        if args.trace:
            jax.profiler.stop_trace()
    if log.overflow:
        raise RuntimeError("the closed loop outran its log; raise "
                           "loadgen.MAX_RATE_FPS")

    run = types.SimpleNamespace(
        cfg=cfg, traffic=traffic, cell=cell, chips=chips,
        seconds=args.seconds, setup_s=state["setup_s"], log=log, t0=t0, t1=t1,
        counters={k: state["after"][k] - state["before"][k]
                  for k in state["before"]},
        request_traces=[], trace=None, peak=peak,
        work_per_frame=cost.work_per_frame(
            cfg["network"], cost.nonzero_counts(weights)),
        weight_bytes=cost.weight_bytes(cfg["network"]),
        frame_bytes=cost.frame_bytes(cfg["network"],
                                     int(cfg.get("step_counters", 0))))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem}
    if args.trace:
        from repro.obs.trace import disable_tracing

        run.request_traces = [[(e.name, e.t) for e in tr.events]
                              for tr in tracer.completed()]
        disable_tracing()
        if not args.rehearse:
            run.trace = reduce_profile(trace_dir, state, run.request_traces)
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
        if args.trace_dir is None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    n = log.n
    missing = int(n - np.count_nonzero(log.ok[:n]))
    answered = log.ok[:n]
    host_weights = weights_mod.to_host(weights)
    del weights, engine
    numbers, info = check.compare(cfg, args.seed, pool, host_weights,
                                  log.frame[:n][answered],
                                  log.answer[:n][answered], missing)
    values = {}
    for m in wanted:
        v = readers[m["name"]](run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": check.passed(numbers), "attempted": int(n),
              "failed": missing, "metrics": values, "device": device}
    if run.trace is not None:
        result["breakdown"] = {"device_ops": run.trace["top_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["check"] = numbers
    return result, info


def reduce_profile(trace_dir: Path, state: dict, request_traces) -> dict:
    """The window's device time, with idle gaps labelled by engine phase."""
    import trace_reduce

    pd = trace_reduce.load(trace_reduce.find_xplane(str(trace_dir)))
    window = trace_reduce.find_annotation(pd, "bench.window")
    if window is None:
        raise RuntimeError("the trace lost its bench.window annotation")
    lo, hi = window
    # the annotation opened just before p0 and closed just after p1 on
    # the host clock: map perf_counter seconds onto the profiler's clock
    scale = (hi - lo) / ((state["p1"] - state["p0"]) * 1e9)

    def to_ns(t):
        return lo + (t - state["p0"]) * 1e9 * scale

    return trace_reduce.reduce_trace(pd, lo, hi,
                                     engine_phases(request_traces, to_ns))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU to check the harness; prints no "
                         "metric values")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace in this directory")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    try:
        result, info = run_cell(args)
    except Refused as e:
        print(e.code, file=sys.stderr)
        return 2
    numbers = result["check"]
    print(f"bench: {info}", file=sys.stderr)
    for name, v in numbers.items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    if args.rehearse:
        result = {"rehearsal": True, "correct": result["correct"],
                  "attempted": result["attempted"],
                  "failed": result["failed"],
                  "metrics_read": sorted(result["metrics"]),
                  "device": {k: result["device"][k]
                             for k in ("platform", "kind", "count")},
                  "check": numbers}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
