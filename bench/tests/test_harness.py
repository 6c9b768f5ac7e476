"""The harness end to end on the CPU, in a temporary copy of the benchmark.

* A cell, a configuration, a traffic mix and a metric are added as files
  and ``BENCHMARK.json`` entries only, and the harness runs them.
* With the timed path broken underneath (the engine's jitted step), each
  fault a served cell can have makes ``correct`` come out false: an answer
  altered where it is produced, half of a batch left out, and the
  exchange between chips left out.
* With no TPU and no ``--rehearse`` the harness prints no result, and it
  prints none in a checkout that holds only the benchmark.
* A network of residual stages runs, correct, where the program's
  ``SNNConfig`` has the fields of one (the ``stage_fields`` fixture), and
  the broken timed path makes it not correct; where it has not, the run
  is refused (rc 2) with the fields it lacks named, and the broken-path
  cases are skipped with that reason.

Runs the harness with ``--rehearse`` at test sizes (``data/tiny-*.json``).
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
SEED = 2 ** 31 + 77

NEW_CELLS = [
    {"name": "tiny-f32.closed", "config": "tiny-f32", "traffic": "tiny-closed",
     "chips": 1, "why": "test size, fused kernel in interpret mode"},
    {"name": "tiny-int8.closed", "config": "tiny-int8",
     "traffic": "tiny-closed", "chips": 1, "why": "test size, integer twin"},
    {"name": "tiny-f32.closed.x4", "config": "tiny-f32",
     "traffic": "tiny-closed.x4", "chips": 4, "why": "test size, 4 devices"},
    {"name": "tiny-resnet.closed", "config": "tiny-resnet",
     "traffic": "tiny-closed", "chips": 1,
     "why": "test size, residual stages"},
]
STAGE_CELL = "tiny-resnet.closed"
NEW_METRIC = {"name": "answers_per_s", "unit": "1/s", "better": "higher",
              "bound": 0.25, "source": "host_clock",
              "workloads": ["tiny-int8.closed"]}

# The driver of one run: optionally breaks the engine's jitted step, then
# runs the harness in this process.
DRIVER = r"""
import sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
fault = sys.argv[3]
import jax.numpy as jnp
from repro.serve.engine import AsyncAMCServeEngine

def broken(logits):
    b = logits.shape[0]
    if fault == "answer":        # one answer per batch altered
        top = jnp.argmax(logits[0])
        bump = jnp.zeros(logits.shape[1]).at[(top + 1) % logits.shape[1]]
        return logits.at[0].add(bump.set(1e4))
    if fault == "half-batch":    # the second half of the rows left out
        half = logits[: max(1, b // 2)]
        return jnp.concatenate([half] * (b // half.shape[0])
                               + [half[: b % half.shape[0]]])
    if fault == "exchange":      # every chip's rows replaced by the first's
        quarter = logits[: b // 4]
        return jnp.concatenate([quarter] * 4)
    return logits

if fault != "none":
    wrap = AsyncAMCServeEngine._wrap_batch_fn
    def wrapped(self, batch_fn, int_encode=False):
        step = wrap(self, batch_fn, int_encode)
        def run(iq):
            out = step(iq)
            if isinstance(out, tuple):
                return (broken(out[0]),) + tuple(out[1:])
            return broken(out)
        return run
    AsyncAMCServeEngine._wrap_batch_fn = wrapped

import run
sys.exit(run.main(sys.argv[4:]))
"""


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark with cells, files and a metric added."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in ("tiny-f32.json", "tiny-int8.json", "tiny-resnet.json"):
        shutil.copy(os.path.join(DATA, name), root / "bench" / "configs")
    shutil.copy(os.path.join(DATA, "tiny-closed.json"),
                root / "bench" / "traffic")
    with open(os.path.join(DATA, "tiny-closed.json")) as f:
        x4 = dict(json.load(f), outstanding=64)
    with open(root / "bench" / "traffic" / "tiny-closed.x4.json", "w") as f:
        json.dump(x4, f)
    shutil.copy(os.path.join(DATA, "answers_per_s.py"),
                root / "bench" / "metrics")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"] += NEW_CELLS
    spec["end_to_end"].append(NEW_METRIC)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return root


def _run(checkout, cell, fault="none", rehearse=True, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={devices}")
    args = ["--workload", cell, "--seed", str(SEED), "--seconds", "1",
            "--trace", "0"] + (["--rehearse"] if rehearse else [])
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, str(checkout / "bench"),
         os.path.join(ROOT, "src"), fault] + args,
        env=env, capture_output=True, text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    return proc.returncode, (json.loads(lines[-1]) if lines else None), proc


def test_added_cell_and_metric_run_through_the_harness(checkout):
    rc, out, proc = _run(checkout, "tiny-int8.closed")
    assert rc == 0, proc.stderr[-2000:]
    assert out["rehearsal"] and out["correct"], proc.stderr[-2000:]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "answers_per_s" in out["metrics_read"]
    assert "setup_s" in out["metrics_read"]
    assert "value" not in json.dumps(out["metrics_read"])
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell,fault,devices", [
    ("tiny-f32.closed", "none", 1),
    ("tiny-f32.closed", "answer", 1),
    ("tiny-f32.closed", "half-batch", 1),
    ("tiny-int8.closed", "answer", 1),
    ("tiny-int8.closed", "half-batch", 1),
    ("tiny-f32.closed.x4", "none", 4),
    ("tiny-f32.closed.x4", "exchange", 4),
    (STAGE_CELL, "answer", 1),
    (STAGE_CELL, "half-batch", 1),
])
def test_a_broken_timed_path_is_not_correct(checkout, cell, fault, devices,
                                            stage_fields):
    if cell == STAGE_CELL and not stage_fields:
        pytest.skip("the program's SNNConfig has no field input_channels, "
                    "stages: it cannot run a stage network yet")
    rc, out, proc = _run(checkout, cell, fault, devices=devices)
    assert rc == 0, proc.stderr[-2000:]
    assert out["correct"] is (fault == "none"), proc.stderr[-2000:]
    if fault != "none":
        assert out["check"]["wrong_share"]["value"] > \
            out["check"]["wrong_share"]["limit"]


def test_a_stage_network_runs_or_is_refused_naming_what_it_lacks(
        checkout, stage_fields):
    rc, out, proc = _run(checkout, STAGE_CELL)
    if stage_fields:
        assert rc == 0, proc.stderr[-2000:]
        assert out["correct"], proc.stderr[-2000:]
        assert out["failed"] == 0 and out["attempted"] > 0
        return
    assert rc == 2 and out is None, proc.stderr[-2000:]
    assert "Traceback" not in proc.stderr
    assert "SNNConfig has no field input_channels, stages" in proc.stderr


def test_no_tpu_no_result(checkout):
    rc, out, proc = _run(checkout, "tiny-int8.closed", rehearse=False)
    assert rc != 0 and out is None
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path, checkout):
    shutil.copytree(checkout / "bench", tmp_path / "bench")
    shutil.copy(checkout / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         "tiny-int8.closed", "--seed", "1", "--seconds", "1", "--trace", "0",
         "--rehearse"], env=env, capture_output=True, text=True,
        timeout=600, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
