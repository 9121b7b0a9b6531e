"""The four-worker training cell on four CPU devices at a small size: a
sound run is correct, and the control of a lower precision and each
fault the cell can have make ``correct`` false.  The harness's look for
a chip is skipped; each case runs in a process of its own, which is
given four host devices before JAX starts."""
import json
import os
import subprocess
import sys

import pytest

from bench import common
from bench.tests import faults

CELL = "train4-qwen3-0.6b-mean-m4"

SCRIPT = r"""
import json, sys
import jax
import jax.numpy as jnp
from bench import common, run
from bench.tests import faults
from bench.tests.conftest import tiny_config, tiny_traffic

spec = common.benchmark()
cell = common.cell(sys.argv[1], spec)
case = sys.argv[2]
cfg, tr = tiny_config(cell["config"]), tiny_traffic(cell["traffic"])
if case == "control":
    win = common.window("train")
    r = win.Run({"config": cfg, "traffic": tr, "devices": jax.devices()})
    r.start(2**31 + 19)
    want = r.reference()
    low = r.reference(precision=jax.lax.Precision.DEFAULT, dtype=jnp.bfloat16)
    numbers = win.compare(low, want)
    correct = all(v <= tr["limits"][k] for k, v in numbers.items()
                  if k in tr["limits"])
    print(json.dumps({"correct": correct, "checks": numbers}))
    sys.exit(0)
if case != "sound":
    faults.MULTI_WORKER_FAULTS[case](setattr)
out = run.run_cell(spec, cell, cfg, tr, 2**31 + 17, 0.2, False, jax.devices())
print(json.dumps({"correct": out["correct"], "checks": out["checks"],
                  "metrics": sorted(out["metrics"]), "m": tr["m"],
                  "devices": len(jax.devices())}))
"""


def run_case(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(common.ROOT), str(common.ROOT / "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, CELL, case], env=env,
                          cwd=common.ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_four_worker_run_is_correct():
    out = run_case("sound")
    assert out["m"] == out["devices"] == 4
    assert out["correct"] is True, out["checks"]
    assert out["metrics"] == ["setup_s", "train4_step_p90_ms", "train4_tokens_per_s"]


@pytest.mark.parametrize("case", ["control", *sorted(faults.MULTI_WORKER_FAULTS)])
def test_four_worker_faults_and_control_are_caught(case):
    out = run_case(case)
    assert out["correct"] is False, out["checks"]
