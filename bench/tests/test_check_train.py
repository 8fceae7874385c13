"""Output check of `train-qwen1.5-0.5b-k4` on 4 virtual CPU devices at a
small size: the sound run is correct; the control and every planted
fault of the timed path are not.  One process drives every case
(``bench/tests/_check_train_main.py``)."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests.helpers import ROOT

CASES = ("state_unchanged", "half_batch", "no_exchange", "altered_answer")


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4").strip())
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "tests",
                                                     "_check_train_main.py")],
                       capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(results):
    assert results["sound"]["correct"] is True, results["sound"]["checks"]


def test_control_fails_a_limit(results):
    assert results["control"]["correct"] is False, results["control"]["checks"]


@pytest.mark.parametrize("fault", CASES)
def test_broken_timed_path_is_not_correct(results, fault):
    assert results[fault]["correct"] is False, (fault, results[fault]["checks"])
