"""The stacked robust all-reduce's round, sliced over the parameters on
four devices, against the one launch on one device
(``tests/_sliced_round_main.py``, in a process of its own with four
virtual CPU devices)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sliced_rounds():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4").strip(),
               PYTHONPATH=os.path.join(REPO, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    p = subprocess.run([sys.executable, os.path.join(REPO, "tests", "_sliced_round_main.py")],
                       capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("history", ["history", "no_history"])
@pytest.mark.parametrize("method", ["wfagg", "alt_wfagg"])
def test_sliced_round_matches_one_launch(sliced_rounds, method, history):
    """Four steps, one IPM worker, with the temporal filter voting from
    the third or without WFAgg-T state: the masks and weights equal the
    one launch's; the aggregate and the WFAgg-T history match to
    float32 rounding (only the accumulators' summation order differs;
    readings <= 4e-8 and <= 4e-7); the new ``prev`` is the candidates,
    bit for bit."""
    r = sliced_rounds[f"{method}.{history}"]
    assert r["sliced_route"] and r["one_route"]
    for s in r["steps"]:
        for k in ("mask_d", "mask_c", "mask_t", "weights"):
            assert s[k] == s["one_" + k], (k, s)
        assert s["out"] <= 1e-6, s
        if history == "history":
            assert s["prev"] == 0.0, s
            assert s["hist_s"] <= 1e-5 and s["hist_b"] <= 1e-5, s
            assert s["count"][0] == s["count"][1]
    # the test means something: the IPM worker is rejected and, with
    # history, the temporal filter votes in the last steps
    assert all(s["weights"][2] == 0.0 for s in r["steps"])
    assert any(any(s["mask_t"]) for s in r["steps"]) == (history == "history")


def test_sliced_tile_is_wide_at_the_trainer_slice():
    """The trainer's per-chip slice, P / 4 = 64,596,224 = 63,083 blocks
    of 1,024 lanes (199 x 317), has no divisor under the tile budget's
    51 blocks: unpadded, ``round_tile_width`` would give T = 1,024.
    ``round_padded_width`` pads it by under one block a tile, so the
    sliced launch runs tiles of at least 16,384 lanes."""
    from repro.kernels.robust_stats.kernel import (round_padded_width,
                                                   round_tile_width)

    k, d = 4, 258_384_896 // 4
    assert round_tile_width(k, d, True) == 1024
    padded = round_padded_width(k, d, True)
    t = round_tile_width(k, padded, True)
    assert t >= 16_384 and padded % t == 0
    assert 0 <= padded - d < (padded // t) * 1024
    # a width the rule already tiles wide is left as it is
    assert round_padded_width(k, 51 * 1024 * 7, True) == 51 * 1024 * 7
