"""The ``robust_dp`` step on 4 virtual CPU devices (a (data=4, model=1)
mesh, one worker per device): one and two steps of the program against
the plain reference step (``repro.models.reference``), and the step's
compiled HLO with its phase scopes against the same step compiled with
every scope a null context.

Run by ``tests/test_reference.py`` in a process of its own, with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  Prints one JSON
line of the compared numbers; the tests hold them to their tolerances.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.topology import spaced_malicious
from repro.core.wfagg import WFAggConfig
from repro.distributed.robust_allreduce import RobustAggConfig
from repro.launch.mesh import make_test_mesh
from repro.launch.train import init_sharded_state
from repro.models import model as M
from repro.models import reference as R
from repro.obs.profile import phase
from repro.train import trainer as tr

K, SEQ, STEPS, LR = 4, 24, 2, 1e-3


def small_config():
    """qwen1.5-0.5b cut to 2 layers, d_model 64 and vocab 512, computing
    in float32 (the published widths' bf16 rounding would hide a
    structural difference behind 2 % gradient noise)."""
    return dataclasses.replace(
        get_config("qwen1.5-0.5b"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=512, loss_chunk=8,
        dtype="float32")


def _strip(hlo: str) -> str:
    """Compiled text without metadata (op names, source locations, the
    stack-frame tables that index them) and with every instruction and
    computation renamed in order of appearance: XLA derives some names
    from the scope an op was traced in."""
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    hlo = re.sub(r"\n\nFileNames\n.*?(?=\n\n%|\n\nENTRY)", "", hlo, flags=re.S)
    names: dict = {}
    return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(m.group(0), f"%v{len(names)}"), hlo)


def main() -> int:
    assert jax.device_count() == K, jax.devices()
    cfg = small_config()
    mesh = make_test_mesh(data=K, model=1)
    tc = tr.TrainConfig(
        agg=RobustAggConfig(method="wfagg", layout="stacked", backend="fused",
                            wfagg=WFAggConfig(f=1)),
        lr=LR, warmup=0, total_steps=10, attack="ipm_0.5", n_malicious=1)
    state = init_sharded_state(cfg, tc, mesh, seed=3)
    params0 = jax.device_get(state.params)
    step = tr.build_train_step(cfg, tc, mesh)
    batch = {"tokens": jnp.zeros((K, SEQ), jnp.int32)}
    with mesh:
        phased = step.lower(state, batch).compile().as_text()
        tr.phase = M.phase = lambda name: contextlib.nullcontext()
        bare = tr.build_train_step(cfg, tc, mesh).lower(state, batch).compile().as_text()
        tr.phase = M.phase = phase
    out = {"phases": sorted(set(re.findall(r"phase\.(\w+)", phased))),
           "bare_phases": sorted(set(re.findall(r"phase\.(\w+)", bare))),
           "same_program": _strip(phased) == _strip(bare), "steps": []}
    sc = R.StepConfig(malicious=tuple(np.flatnonzero(spaced_malicious(K, 1))),
                      lr=LR, warmup=0, total_steps=10)
    rst = R.init_state(params0, K, sc)
    for i in range(STEPS):
        tokens = jax.random.randint(jax.random.PRNGKey(100 + i), (K, SEQ), 0,
                                    cfg.vocab_size)
        with mesh:
            state, m = step(state, {"tokens": tokens})
        rst, info = R.robust_dp_step(cfg, sc, rst, [tokens[k:k + 1] for k in range(K)])
        p, mom = jax.device_get((state.params, state.opt_state["m"]))
        change, moment = {}, {}
        for (path, a), b, a0, ma, mb in zip(
                jax.tree_util.tree_leaves_with_path(p), jax.tree.leaves(rst.params),
                jax.tree.leaves(params0), jax.tree.leaves(mom), jax.tree.leaves(rst.m)):
            name = jax.tree_util.keystr(path)
            da = np.asarray(a, np.float64) - np.asarray(a0, np.float64)
            db = np.asarray(b, np.float64) - np.asarray(a0, np.float64)
            change[name] = float(np.linalg.norm(da - db) / np.linalg.norm(db))
            mb = np.asarray(mb, np.float64)
            moment[name] = float(np.linalg.norm(np.asarray(ma, np.float64) - mb)
                                 / np.linalg.norm(mb))
        out["steps"].append({
            "losses": np.asarray(m["losses"]).tolist(),
            "ref_losses": np.asarray(info["losses"]).tolist(),
            "weights": np.asarray(m["weights"]).tolist(),
            "ref_weights": np.asarray(info["weights"]).tolist(),
            "n_accepted": int(m["n_accepted"]),
            "param_change": change,
            "adam_m": moment,
        })
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
