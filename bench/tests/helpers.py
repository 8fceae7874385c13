"""Shared helpers of the benchmark's tests (CPU only, small sizes)."""
from __future__ import annotations

import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_json(rel: str):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def lenet_cfg():
    return load_json("bench/configs/lenet-mnist-paper.json")


@functools.lru_cache(maxsize=None)
def lenet():
    from bench.run import load_module
    return load_module(os.path.join(ROOT, "bench", "configs", "lenet-mnist-paper.py"),
                       "bench_model_lenet_mnist_paper")


def small_mix(traffic: str, **kw):
    """A cell's own mix, cut to a size a CPU test run can hold."""
    mix = load_json(f"bench/traffic/{traffic}.json")
    if mix["topology"] == "ring":
        mix.update(nodes=6, degree=4, width=4, n_malicious=1,
                   schedule_rounds=3, rounds_per_chunk=3, check_chunks=1)
    else:
        mix.update(nodes=12, degree=4, width=10, n_malicious=2,
                   schedule_rounds=6, rounds_per_chunk=2, check_chunks=2)
    mix.update(kw)
    return mix
