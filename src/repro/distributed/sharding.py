"""Per-architecture PartitionSpecs (params, caches, activations).

Specs are derived from the param *name* (leaf key) + rank, applied to the
TRAILING dims (leading stacked-layer/group dims fill with None).  Two
modes:
  tp_only   params sharded over 'model' only (replicated across data) —
            required by the mode-B shard_map trainer.
  fsdp      additionally shard the largest remaining big dim over 'data'
            (+ 'pod' folded into 'data' for multi-pod) — serving / the
            GSPMD-mean trainer for >=100B params.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig


# trailing-dim specs keyed by leaf name (without the 'model' axis resolved)
_TRAILING: Dict[str, Tuple[Optional[str], ...]] = {
    # attention
    "wq": (None, "model"), "wk": (None, "model"), "wv": (None, "model"),
    "wo": ("model", None),
    "bq": ("model",), "bk": ("model",), "bv": ("model",),
    # MLA
    "w_dkv": (None, None), "w_kr": (None, None),
    "w_uk": (None, "model"), "w_uv": (None, "model"), "kv_norm": (None,),
    # embeddings
    "embed": ("model", None), "unembed": (None, "model"),
    # router / norms / scalars
    "router": (None, None), "scale": (None,), "bias": (None,),
    "gnorm": ("model",), "dt_bias": ("model",), "D": ("model",),
    # mamba
    "in_proj": (None, "model"), "out_proj": ("model", None),
    "conv_w": (None, "model"), "conv_b": ("model",),
    "x_proj": ("model", None), "dt_proj": (None, "model"),
    "A_log": ("model", None), "bc_proj": ("model", None),
    # projector (vlm) / encoder input
    "w1": (None, "model"), "w2": ("model", None), "enc_in_proj": (None, None),
}

# dense-MLP vs MoE expert tensors share names; disambiguate by rank below.
_MLP2 = {"w_gate": (None, "model"), "w_up": (None, "model"), "w_down": ("model", None)}
_MOE3 = {"w_gate": ("model", None, None), "w_up": ("model", None, None),
         "w_down": ("model", None, None)}

_FSDP_MIN_DIM = 1024  # only shard dims at least this large over 'data'


def _axis_size(mesh, axis) -> int:
    if axis is None or mesh is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= int(mesh.shape[a])
        return n
    return int(mesh.shape[axis])


def prune_spec(spec: P, shape: Tuple[int, ...], mesh) -> P:
    """Drop spec entries whose mesh-axis size does not divide the dim.

    Keeps dry-runs honest across all (arch x shape) cells: global_batch=1
    cannot shard over data=16, kv_heads=4 cannot shard over model=16 (the
    KV cache is then replicated across TP shards, the standard GQA
    fallback).
    """
    if mesh is None:
        return spec
    out = []
    for i, ax in enumerate(tuple(spec) + (None,) * (len(shape) - len(spec))):
        out.append(ax if shape[i] % _axis_size(mesh, ax) == 0 else None)
    return P(*out)


def _leaf_name(path) -> str:
    for p in reversed(path):
        k = getattr(p, "key", None)
        if isinstance(k, str):
            return k
    return ""


def _spec_for(name: str, shape: Tuple[int, ...], n_stack: int) -> Tuple[Optional[str], ...]:
    """n_stack = how many leading dims are layer/group stacking."""
    trailing_rank = len(shape) - n_stack
    if name in ("w_gate", "w_up", "w_down"):
        tr = _MOE3[name] if trailing_rank == 3 else _MLP2[name]
    elif name in _TRAILING:
        tr = _TRAILING[name]
        tr = tr[-trailing_rank:] if trailing_rank <= len(tr) else (None,) * (trailing_rank - len(tr)) + tr
    else:
        tr = (None,) * trailing_rank
    return (None,) * n_stack + tuple(tr)


def _count_stack_dims(name: str, shape: Tuple[int, ...],
                      cfg: Optional[ArchConfig] = None) -> int:
    """Infer leading stacked dims: total rank minus the natural rank."""
    if name in ("w_gate", "w_up", "w_down"):
        # dense (2) or expert (3): a rank-4 w_gate is stacked expert (1+3);
        # rank-3 is ambiguous (stacked dense (L,d,ff) vs unstacked expert
        # (E,d,ff)) — the config disambiguates: dense archs have no expert
        # tensors, and expert tensors lead with exactly n_experts.
        if len(shape) == 4:
            return 1
        if len(shape) == 3:
            if cfg is not None and cfg.n_experts and shape[0] == cfg.n_experts:
                return 0  # unstacked expert tensor
            return 1      # stacked dense MLP
        return 0
    base = {"scale": 1, "bias": 1, "bq": 1, "bk": 1, "bv": 1, "gnorm": 1,
            "dt_bias": 1, "D": 1, "conv_b": 1, "kv_norm": 1}.get(name, 2)
    return max(0, len(shape) - base)


def param_specs(cfg: ArchConfig, params_shape: Any, fsdp: bool = False,
                data_axes: Tuple[str, ...] = ("data",), mesh=None) -> Any:
    """Build a PartitionSpec pytree mirroring params."""
    data_axis = data_axes if len(data_axes) > 1 else data_axes[0]

    def one(path, leaf):
        name = _leaf_name(path)
        shape = tuple(leaf.shape)
        n_stack = _count_stack_dims(name, shape, cfg)
        spec = list(_spec_for(name, shape, n_stack))
        if fsdp:
            # put 'data' on the largest unsharded trailing dim
            best, best_size = -1, _FSDP_MIN_DIM - 1
            for i in range(n_stack, len(shape)):
                if spec[i] is None and shape[i] > best_size:
                    best, best_size = i, shape[i]
            if best >= 0:
                spec[best] = data_axis
        return prune_spec(P(*spec), shape, mesh)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def cache_specs(cfg: ArchConfig, cache_shape: Any, data_axes: Tuple[str, ...] = ("data",), mesh=None) -> Any:
    """Decode-cache specs: batch over data, heads/inner over model."""
    data_axis = data_axes if len(data_axes) > 1 else data_axes[0]

    def one(path, leaf):
        name = _leaf_name(path)
        shape = tuple(leaf.shape)
        if name == "idx" or leaf.ndim == 0:
            return P()
        if name in ("k", "v"):        # (..., B, Hkv, cap, hd)
            lead = (None,) * (len(shape) - 4)
            mdl = "model" if cfg.n_kv_heads > 1 else None
            return P(*lead, data_axis, mdl, None, None)
        if name in ("ckv", "krope"):  # (..., B, cap, r)
            lead = (None,) * (len(shape) - 3)
            return P(*lead, data_axis, None, None)
        if name == "conv":            # (..., B, kw-1, di)
            lead = (None,) * (len(shape) - 3)
            return P(*lead, data_axis, None, "model")
        if name == "h":
            if cfg.ssm_variant == "mamba2":   # (..., B, Hm, p, n)
                lead = (None,) * (len(shape) - 4)
                return P(*lead, data_axis, "model", None, None)
            lead = (None,) * (len(shape) - 3)  # (..., B, di, n)
            return P(*lead, data_axis, "model", None)
        if name == "enc_out":         # (B, S_enc, d)
            return P(data_axis, None, None)
        return P(*(None,) * len(shape))

    def pruned(path, leaf):
        return prune_spec(one(path, leaf), tuple(leaf.shape), mesh)

    return jax.tree_util.tree_map_with_path(pruned, cache_shape)


def batch_specs(batch_shape: Any, data_axes: Tuple[str, ...] = ("data",), mesh=None) -> Any:
    data_axis = data_axes if len(data_axes) > 1 else data_axes[0]

    def one(path, leaf):
        return prune_spec(P(data_axis, *(None,) * (leaf.ndim - 1)),
                          tuple(leaf.shape), mesh)

    return jax.tree_util.tree_map_with_path(one, batch_shape)


def activation_rules(mode: str, multi_pod: bool) -> Dict[str, Any]:
    """Logical-axis rules for repro.distributed.logical.use_sharding."""
    batch_axes = ("pod", "data") if multi_pod else "data"
    rules = {
        "heads": "model", "kv_heads": "model", "ff": "model",
        "vocab": "model", "expert": "model", "inner": "model",
        "embed": None, "seq": None,
    }
    if mode == "robust_dp":
        rules["batch"] = None          # batch axis is manual-local per node
    else:
        rules["batch"] = batch_axes
    return rules
