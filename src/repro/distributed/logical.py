"""Logical-axis sharding annotations (flax-style rules, dependency-free).

Model code annotates activations with *logical* axis names:

    h = shard(h, "batch", "seq", "embed")

The launcher installs a mesh + a logical->mesh-axis rule table; outside a
`use_sharding` context the annotations are no-ops, so the same model code
runs single-device (tests, smoke) and multi-pod (dry-run, production).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxis = Union[None, str, Tuple[str, ...]]


_state = threading.local()


def _ctx() -> Optional[Tuple[Mesh, Dict[str, MeshAxis]]]:
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: Dict[str, MeshAxis]):
    """Install (mesh, logical->mesh rules) for the enclosed region."""
    prev = _ctx()
    _state.ctx = (mesh, dict(rules))
    try:
        with mesh:
            yield
    finally:
        _state.ctx = prev


def logical_spec(*axes: Optional[str]) -> P:
    ctx = _ctx()
    rules = ctx[1] if ctx else {}
    return P(*[rules.get(a) if a else None for a in axes])


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain ``x`` to the sharding implied by logical ``axes``.

    No-op outside a `use_sharding` context.  Extra trailing dims (beyond
    the names given) are unconstrained (replicated spec position).
    """
    ctx = _ctx()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = [rules.get(a) if a else None for a in axes[: x.ndim]]
    spec += [None] * (x.ndim - len(spec))
    # Use the CURRENT abstract mesh so axis types (Manual inside shard_map
    # regions vs Auto outside) match the trace context — a concrete-mesh
    # NamedSharding would poison downstream avals with Auto-typed axes and
    # break AD zero-instantiation inside partial-manual shard_map.
    cur = jax.sharding.get_abstract_mesh()
    use = cur if (cur is not None and not cur.empty) else mesh
    return jax.lax.with_sharding_constraint(x, NamedSharding(use, P(*spec)))


def current_mesh() -> Optional[Mesh]:
    ctx = _ctx()
    return ctx[0] if ctx else None


def current_rules() -> Dict[str, MeshAxis]:
    ctx = _ctx()
    return dict(ctx[1]) if ctx else {}
