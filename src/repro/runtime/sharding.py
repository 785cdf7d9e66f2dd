"""Logical-axis sharding layer (MaxText-style).

Model code annotates parameters and activations with *logical* axis names;
a rule table maps logical names to physical mesh axes.  On a single device
(smoke tests) everything resolves to fully-replicated and the annotations
are no-ops, so the same model code runs on 1 CPU device and on the 512-chip
production mesh.

Physical mesh axes (see :mod:`repro.launch.mesh`):
  * ``pod``   — FedAT tier axis (multi-pod mesh only)
  * ``data``  — intra-tier data parallelism + FSDP weight sharding,
                and the per-round *client* axis of the fused round step
                (core/executor.py shards ``clients_per_round`` over it)
  * ``model`` — tensor parallelism (heads / mlp / vocab / experts)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Tuple[str, ...], None]

# Logical-name -> physical mesh axis (or tuple of axes).
DEFAULT_RULES: Dict[str, Axis] = {
    # federated round execution (core/executor.py / core/simulation.py)
    "clients": "data",          # per-round client fan-out + resident stacks
    "tiers": "pod",             # tier-model stack leading dim (optional)
    # activations
    "batch": ("pod", "data"),   # global batch over pods (tiers) x data
    "seq": None,                # activation sequence dim: replicated
    "embed": None,              # activation d_model dim: replicated
    # parameters
    "fsdp": "data",             # ZeRO-3 weight dim (usually the in-feature dim)
    "tp": "model",              # tensor-parallel dim (heads*hd / d_ff / vocab)
    "experts": "model",         # expert parallelism (deepseek-style EP)
    "layers": None,             # stacked-layer leading dim
    "none": None,
    # caches
    "kv_seq": "model",          # seq-sharded KV cache (non-divisible kv heads)
    "kv_heads": "model",        # head-sharded KV cache
    "cache_batch": ("pod", "data"),
}

_local = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_local, "mesh", None)


def current_rules() -> Dict[str, Axis]:
    return getattr(_local, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[Dict[str, Axis]] = None):
    """Install ``mesh`` (+ optional rule overrides) for model tracing."""
    prev = (current_mesh(), current_rules())
    _local.mesh = mesh
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    _local.rules = merged
    try:
        yield
    finally:
        _local.mesh, _local.rules = prev


def _resolve(axes: Sequence[Optional[str]], mesh: Mesh, rules: Dict[str, Axis]) -> P:
    phys = []
    used: set = set()
    for name in axes:
        if name is None:
            phys.append(None)
            continue
        ax = rules.get(name)
        if ax is None:
            phys.append(None)
            continue
        # drop axes not present in this mesh (e.g. "pod" on the single-pod mesh)
        if isinstance(ax, tuple):
            ax = tuple(a for a in ax if a in mesh.shape and a not in used)
            # unwrap 1-tuples: P(("data",)) and P("data") denote the same
            # partitioning but only compare equal on newer jax
            ax = ax[0] if len(ax) == 1 else (ax if ax else None)
        elif ax not in mesh.shape or ax in used:
            ax = None
        if ax is not None:
            used.update(ax if isinstance(ax, tuple) else (ax,))
        phys.append(ax)
    return P(*phys)


def logical_sharding(axes: Sequence[Optional[str]],
                     mesh: Optional[Mesh] = None) -> Optional[NamedSharding]:
    """NamedSharding for logical ``axes`` under the current (or given) mesh."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, _resolve(axes, mesh, current_rules()))


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """``with_sharding_constraint`` by logical axes; no-op without a mesh.

    Inside ``shard_map`` bodies the ambient *abstract* mesh (which marks the
    manual axes) must be used, otherwise XLA rejects the mixed-mesh program;
    the rule tables there must avoid manual axes (see core/steps.py
    INNER_RULES).
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = _resolve(axes, mesh, current_rules())
    am = jax.sharding.get_abstract_mesh()
    if not am.empty and set(am.axis_names) == set(
            mesh.axis_names):
        return jax.lax.with_sharding_constraint(x, NamedSharding(am, spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_shardings(axes_tree, mesh: Optional[Mesh] = None):
    """Map a pytree of logical-axes tuples to NamedShardings (or None)."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return jax.tree.map(lambda _: None, axes_tree,
                            is_leaf=lambda l: isinstance(l, tuple))
    return jax.tree.map(lambda ax: logical_sharding(ax, mesh), axes_tree,
                        is_leaf=lambda l: isinstance(l, tuple) and all(
                            a is None or isinstance(a, str) for a in l))


def mesh_axis_size(name: str) -> int:
    """Size of a physical mesh axis under the thread-local current mesh
    (1 if absent).  Note for mesh-carrying objects (``SimEnv``,
    ``RoundExecutor``): size axes from your *own* mesh directly — this
    helper reads the ambient mesh, which is wrong for a no-mesh
    environment built inside a ``use_mesh()`` context."""
    mesh = current_mesh()
    if mesh is None or name not in mesh.shape:
        return 1
    return mesh.shape[name]


def tp_size() -> int:
    """Tensor-parallel degree implied by the current mesh ('model' axis)."""
    return mesh_axis_size("model")
