"""Names of a pytree's leaves, which the readings compare by."""
from __future__ import annotations

from typing import Any, Dict

import jax


def _part(key) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(key, attr):
            return str(getattr(key, attr))
    return str(key)


def named(tree: Any) -> Dict[str, Any]:
    """``{name: leaf}`` sorted by name, where a leaf's name is its path
    of dict keys (sequence indices) joined with ``/``.  A flat dict keeps
    its keys, so a dict of named leaves maps to itself; two leaves that
    would share a name raise ValueError."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {"/".join(_part(k) for k in path): leaf for path, leaf in flat}
    if len(out) != len(flat):
        raise ValueError("two leaves of the tree have the same name")
    return dict(sorted(out.items()))
