"""Device-mesh construction + the named-mesh registry (DESIGN.md
§Scale-mapping).

Two families of meshes, one axis vocabulary (``pod``/``data``/``model``,
see :mod:`repro.runtime.sharding`):

* :func:`make_production_mesh` — the datacenter shapes: one pod of 256
  chips as ``(data=16, model=16)``, or two pods as ``(pod=2, data=16,
  model=16)`` where the ``pod`` axis is the FedAT *tier* axis.
* :func:`make_host_mesh` — a degenerate mesh over however many devices
  this host actually has, so CPU drivers/tests exercise the *same*
  sharded code path on 1–N local devices (force N with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before first
  jax init).

Both are functions (never module-level constants) so importing this
module does not touch jax device state.

The string grammar accepted by :func:`resolve_mesh` / :func:`parse_mesh_name`
is what :class:`~repro.api.spec.MeshSpec` serializes to — ``None`` (single
device, no mesh), ``"host"``, ``"host:<n_pods>"``, ``"production"``,
``"production:2"``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis in Auto mode (GSPMD decides the
    layout of whatever the program does not constrain)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """The 256/512-chip datacenter mesh.

    ``multi_pod=False``: one pod, ``(data=16, model=16)`` — 256 devices.
    ``multi_pod=True``: two pods, ``(pod=2, data=16, model=16)`` — 512
    devices; the ``pod`` axis is the FedAT tier axis.

    Requires that many devices to be visible (the dry-run forces them via
    ``--xla_force_host_platform_device_count=512``).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_pods: int = 1) -> jax.sharding.Mesh:
    """A mesh over however many devices this host actually has.

    With ``n_pods == 1`` (or a device count not divisible by ``n_pods``)
    the shape is ``(data=n_devices, model=1)``; otherwise ``(pod=n_pods,
    data=n_devices/n_pods, model=1)``.  Used by CPU drivers and tests so a
    single code path covers 1 local device up to a forced N-device host.

    The indivisible-device-count fallback is a convenience for direct
    callers (``launch/train.py --multi_pod`` on a 1-device box); the
    declarative path (:func:`resolve_mesh`, i.e. ``MeshSpec``) rejects it
    instead — a spec that names ``host:N`` must get N pods or fail loudly.
    """
    n = len(jax.devices())
    if n_pods > 1 and n % n_pods == 0:
        return make_mesh((n_pods, n // n_pods, 1), ("pod", "data", "model"))
    return make_mesh((n, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# named meshes (the MeshSpec grammar)
# ---------------------------------------------------------------------------

MESH_KINDS = ("single", "host", "production")

#: data-axis sizes known without building the mesh (None = depends on the
#: runtime device count); MeshSpec uses this for static pad validation.
STATIC_DATA_AXIS = {"single": 1, "production": 16}


def parse_mesh_name(name: Optional[str]) -> Tuple[str, int]:
    """``None``/``"single"`` -> ("single", 1); ``"host[:p]"`` /
    ``"production[:p]"`` -> (kind, n_pods).  Raises ValueError with the
    accepted grammar on anything else."""
    if name is None or name == "single":
        return "single", 1
    kind, _, arg = str(name).partition(":")
    if kind not in ("host", "production"):
        raise ValueError(
            f"unknown mesh {name!r}; expected one of {MESH_KINDS} "
            f"(optionally 'host:<n_pods>' / 'production:2')")
    try:
        n_pods = int(arg) if arg else 1
    except ValueError:
        raise ValueError(f"bad n_pods in mesh name {name!r} "
                         f"(expected e.g. 'host:2')")
    if n_pods < 1:
        raise ValueError(f"mesh n_pods must be >= 1, got {n_pods}")
    if kind == "production" and n_pods > 2:
        raise ValueError(
            f"production mesh has 1 or 2 pods, got n_pods={n_pods}")
    return kind, n_pods


def resolve_mesh(name: Optional[str]) -> Optional[jax.sharding.Mesh]:
    """Materialize a named mesh (``None`` for the single-device default).

    This touches jax device state, so callers (``SimEnv``) resolve lazily
    at environment build time, never at import time.
    """
    kind, n_pods = parse_mesh_name(name)
    if kind == "single":
        return None
    if kind == "host":
        n = len(jax.devices())
        if n_pods > 1 and n % n_pods:
            raise ValueError(
                f"mesh {name!r} needs a device count divisible by "
                f"n_pods={n_pods}, but this host has {n} device(s); "
                f"force one with XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N")
        return make_host_mesh(n_pods)
    return make_production_mesh(multi_pod=n_pods > 1)

