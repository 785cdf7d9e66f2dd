"""The lossy step of Google's Encoded Polyline Algorithm on weights
(FedAT §4.3): each value rounded to ``precision`` decimals, which is
what the receiver decodes."""
from __future__ import annotations

import jax.numpy as jnp

DEFAULT_PRECISION = 4


def _precision(arg):
    return DEFAULT_PRECISION if arg in (None, "") else int(arg)


def lossy(x, arg=None):
    """The decoded value: ``x`` rounded to ``precision`` decimals."""
    f = 10.0 ** _precision(arg)
    return jnp.round(x * f) / f
