"""The paper's Sentiment140 model (FedAT §6.1): multinomial logistic
regression over a feature vector."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops


def init(key, spec: dict) -> dict:
    f, c = int(spec["data.n_features"]), int(spec["data.n_classes"])
    return {"w": jax.random.normal(key, (f, c), jnp.float32) * 0.01,
            "b": jnp.zeros((c,), jnp.float32)}


def apply(p: dict, x, precision=jax.lax.Precision.HIGHEST):
    return jnp.dot(x, p["w"], precision=precision) + p["b"]


def forward_flops(spec: dict) -> int:
    return flops.dense(int(spec["data.n_features"]),
                       int(spec["data.n_classes"]))
