"""The paper's CIFAR-10 CNN (FedAT §6.1): conv 32, 64, 64 (3x3, SAME,
each followed by ReLU and a 2x2 max-pool), dense 64, dense n_classes.

Written from the paper's description with ``lax.conv_general_dilated``;
nothing here comes from the program.  The parameter names are the
program's layout, which the benchmark needs to hand it these weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import flops

CONVS = (32, 64, 64)
HIDDEN = 64


def _dims(spec: dict):
    return int(spec["data.image_hw"]), int(spec["data.n_classes"])


def init(key, spec: dict) -> dict:
    """He-normal weights and zero biases, float32."""
    hw, n_classes = _dims(spec)
    ks = jax.random.split(key, 5)
    p, c_in = {}, 3
    for i, c_out in enumerate(CONVS):
        fan_in = 9 * c_in
        p[f"c{i + 1}_w"] = jax.random.normal(ks[i], (3, 3, c_in, c_out),
                                             jnp.float32) \
            * math.sqrt(2.0 / fan_in)
        p[f"c{i + 1}_b"] = jnp.zeros((c_out,), jnp.float32)
        c_in = c_out
    flat = (hw // 8) * (hw // 8) * CONVS[-1]
    p["d1_w"] = jax.random.normal(ks[3], (flat, HIDDEN), jnp.float32) \
        * math.sqrt(2.0 / flat)
    p["d1_b"] = jnp.zeros((HIDDEN,), jnp.float32)
    p["d2_w"] = jax.random.normal(ks[4], (HIDDEN, n_classes), jnp.float32) \
        * math.sqrt(2.0 / HIDDEN)
    p["d2_b"] = jnp.zeros((n_classes,), jnp.float32)
    return p


def apply(p: dict, x, precision=jax.lax.Precision.HIGHEST):
    """x: (B, H, W, 3) -> logits (B, n_classes)."""
    for i in range(len(CONVS)):
        w, b = p[f"c{i + 1}_w"], p[f"c{i + 1}_b"]
        x = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision) + b
        x = jax.nn.relu(x)
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, p["d1_w"], precision=precision) + p["d1_b"])
    return jnp.dot(x, p["d2_w"], precision=precision) + p["d2_b"]


def forward_flops(spec: dict) -> int:
    """Operations of one image through the forward pass."""
    hw, n_classes = _dims(spec)
    total, c_in = 0, 3
    for c_out in CONVS:
        total += flops.conv2d(hw, hw, c_in, c_out, 3)
        hw //= 2
        c_in = c_out
    return (total + flops.dense(hw * hw * CONVS[-1], HIDDEN)
            + flops.dense(HIDDEN, n_classes))
