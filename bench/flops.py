"""Floating-point operations of layers, counted from their shapes.

A multiply-add counts as two operations.  Bias adds, activations, norms,
softmax and pooling are left out: they are a fraction of a percent of a
convolution or a matmul and run on the vector unit, not against the
matrix peak.  Training is counted as three forward passes (forward, and
the two products of the backward pass).
"""
from __future__ import annotations


def conv2d(h: int, w: int, c_in: int, c_out: int, k: int) -> int:
    """A stride-1 SAME convolution of a (h, w, c_in) input, k x k kernel."""
    return 2 * h * w * c_out * k * k * c_in


def dense(n_in: int, n_out: int) -> int:
    return 2 * n_in * n_out


def training(forward: int) -> int:
    """Operations of one training sample: forward plus backward."""
    return 3 * forward


def causal_attention(seq: int, heads: int, head_dim: int) -> int:
    """Scores and weighted values of causal self-attention over ``seq``
    positions: each query attends itself and the positions before it."""
    return 2 * 2 * heads * head_dim * seq * (seq + 1) // 2
