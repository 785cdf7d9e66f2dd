"""Floating-point operations of layers, counted from their shapes.

A multiply-add counts as two operations.  Bias adds, activations and
pooling are left out: they are a fraction of a percent of a convolution
and run on the vector unit, not against the matrix peak.  Training is
counted as three forward passes (forward, and the two products of the
backward pass).
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
