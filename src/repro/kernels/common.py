"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + explicit BlockSpec VMEM tiling,
MXU-aligned block shapes) and are *validated* on CPU with interpret=True
against their pure-jnp oracles in ref.py.
"""
from __future__ import annotations

import jax.numpy as jnp

# TPU v5e hardware alignment
MXU = 128        # systolic array dim; matmul tiles should be multiples
SUBLANE = 8      # fp32 sublane packing
LANE = 128


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(x: jnp.ndarray, axis: int, multiple: int):
    """Zero-pad ``axis`` up to a multiple; returns (padded, original_size)."""
    size = x.shape[axis]
    target = cdiv(size, multiple) * multiple
    if target == size:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad), size
