"""Output-stationary tiled GEMM — the paper's Listing-1 dataflow on TPU.

CGRA -> TPU adaptation (DESIGN.md section 3):
  * the paper sizes an output tile to the cluster's on-chip banks and keeps
    O resident while W/I stream through; here the (bm, bn) fp32 accumulator
    lives in VMEM scratch and A/B tiles stream HBM->VMEM per K step;
  * the paper's *loop unrolling* raising PE utilization maps to unrolling
    the K micro-loop over MXU-aligned (128x128) blocks;
  * the paper's *loop coalescing* (Listing 4) — one flat loop instead of a
    nest, slashing invocation overhead — maps to grid flattening: a single
    linearized grid dimension with div/mod index reconstruction, enabling
    revolving-buffer reuse and removing per-dimension grid bookkeeping.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv


def _apply_act(acc, activation):
    if activation == "relu":
        return jnp.maximum(acc, 0.0)
    if activation == "gelu":
        return 0.5 * acc * (1.0 + jnp.tanh(
            0.7978845608028654 * (acc + 0.044715 * acc ** 3)))
    if activation == "silu":
        return acc * (1.0 / (1.0 + jnp.exp(-acc)))
    assert activation is None
    return acc


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps, activation,
                 k_axis):
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _done():
        o_ref[...] = _apply_act(acc_ref[...], activation).astype(o_ref.dtype)


def _gemm_bias_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *, k_steps,
                      activation, k_axis):
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _done():
        acc = acc_ref[...] + bias_ref[...].astype(jnp.float32)
        o_ref[...] = _apply_act(acc, activation).astype(o_ref.dtype)


def gemm_os_pallas(a: jnp.ndarray, b: jnp.ndarray,
                   bias: Optional[jnp.ndarray] = None, *,
                   bm: int = 128, bn: int = 128, bk: int = 128,
                   activation: Optional[str] = None,
                   coalesce_grid: bool = False,
                   out_dtype=None,
                   interpret: bool = False) -> jnp.ndarray:
    """C[M,N] = act(A[M,K] @ B[K,N] + bias).  Shapes must be multiples of
    the block sizes (ops.py pads)."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    gm, gn, gk = M // bm, N // bn, K // bk
    out_dtype = out_dtype or a.dtype
    out_shape = jax.ShapeDtypeStruct((M, N), out_dtype)
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]

    if coalesce_grid:
        # Listing-4 analogue: one flat loop over output tiles; K innermost.
        grid = (gm * gn, gk)
        k_axis = 1

        def a_idx(t, k):
            return (t // gn, k)

        def b_idx(t, k):
            return (k, t % gn)

        def o_idx(t, k):
            return (t // gn, t % gn)

        def bias_idx(t, k):
            return (0, t % gn)

        semantics = ("arbitrary", "arbitrary")
    else:
        grid = (gm, gn, gk)
        k_axis = 2

        def a_idx(i, j, k):
            return (i, k)

        def b_idx(i, j, k):
            return (k, j)

        def o_idx(i, j, k):
            return (i, j)

        def bias_idx(i, j, k):
            return (0, j)

        semantics = ("parallel", "arbitrary", "arbitrary")

    in_specs = [pl.BlockSpec((bm, bk), a_idx),
                pl.BlockSpec((bk, bn), b_idx)]
    args = [a, b]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), bias_idx))
        args.append(bias.reshape(1, N))
        kern = functools.partial(_gemm_bias_kernel, k_steps=gk,
                                 activation=activation, k_axis=k_axis)
    else:
        kern = functools.partial(_gemm_kernel, k_steps=gk,
                                 activation=activation, k_axis=k_axis)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), o_idx),
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )(*args)
