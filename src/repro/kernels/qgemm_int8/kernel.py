"""Quantized int8 x int8 -> int32 GEMM with per-row/col scales.

The paper's target CGRA has a 16-bit integer datapath ("in line with a
16-bit data path"); the edge-inference analogue on TPU is int8 MXU matmul
with int32 accumulation and fp32 rescale — the serving-path quantized
deployment kernel.  Same output-stationary structure as gemm_os: int32
accumulator resident in VMEM, A/B int8 tiles streamed per K step, scales
applied once on the final K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qgemm_kernel(a_ref, b_ref, sa_ref, sb_ref, o_ref, acc_ref, *, k_steps):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.int32),
                            b_ref[...].astype(jnp.int32),
                            preferred_element_type=jnp.int32)

    @pl.when(k == k_steps - 1)
    def _done():
        sa = sa_ref[...].astype(jnp.float32)     # (bm, 1)
        sb = sb_ref[...].astype(jnp.float32)     # (1, bn)
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * sa * sb
                      ).astype(o_ref.dtype)


def qgemm_int8_pallas(a, b, a_scale, b_scale, *, bm: int = 128,
                      bn: int = 128, bk: int = 256, out_dtype=jnp.float32,
                      interpret: bool = False):
    M, K = a.shape
    _, N = b.shape
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    gm, gn, gk = M // bm, N // bn, K // bk
    scratch = [pltpu.VMEM((bm, bn), jnp.int32)]
    return pl.pallas_call(
        functools.partial(_qgemm_kernel, k_steps=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(a, b, a_scale.reshape(M, 1), b_scale.reshape(1, N))
