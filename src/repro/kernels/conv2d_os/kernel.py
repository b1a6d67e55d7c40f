"""Output-stationary direct convolution (the paper's Listing-2/5 dataflow).

CGRA -> TPU adaptation: the paper keeps one output-channel tile resident in
the cluster banks and fully unrolls the KxK taps (CONV-U-C); here each grid
step keeps a (OH*OW, bco) fp32 accumulator in VMEM and unrolls the KxK taps
as static slices feeding MXU matmuls (implicit GEMM over Cin).  The spatial
image of an edge-AI conv (e.g. 64x64) fits VMEM whole, exactly like the
paper's 8 kB banks hold the 64x64 int16 tile.

Grid: (N, Cout/bco) — both "arbitrary"; input block is the full image of
one batch element, weights stream one output-channel tile per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _conv_kernel(x_ref, w_ref, o_ref, acc_ref, *, KH, KW, OH, OW):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    x = x_ref[0]                      # (H, W, Cin)
    Cin = x.shape[-1]
    for kh in range(KH):
        for kw in range(KW):
            patch = x[kh:kh + OH, kw:kw + OW, :].reshape(OH * OW, Cin)
            tap = w_ref[kh, kw]       # (Cin, bco)
            acc_ref[...] += jnp.dot(patch, tap,
                                    preferred_element_type=jnp.float32)
    o_ref[...] = acc_ref[...].reshape(1, OH, OW, -1).astype(o_ref.dtype)


def conv2d_os_pallas(x: jnp.ndarray, w: jnp.ndarray, *, bco: int = 128,
                     out_dtype=None, interpret: bool = False) -> jnp.ndarray:
    N, H, W, Cin = x.shape
    KH, KW, Cin2, Cout = w.shape
    assert Cin == Cin2 and Cout % bco == 0
    OH, OW = H - KH + 1, W - KW + 1
    out_dtype = out_dtype or x.dtype
    scratch = [pltpu.VMEM((OH * OW, bco), jnp.float32)]

    return pl.pallas_call(
        functools.partial(_conv_kernel, KH=KH, KW=KW, OH=OH, OW=OW),
        grid=(N, Cout // bco),
        in_specs=[
            pl.BlockSpec((1, H, W, Cin), lambda n, c: (n, 0, 0, 0)),
            pl.BlockSpec((KH, KW, Cin, bco), lambda n, c: (0, 0, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, OH, OW, bco), lambda n, c: (n, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((N, OH, OW, Cout), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, w)
