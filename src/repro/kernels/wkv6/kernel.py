"""RWKV6 WKV recurrence kernel (data-dependent decay), chunked over time.

TPU adaptation: the (D x D) per-head state is the "output-stationary"
resident in VMEM scratch across the sequential time-chunk grid axis;
r/k/v/w chunks stream HBM->VMEM once.  Within a chunk the recurrence is
stepped sequentially (the mathematically-exact form; a matmul-rich chunked
reformulation exists but divides by cumulative decays and is numerically
unsafe for long chunks — documented trade-off, see DESIGN.md).

Grid: (B*H, T/ct), both axes "arbitrary" (state carries across chunks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _make_kernel(ct: int, s_steps: int):
    def kern(u_ref, s0_ref, r_ref, k_ref, v_ref, w_ref, o_ref, sout_ref,
             state):
        s = pl.program_id(1)

        @pl.when(s == 0)
        def _init():
            state[...] = s0_ref[0].astype(jnp.float32)

        u = u_ref[0].astype(jnp.float32)        # (D,)

        def body(i, S):
            t = pl.ds(i, 1)
            rt = r_ref[0, t, :][0].astype(jnp.float32)
            kt = k_ref[0, t, :][0].astype(jnp.float32)
            vt = v_ref[0, t, :][0].astype(jnp.float32)
            wt = w_ref[0, t, :][0].astype(jnp.float32)
            kv = kt[:, None] * vt[None, :]
            out = jnp.dot(rt[None, :], S + u[:, None] * kv,
                          preferred_element_type=jnp.float32)
            o_ref[0, t, :] = out.astype(o_ref.dtype)
            return wt[:, None] * S + kv

        S = jax.lax.fori_loop(0, ct, body, state[...])
        state[...] = S
        sout_ref[0] = S.astype(sout_ref.dtype)

    return kern


def wkv6_pallas(r, k, v, w, u, state0, *, ct: int = 64,
                interpret: bool = False):
    """r/k/v/w: (BH, T, D); u: (H, D); state0: (BH, D, D); BH = B*H.
    Returns (out (BH,T,D), state (BH,D,D))."""
    BH, T, D = r.shape
    H = u.shape[0]
    assert T % ct == 0
    s_steps = T // ct
    kern = _make_kernel(ct, s_steps)
    out, sout = pl.pallas_call(
        kern,
        grid=(BH, s_steps),
        in_specs=[
            pl.BlockSpec((1, D), lambda bh, s: (bh % H, 0)),
            pl.BlockSpec((1, D, D), lambda bh, s: (bh, 0, 0)),
            pl.BlockSpec((1, ct, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, ct, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, ct, D), lambda bh, s: (bh, s, 0)),
            pl.BlockSpec((1, ct, D), lambda bh, s: (bh, s, 0)),
        ],
        out_specs=(pl.BlockSpec((1, ct, D), lambda bh, s: (bh, s, 0)),
                   pl.BlockSpec((1, D, D), lambda bh, s: (bh, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((BH, T, D), r.dtype),
                   jax.ShapeDtypeStruct((BH, D, D), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(u, state0, r, k, v, w)
    return out, sout
