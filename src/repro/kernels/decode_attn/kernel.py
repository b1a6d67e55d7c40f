"""Flash-decode attention kernel: one new token against a long KV cache.

Output-stationary insight applied to attention: the (G, D) output tile for
one kv-head's query group stays resident in VMEM with running max/denom
(online softmax) while KV blocks stream through — KV is read exactly once
from HBM, which is the roofline-optimal schedule for decode (memory-bound).

Grid: (B, Hkv, S/bs) — the S axis is "arbitrary" (sequential) so the
softmax state carries across KV blocks in scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bs, s_steps, scale):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)         # (bs, D)
    v = v_ref[0, 0].astype(jnp.float32)         # (bs, D)
    logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    length = len_ref[0]
    pos = s * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    logits = jnp.where(pos < length, logits, NEG_INF)      # (G, bs)

    m_prev = m_ref[...]                         # (G, 1)
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)                 # (G, bs)
    alpha = jnp.exp(m_prev - m_new)             # (G, 1)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(s == s_steps - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attn_pallas(q, k, v, lengths, *, bs: int = 512, scale=None,
                       interpret: bool = False):
    """q: (B, Hkv, G, D); k/v: (B, Hkv, S, D); lengths: (B,) int32."""
    B, Hkv, G, D = q.shape
    _, _, S, _ = k.shape
    assert S % bs == 0
    s_steps = S // bs
    scale = float(scale if scale is not None else 1.0 / (D ** 0.5))
    return pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, s_steps=s_steps,
                          scale=scale),
        grid=(B, Hkv, s_steps),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, s: (b,)),
            pl.BlockSpec((1, G, D), lambda b, h, s: (b * Hkv + h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, D), lambda b, h, s: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda b, h, s: (b * Hkv + h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(lengths, q.reshape(B * Hkv, G, D), k, v).reshape(B, Hkv, G, D)
