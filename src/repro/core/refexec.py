"""Batched DFG reference execution lowered to JAX (the fast oracle).

``DFG.reference_execute`` is the verification oracle of the paper's IV-C
flow: sequential, non-pipelined dataflow execution of the mapped loop.
The pure-Python interpreter is exactly right for one seed, but a batched
verification sweep runs it over every seed of every invocation, where the
per-node Python dispatch dominates the whole verify pipeline.

This module compiles a DFG into a jitted double ``lax.scan`` — outer scan
over invocations (live-in rows as xs), inner scan over loop iterations —
with every node value a ``[batch]`` int32 vector and the bank images one
flat donated buffer.  Node semantics mirror the interpreter op for op:
values wrap to the datapath width after every node, out-of-range loads
read 0, out-of-range stores drop (they scatter into a dump cell that is
never read back), and loop-carried operands read their ``init`` value for
the first ``dist`` iterations.  ``tests/test_batched_verify.py`` pins the
result word-for-word against both the scalar interpreter and the numpy
batch interpreter for every library kernel.

Compiled executables are cached on the DFG instance keyed by the
execution shape, so re-verifying the same kernel across seed batches
reuses one XLA program.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dfg import DFG, Op, wrap


def _lowered(dfg: DFG, *, n_iters: int, bits: int, B: int,
             banks: Tuple[Tuple[str, int], ...],
             li_names: Tuple[str, ...]):
    """Build (and jit) the executor for one execution shape."""

    order = dfg.topo_order()
    nodes = [dfg.nodes[vid] for vid in order]
    half, full = 1 << (bits - 1), 1 << bits

    off: Dict[str, int] = {}
    tot = 0
    for name, w in banks:
        off[name] = tot
        tot += w
    dump = tot                 # one never-read cell for dropped stores
    stride = tot + 1
    widths = dict(banks)
    li_pos = {n: i for i, n in enumerate(li_names)}
    # loop-carried reads: history depth needed per producing node
    maxdist = {vid: 0 for vid in order}
    for n in nodes:
        for o in n.operands:
            maxdist[o.src] = max(maxdist[o.src], o.dist)

    def awrap(x):
        return ((x + half) & (full - 1)) - half

    def morpher_refexec(mem0: jnp.ndarray,
                        li_mat: jnp.ndarray) -> jnp.ndarray:
        row = jnp.arange(B) * stride                       # [B]

        def one_invocation(mem, li_row):
            hist0 = {vid: jnp.zeros((d, B), jnp.int32)
                     for vid, d in maxdist.items() if d}

            def one_iteration(carry, it):
                mem, hist = carry
                cur: Dict[int, jnp.ndarray] = {}

                def read(o):
                    if o.dist == 0:
                        return cur[o.src]
                    return jnp.where(it >= o.dist, hist[o.src][o.dist - 1],
                                     wrap(o.init, bits))

                for vid, n in zip(order, nodes):
                    if n.op == Op.CONST:
                        cur[vid] = jnp.full((B,), wrap(n.imm, bits),
                                            jnp.int32)
                    elif n.op == Op.LIVEIN:
                        cur[vid] = jnp.broadcast_to(
                            li_row[li_pos[n.livein]], (B,))
                    elif n.op == Op.LOAD:
                        addr = read(n.operands[0])
                        w = widths[n.array]
                        ok = (addr >= 0) & (addr < w)
                        fidx = row + off[n.array] + jnp.clip(addr, 0, w - 1)
                        cur[vid] = jnp.where(ok, jnp.take(mem, fidx), 0)
                    elif n.op == Op.STORE:
                        addr = read(n.operands[0])
                        val = read(n.operands[1])
                        w = widths[n.array]
                        ok = (addr >= 0) & (addr < w)
                        fidx = row + jnp.where(
                            ok, off[n.array] + jnp.clip(addr, 0, w - 1),
                            dump)
                        mem = mem.at[fidx].set(val)
                        cur[vid] = jnp.zeros((B,), jnp.int32)
                    else:
                        a = read(n.operands[0])
                        b = read(n.operands[1]) if len(n.operands) > 1 \
                            else jnp.zeros((B,), jnp.int32)
                        if n.op == Op.ADD:
                            r = a + b
                        elif n.op == Op.SUB:
                            r = a - b
                        elif n.op == Op.MUL:
                            r = a * b
                        elif n.op == Op.SHL:
                            r = a << (b & (bits - 1))
                        elif n.op == Op.SHR:
                            r = a >> (b & (bits - 1))
                        elif n.op == Op.AND:
                            r = a & b
                        elif n.op == Op.OR:
                            r = a | b
                        elif n.op == Op.XOR:
                            r = a ^ b
                        elif n.op == Op.CMPGE:
                            r = (a >= b).astype(jnp.int32)
                        elif n.op == Op.CMPEQ:
                            r = (a == b).astype(jnp.int32)
                        elif n.op == Op.CMPLT:
                            r = (a < b).astype(jnp.int32)
                        elif n.op == Op.SELECT:
                            r = jnp.where(a != 0, b, read(n.operands[2]))
                        else:
                            raise NotImplementedError(n.op)
                        cur[vid] = awrap(r)
                hist = {vid: jnp.concatenate(
                            [cur[vid][None], h[:-1]], axis=0)
                        for vid, h in hist.items()}
                return (mem, hist), 0

            (mem, _), _ = jax.lax.scan(one_iteration, (mem, hist0),
                                       jnp.arange(n_iters))
            return mem, 0

        mem, _ = jax.lax.scan(one_invocation, mem0, li_mat)
        return mem

    donate = (0,) if jax.default_backend() != "cpu" else ()
    # a named function: its XLA module is ``jit_morpher_refexec``
    return jax.jit(morpher_refexec, donate_argnums=donate)


def reference_execute_jax(dfg: DFG, n_iters: int,
                          init_banks: Dict[str, np.ndarray],
                          invocations: Sequence[Dict[str, int]],
                          bits: int) -> Dict[str, np.ndarray]:
    """Fold batched DFG reference execution over all invocations on XLA.

    init_banks: name -> [batch, words] int arrays; returns a fresh dict of
    the same shape, bit-identical per row to folding
    ``DFG.reference_execute`` over the invocations.
    """
    names = sorted(init_banks)
    banks = tuple((k, int(np.asarray(init_banks[k]).shape[1]))
                  for k in names)
    B = int(np.asarray(init_banks[names[0]]).shape[0]) if names else 1
    li_names = tuple(sorted({n.livein for n in dfg.nodes.values()
                             if n.op == Op.LIVEIN}))
    key = (n_iters, bits, B, banks, li_names, len(invocations))
    cache = getattr(dfg, "_refexec_cache", None)
    if cache is None:
        cache = dfg._refexec_cache = {}
    fn = cache.get(key)
    if fn is None:
        fn = cache[key] = _lowered(dfg, n_iters=n_iters, bits=bits, B=B,
                                   banks=banks, li_names=li_names)

    stride = sum(w for _, w in banks) + 1
    mem0 = np.zeros((B, stride), dtype=np.int32)
    pos = 0
    for k, w in banks:
        mem0[:, pos:pos + w] = np.asarray(init_banks[k])
        pos += w
    li_mat = np.array([[wrap(inv[n], bits) for n in li_names]
                       for inv in invocations],
                      dtype=np.int32).reshape(len(invocations),
                                              len(li_names))
    out = np.asarray(fn(jnp.asarray(mem0.reshape(-1)), jnp.asarray(li_mat)))
    out = out.reshape(B, stride)
    final = {}
    pos = 0
    for k, w in banks:
        final[k] = out[:, pos:pos + w].astype(np.int64)
        pos += w
    return final
