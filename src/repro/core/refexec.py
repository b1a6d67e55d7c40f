"""Batched DFG reference execution lowered to JAX (the fast oracle).

This is the DFG oracle of the paper's IV-C flow, the only one production
runs: sequential, non-pipelined dataflow execution of the mapped loop over
every seed and invocation of a verification in one program.  The plain
reference of the same semantics is ``DFG.reference_execute``, a
pure-Python interpreter of one image and one invocation that only tests
call.

This module compiles a DFG into one jitted program (its XLA module is
``jit_morpher_refexec``) with one of two bodies, chosen by ``_body``:

  * the scan (``_scan_body``): a double ``lax.scan`` -- outer scan over
    invocations (live-in rows as xs), inner scan over loop iterations --
    with every node value a ``[batch]`` int32 vector and the bank images
    one flat donated buffer;
  * the VMEM kernel (``_vmem_body``, TPU only): the same loop nest inside
    one Pallas kernel with the image resident in VMEM.  It needs a DFG
    whose LOAD and STORE addresses are *uniform* (``_uniform``): no LOAD
    in their backward slice, so each is one scalar for every row of the
    batch.  Uniform nodes run on the scalar unit; the others are one
    ``[8, 128]`` tile each (row = seed, the value in every lane), and a
    memory node touches the one image tile its scalar address names.

Node semantics mirror the interpreter op for op (``_apply`` is the op
table of both bodies): values wrap to the datapath width after every
node, out-of-range loads read 0, out-of-range stores drop, and
loop-carried operands read their ``init`` value for the first ``dist``
iterations.  ``tests/test_batched_verify.py`` pins the scan word-for-word
against the scalar interpreter folded over the invocations;
``tests/test_refexec_vmem.py`` pins the kernel against the scan and both
against that same reference.

Compiled executables are cached on the DFG instance keyed by the
execution shape, so re-verifying the same kernel across seed batches
reuses one XLA program.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, FrozenSet, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dfg import DFG, Node, Op, wrap
from .simulator import _VMEM_BUDGET, _lanes

# live-in words per SMEM block of the kernel: the tiling of a 1-D int32
# array in SMEM, which a block must divide into
_LI_BLOCK = 1024


def _wrap(x, bits: int):
    """``x`` (int32) wrapped to a signed ``bits``-bit value."""
    return (x << (32 - bits)) >> (32 - bits)


def _apply(op: Op, a, b, c, bits: int):
    """One ALU node's value from its operands, wrapped to ``bits``: the op
    table of both bodies, on [batch] vectors, [8, 128] tiles and scalars
    alike (``c`` is SELECT's third operand; operands of one shape)."""
    if op == Op.ADD:
        r = a + b
    elif op == Op.SUB:
        r = a - b
    elif op == Op.MUL:
        r = a * b
    elif op == Op.SHL:
        r = a << (b & (bits - 1))
    elif op == Op.SHR:
        r = a >> (b & (bits - 1))
    elif op == Op.AND:
        r = a & b
    elif op == Op.OR:
        r = a | b
    elif op == Op.XOR:
        r = a ^ b
    elif op == Op.CMPGE:
        r = (a >= b).astype(jnp.int32)
    elif op == Op.CMPEQ:
        r = (a == b).astype(jnp.int32)
    elif op == Op.CMPLT:
        r = (a < b).astype(jnp.int32)
    elif op == Op.SELECT:
        r = jax.lax.select(a != 0, b, c)
    else:
        raise NotImplementedError(op)
    return _wrap(r, bits)


def _uniform(dfg: DFG) -> FrozenSet[int]:
    """Ids of the nodes with no LOAD in their backward slice, loop-carried
    operands included (a fixed point over operands): CONST, LIVEIN and
    what is computed from them alone.  Their values are the same for
    every row of a batch."""
    uni = {v for v, n in dfg.nodes.items() if n.op != Op.LOAD}
    changed = True
    while changed:
        changed = False
        for v in sorted(uni):
            if any(o.src not in uni for o in dfg.nodes[v].operands):
                uni.discard(v)
                changed = True
    return frozenset(uni)


def _eligible(dfg: DFG) -> bool:
    """Whether every LOAD and STORE address is uniform (``_uniform``), as
    the VMEM kernel needs."""
    uni = _uniform(dfg)
    return all(n.operands[0].src in uni for n in dfg.nodes.values()
               if n.is_mem)


def _vmem_bytes(words: int) -> int:
    """VMEM the kernel holds for an image row of ``words``: one row
    block's [tiles, 8, 128] int32 image (the row and at least one zero
    word after it) in and out, each double-buffered by the pipeline."""
    return 4 * _lanes(words + 1) * 8 * 4


def _body(dfg: DFG, words: int) -> str:
    """Which body runs ``dfg`` over an image row of ``words``: ``"vmem"``
    (the Pallas kernel) on a TPU backend for a DFG whose addresses are
    uniform (``_eligible``) and an image that fits ``_VMEM_BUDGET``, else
    ``"scan"``.  Shared by the traced function and the oracle's span, so
    the two cannot disagree."""
    if (jax.default_backend() != "tpu" or not _eligible(dfg)
            or _vmem_bytes(words) > _VMEM_BUDGET):
        return "scan"
    return "vmem"


@dataclass(frozen=True)
class _Program:
    """A DFG's node program for one execution shape: nodes in
    ``topo_order``, the flat image row (banks at ``off``, then one
    never-read dump cell: ``stride`` words), live-in columns, the history
    depth each loop-carried producer needs, and the uniform nodes."""
    nodes: Tuple[Node, ...]
    n_iters: int
    bits: int
    off: Dict[str, int]
    widths: Dict[str, int]
    stride: int
    li_pos: Dict[str, int]
    maxdist: Dict[int, int]
    uniform: FrozenSet[int]

    @staticmethod
    def of(dfg: DFG, *, n_iters: int, bits: int,
           banks: Tuple[Tuple[str, int], ...],
           li_names: Tuple[str, ...]) -> "_Program":
        nodes = tuple(dfg.nodes[vid] for vid in dfg.topo_order())
        off: Dict[str, int] = {}
        tot = 0
        for name, w in banks:
            off[name] = tot
            tot += w
        maxdist = {n.id: 0 for n in nodes}
        for n in nodes:
            for o in n.operands:
                maxdist[o.src] = max(maxdist[o.src], o.dist)
        return _Program(nodes=nodes, n_iters=n_iters, bits=bits, off=off,
                        widths=dict(banks), stride=tot + 1,
                        li_pos={n: i for i, n in enumerate(li_names)},
                        maxdist={v: d for v, d in maxdist.items() if d},
                        uniform=_uniform(dfg))


def _scan_body(p: _Program, mem0: jnp.ndarray,
               li_mat: jnp.ndarray) -> jnp.ndarray:
    """The double ``lax.scan`` over a flat [B * stride] image."""
    B = mem0.shape[0] // p.stride
    bits = p.bits
    row = jnp.arange(B) * p.stride                          # [B]
    dump = p.stride - 1

    def one_invocation(mem, li_row):
        hist0 = {vid: jnp.zeros((d, B), jnp.int32)
                 for vid, d in p.maxdist.items()}

        def one_iteration(carry, it):
            mem, hist = carry
            cur: Dict[int, jnp.ndarray] = {}

            def read(o):
                if o.dist == 0:
                    return cur[o.src]
                return jnp.where(it >= o.dist, hist[o.src][o.dist - 1],
                                 wrap(o.init, bits))

            for n in p.nodes:
                vid = n.id
                if n.op == Op.CONST:
                    cur[vid] = jnp.full((B,), wrap(n.imm, bits), jnp.int32)
                elif n.op == Op.LIVEIN:
                    cur[vid] = jnp.broadcast_to(
                        li_row[p.li_pos[n.livein]], (B,))
                elif n.op == Op.LOAD:
                    addr = read(n.operands[0])
                    w = p.widths[n.array]
                    ok = (addr >= 0) & (addr < w)
                    fidx = row + p.off[n.array] + jnp.clip(addr, 0, w - 1)
                    cur[vid] = jnp.where(ok, jnp.take(mem, fidx), 0)
                elif n.op == Op.STORE:
                    addr = read(n.operands[0])
                    val = read(n.operands[1])
                    w = p.widths[n.array]
                    ok = (addr >= 0) & (addr < w)
                    fidx = row + jnp.where(
                        ok, p.off[n.array] + jnp.clip(addr, 0, w - 1),
                        dump)
                    mem = mem.at[fidx].set(val)
                    cur[vid] = jnp.zeros((B,), jnp.int32)
                else:
                    ops = [read(o) for o in n.operands]
                    ops += [jnp.zeros((B,), jnp.int32)] * (3 - len(ops))
                    cur[vid] = _apply(n.op, *ops, bits)
            hist = {vid: jnp.concatenate([cur[vid][None], h[:-1]], axis=0)
                    for vid, h in hist.items()}
            return (mem, hist), 0

        (mem, _), _ = jax.lax.scan(one_iteration, (mem, hist0),
                                   jnp.arange(p.n_iters))
        return mem, 0

    mem, _ = jax.lax.scan(one_invocation, mem0, li_mat)
    return mem


def _vmem_body(p: _Program, mem0: jnp.ndarray, li_mat: jnp.ndarray, *,
               interpret: bool = False) -> jnp.ndarray:
    """``_scan_body`` of an eligible DFG (``_eligible``) as one Pallas
    kernel, word for word.  The image row is laid out as [tiles, 8, 128]
    (flat word ``f`` at tile ``f >> 7``, lane ``f & 127``, one row per
    seed); a grid axis runs the rows in blocks of 8 with their image
    block resident in VMEM, and a second, sequential one the invocations
    in blocks whose live-ins stream into SMEM.  Inside, ``fori_loop``s
    run the invocations and iterations; uniform nodes are int32 scalars,
    the others [8, 128] tiles.  A LOAD reads its address's tile and
    takes the lane (an address outside its bank reads a zero word past
    the row); a STORE writes the lane back under ``pl.when`` of its
    bounds check.  The body uses ``jax.lax`` ops, not the jitted ``jnp``
    helpers (``where``, ``clip``, ``sum``): it is traced and lowered in
    every process, once per kernel, and each equation costs set-up
    time.  ``interpret=True`` runs it in the
    Pallas interpreter (the CPU tests)."""
    B = mem0.shape[0] // p.stride
    n_inv, n_li = li_mat.shape
    if n_inv == 0:
        return mem0
    i32 = jnp.int32
    bits = p.bits
    R, Wt = -(-B // 8), _lanes(p.stride + 1) // 128
    NL = 1 << max(0, n_li - 1).bit_length()     # live-ins, to a power of 2
    IB = max(1, _LI_BLOCK // NL)                # invocations per block
    if IB >= n_inv:
        IB = n_inv
    G = -(-n_inv // IB)

    mem = jnp.pad(mem0.reshape(B, p.stride),
                  ((0, R * 8 - B), (0, Wt * 128 - p.stride)))
    mem = mem.reshape(R * 8, Wt, 128).transpose(1, 0, 2)  # [Wt, B8, 128]
    li = jnp.pad(li_mat.astype(i32),
                 ((0, G * IB - n_inv), (0, NL - n_li))).reshape(-1)

    def const(vid, v):
        """A node's constant ``v``: a scalar for a uniform node, else a
        tile."""
        if vid in p.uniform:
            return jnp.int32(v)
        return jnp.full((8, 128), v, i32)

    def tile(x):
        return x if x.ndim else jax.lax.broadcast_in_dim(x, (8, 128), ())

    def kernel(li_ref, mem_in, mem_ref):
        g = pl.program_id(1)

        @pl.when(g == 0)
        def _():
            mem_ref[...] = mem_in[...]

        lane = jax.lax.broadcasted_iota(i32, (8, 128), 1)

        def invocation(i, carry):
            @pl.when(g * IB + i < n_inv)
            def _():
                livein = {n.id: li_ref[i * NL + p.li_pos[n.livein]]
                          for n in p.nodes if n.op == Op.LIVEIN}

                def iteration(it, hist):
                    cur: Dict[int, jnp.ndarray] = {}
                    zeros = jnp.zeros((8, 128), i32)

                    def read(o):
                        if o.dist == 0:
                            return cur[o.src]
                        return jax.lax.select(
                            it >= o.dist, hist[o.src][o.dist - 1],
                            const(o.src, wrap(o.init, bits)))

                    for n in p.nodes:
                        vid = n.id
                        if n.op == Op.CONST:
                            cur[vid] = jnp.int32(wrap(n.imm, bits))
                        elif n.op == Op.LIVEIN:
                            cur[vid] = livein[vid]
                        elif n.is_mem:
                            # scalar address arithmetic; an address out of
                            # its bank reads the zero word after the row
                            # and stores nothing
                            addr = read(n.operands[0])
                            ok = (addr >= 0) & (addr < p.widths[n.array])
                            f = jax.lax.select(ok, addr + p.off[n.array],
                                               p.stride)
                            t = f >> 7
                            at = lane == (f & 127)
                            if n.op == Op.LOAD:
                                word = jax.lax.reduce(
                                    jax.lax.select(at, mem_ref[t], zeros),
                                    np.int32(0), jax.lax.add, (1,))
                                cur[vid] = jax.lax.broadcast_in_dim(
                                    word, (8, 128), (0,))
                            else:
                                val = tile(read(n.operands[1]))

                                @pl.when(ok)
                                def _store():
                                    mem_ref[t] = jax.lax.select(
                                        at, val, mem_ref[t])

                                cur[vid] = const(vid, 0)
                        else:
                            ops = [read(o) for o in n.operands]
                            ops += [const(vid, 0)] * (3 - len(ops))
                            if vid not in p.uniform:
                                ops = [tile(x) for x in ops]
                            cur[vid] = _apply(n.op, *ops, bits)
                    return {vid: (cur[vid],) + h[:-1]
                            for vid, h in hist.items()}

                hist0 = {vid: (const(vid, 0),) * d
                         for vid, d in p.maxdist.items()}
                jax.lax.fori_loop(0, p.n_iters, iteration, hist0)

            return carry

        jax.lax.fori_loop(0, IB, invocation, 0)

    image = pl.BlockSpec((Wt, 8, 128), lambda r, g: (0, r, 0))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(mem.shape, i32),
        grid=(R, G),
        in_specs=[pl.BlockSpec((IB * NL,), lambda r, g: (g,),
                               memory_space=pltpu.SMEM), image],
        out_specs=image,
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(p.stride) + (16 << 20)),
        interpret=interpret,
        name="morpher_refexec_loops",
    )(li, mem)
    out = out.transpose(1, 0, 2).reshape(R * 8, Wt * 128)
    return out[:B, :p.stride].reshape(-1).astype(mem0.dtype)


def _lowered(dfg: DFG, *, n_iters: int, bits: int,
             banks: Tuple[Tuple[str, int], ...],
             li_names: Tuple[str, ...]):
    """Build (and jit) the executor for one execution shape."""
    p = _Program.of(dfg, n_iters=n_iters, bits=bits, banks=banks,
                    li_names=li_names)

    def morpher_refexec(mem0: jnp.ndarray,
                        li_mat: jnp.ndarray) -> jnp.ndarray:
        if _body(dfg, p.stride) == "vmem":
            return _vmem_body(p, mem0, li_mat)
        return _scan_body(p, mem0, li_mat)

    donate = (0,) if jax.default_backend() != "cpu" else ()
    # a named function: its XLA module is ``jit_morpher_refexec``
    return jax.jit(morpher_refexec, donate_argnums=donate)


def _stride(init_banks: Dict[str, np.ndarray]) -> int:
    """Words of one row of the flat image: every bank, then the dump
    cell."""
    return sum(int(np.shape(v)[1]) for v in init_banks.values()) + 1


def oracle_body(dfg: DFG, init_banks: Dict[str, np.ndarray]) -> str:
    """The body ``reference_execute_jax`` runs ``dfg`` with on these
    banks (``_body``)."""
    return _body(dfg, _stride(init_banks))


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
        fn = cache[key] = _lowered(dfg, n_iters=n_iters, bits=bits,
                                   banks=banks, li_names=li_names)

    stride = _stride(init_banks)
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
