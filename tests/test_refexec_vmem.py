"""The DFG oracle's VMEM-resident Pallas body (``refexec._vmem_body``, the
body a TPU backend runs) against its scan body (``refexec._scan_body``)
and the plain reference (``DFG.reference_execute`` folded over the
invocations, row by row), word for word, in the Pallas interpreter on the
CPU.

Every Table-I kernel at small dims and the three KWS DS-CNN layers at
published widths (their invocation lists cut short), at batch 1 on the
kernel's own test data and at batch 8 and 11 (rows not a multiple of 8)
on images and live-ins drawn over the whole int16 range: there addresses
leave their banks at both ends and MULs overflow 16 bits.  A hand-built
DFG adds every ALU op on both node classes and loop-carried operands of
distance 2; ``_body`` is checked for the dispatch rule.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import refexec
from repro.core.dfg import DFG, Node, Op, Operand, wrap
from repro.core.kernels_lib import table1_kernels
from repro.core.verify import generate_test_data_batch
from repro.frontend import layers

from dfg_reference import reference_rows

TABLE1 = ["GEMM", "GEMM-U", "GEMM-U-C", "CONV", "CONV-U-C-1", "CONV-U-C-2"]
KWS = ["build_conv2d", "build_dwconv_layer", "build_pwconv"]
KWS_INVOCATIONS = 2


@functools.lru_cache(maxsize=None)
def _spec(name):
    if name in KWS:
        return getattr(layers, name)()
    return table1_kernels(small=True)[name]


def _banks(spec, batch, extreme):
    if not extreme:
        return generate_test_data_batch(spec, list(range(batch))).init_banks
    rng = np.random.default_rng(batch)
    return {f"bank{bid}": rng.integers(-2 ** 15, 2 ** 15, size=(batch, w))
            for bid, w in sorted(spec.layout.bank_image_size().items())}


def _extreme_invocations(invocations, seed):
    """The invocations, then as many again (at most four) with live-ins
    drawn over the whole int16 range."""
    rng = np.random.default_rng(seed)
    return list(invocations) + [
        {k: int(rng.integers(-2 ** 15, 2 ** 15)) for k in inv}
        for inv in invocations[:4]]


def _bodies(dfg, n_iters, init_banks, invocations, bits):
    """The initial image and the final images of the scan, the kernel
    (interpreted) and the plain reference, each [batch, words]:
    the banks in name order, without the scan's dump cell for dropped
    stores, which no caller reads."""
    names = sorted(init_banks)
    banks = tuple((k, init_banks[k].shape[1]) for k in names)
    li_names = tuple(sorted({n.livein for n in dfg.nodes.values()
                             if n.op == Op.LIVEIN}))
    p = refexec._Program.of(dfg, n_iters=n_iters, bits=bits, banks=banks,
                            li_names=li_names)
    B = init_banks[names[0]].shape[0]
    mem0 = np.zeros((B, p.stride), np.int32)
    mem0[:, :-1] = np.concatenate([init_banks[k] for k in names], axis=1)
    li = np.array([[wrap(inv[n], bits) for n in li_names]
                   for inv in invocations],
                  np.int32).reshape(len(invocations), len(li_names))
    args = (jnp.asarray(mem0.reshape(-1)), jnp.asarray(li))
    scan = np.asarray(jax.jit(functools.partial(
        refexec._scan_body, p))(*args)).reshape(B, -1)
    vmem = np.asarray(jax.jit(functools.partial(
        refexec._vmem_body, p, interpret=True))(*args)).reshape(B, -1)
    ref = reference_rows(dfg, init_banks, invocations, n_iters, bits)
    ref = np.concatenate([ref[k] for k in names], axis=1)
    return mem0[:, :-1], scan[:, :-1], vmem[:, :-1], ref


@pytest.mark.parametrize("name", TABLE1 + KWS)
@pytest.mark.parametrize("batch,extreme", [(1, False), (8, True),
                                           (11, True)])
def test_vmem_body_matches_scan(name, batch, extreme):
    spec = _spec(name)
    invocations = spec.invocations
    if name in KWS:
        invocations = invocations[:KWS_INVOCATIONS]
    if extreme:
        invocations = _extreme_invocations(invocations, batch)
    assert refexec._eligible(spec.dfg)
    mem0, scan, vmem, ref = _bodies(
        spec.dfg, spec.mapped_iters, _banks(spec, batch, extreme),
        invocations, spec.arch.datapath_bits)
    assert vmem.dtype == scan.dtype and vmem.shape == scan.shape
    np.testing.assert_array_equal(vmem, scan)
    np.testing.assert_array_equal(scan, ref)
    assert (scan != mem0).any()          # the kernel did store something


def _every_op_dfg(load_address: bool = False) -> DFG:
    """Every ALU op once on uniform operands (a counter, a live-in) and
    once on data operands (loaded words), with operands carried over 1
    and 2 iterations; addresses from the uniform side only, unless
    ``load_address``, where a STORE's address is a loaded word."""
    dfg = DFG("every_op")
    nodes = []

    def add(op, *operands, **kw):
        nid = len(nodes)
        ops = tuple(o if isinstance(o, Operand) else Operand(o)
                    for o in operands)
        nodes.append(Node(nid, op, ops, **kw))
        return nid

    one = add(Op.CONST, imm=1)
    mask = add(Op.CONST, imm=15)
    base = add(Op.LIVEIN, livein="base")
    i = add(Op.ADD, Operand(3, dist=1, init=-1), one)       # 0, 1, 2, ...
    addr = add(Op.ADD, base, i)
    x = add(Op.LOAD, addr, array="bank0")
    y = add(Op.LOAD, Operand(addr, dist=2, init=-3), array="bank1")
    acc = x
    for op in (Op.ADD, Op.SUB, Op.MUL, Op.SHL, Op.SHR, Op.AND, Op.OR,
               Op.XOR, Op.CMPGE, Op.CMPEQ, Op.CMPLT):
        u = add(op, Operand(addr, dist=2, init=7), base)    # uniform
        d = add(op, Operand(x, dist=2, init=-5), y)         # data
        m = add(op, d, u)                                   # mixed
        acc = add(Op.ADD, acc, add(Op.XOR, m, u))
        add(Op.STORE, add(Op.ADD, base, add(Op.AND, u, mask)), u,
            array="bank1")
    sel_u = add(Op.SELECT, add(Op.CMPLT, i, one), base, addr)
    sel_d = add(Op.SELECT, x, Operand(acc, dist=2, init=9), sel_u)
    add(Op.STORE, Operand(addr, dist=1, init=-1), sel_d, array="bank0")
    add(Op.STORE, x if load_address else sel_u, acc, array="bank2")
    dfg.nodes = {n.id: n for n in nodes}
    dfg.validate()
    return dfg


@pytest.mark.parametrize("batch", [1, 11])
def test_every_op_and_distance_two(batch):
    dfg = _every_op_dfg()
    assert refexec._eligible(dfg)
    uni = refexec._uniform(dfg)
    assert {3, 4} <= uni and not {5, 6} & uni
    rng = np.random.default_rng(batch)
    banks = {f"bank{k}": rng.integers(-2 ** 15, 2 ** 15, size=(batch, 40))
             for k in range(3)}
    invocations = [{"base": b} for b in (0, 30, -4, 2 ** 15 - 1)]
    mem0, scan, vmem, ref = _bodies(dfg, 9, banks, invocations, 16)
    np.testing.assert_array_equal(vmem, scan)
    np.testing.assert_array_equal(scan, ref)
    assert (scan != mem0).any()


@pytest.mark.parametrize("backend,dfg,words,body", [
    ("cpu", "eligible", 8193, "scan"),
    ("tpu", "eligible", 8193, "vmem"),
    ("tpu", "load_address", 8193, "scan"),      # address fed by a LOAD
    ("tpu", "eligible", 1 << 24, "scan"),       # image past the budget
    ("tpu", "kws_dw", 32769, "vmem"),
])
def test_body_dispatch(monkeypatch, backend, dfg, words, body):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    g = {"eligible": lambda: _every_op_dfg(),
         "load_address": lambda: _every_op_dfg(load_address=True),
         "kws_dw": lambda: _spec("build_dwconv_layer").dfg}[dfg]()
    assert refexec._body(g, words) == body
    assert refexec._eligible(g) == (dfg != "load_address")
