"""The DS-CNN keyword-spotting layers (``repro.frontend.layers``) on the
paper's 8x8 target, four clusters of 4x4 with two banks each, at small
sizes: each partition maps onto its own cluster, and the scan, the VMEM
body in the Pallas interpreter (one-hot planes resident, and built in the
kernel) and the DFG oracle all leave the images a plain NumPy reference
computes from the same seeded draws.  The three kernels are clean under
the static checker and cross-validate against the instruction-stream
interpreter.  Last, which body and plane mode a 64-PE launch takes.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.check import errors, check_kernel
from repro.core import simcache, simulator
from repro.core.adl import CGRAArch, MemBank, morpher_8x8
from repro.core.router import Usage
from repro.core.toolchain import CompiledKernel, Toolchain
from repro.core.verify import generate_test_data, reference_banks_batch
from repro.frontend.layers import (build_conv2d, build_dwconv_layer,
                                   build_pwconv)
from repro.isa.xval import cross_validate

SMALL = {
    "conv2d": (build_conv2d, dict(H=12, W=6, C_out=8)),
    "dwconv": (build_dwconv_layer, dict(H=5, W=5, C=8)),
    "pwconv": (build_pwconv, dict(N=10, C_in=8, C_out=8)),
}


def _relu16(x):
    x = ((np.asarray(x, np.int64) + (1 << 15)) & 0xFFFF) - (1 << 15)
    return np.maximum(x, 0)


def _reference(kind, kw, seed):
    """{array: words} of every partition's inputs and output, from the
    whole-layer tensors the seed draws (inputs HWC, weights HWIO)."""
    rng = np.random.default_rng(seed)
    n = 4
    if kind == "conv2d":
        H, W, C, KH, KW, s = kw["H"], kw["W"], kw["C_out"], 10, 4, 2
        x = rng.integers(-8, 8, size=(H, W))
        w = rng.integers(-4, 4, size=(KH, KW, C))
        b = rng.integers(-64, 64, size=C)
        OH, OW = -(-H // s), -(-W // s)
        ph, pw = (OH - 1) * s + KH - H, (OW - 1) * s + KW - W
        xp = np.pad(x, ((ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2)))
        y = np.zeros((OH, OW, C), np.int64)
        for i in range(OH):
            for j in range(OW):
                win = xp[s * i:s * i + KH, s * j:s * j + KW]
                y[i, j] = np.einsum("hw,hwc->c", win, w)
        y = _relu16(y + b)
        cp = C // n
        return {**{f"I{k}": xp.ravel() for k in range(n)},
                **{f"W{k}": np.moveaxis(w[:, :, k * cp:(k + 1) * cp], 2, 0)
                   .ravel() for k in range(n)},
                **{f"B{k}": b[k * cp:(k + 1) * cp] for k in range(n)},
                **{f"O{k}": np.moveaxis(y[:, :, k * cp:(k + 1) * cp], 2, 0)
                   .ravel() for k in range(n)}}
    if kind == "dwconv":
        H, W, C = kw["H"], kw["W"], kw["C"]
        x = rng.integers(-8, 8, size=(H, W, C))
        w = rng.integers(-4, 4, size=(3, 3, C))
        b = rng.integers(-64, 64, size=C)
        xp = np.pad(x, ((1, 1), (1, 1), (0, 0)))
        y = sum(xp[a:a + H, d:d + W] * w[a, d] for a in range(3)
                for d in range(3))
        y = _relu16(y + b)
        cp = C // n
        part = {}
        for k in range(n):
            ch = slice(k * cp, (k + 1) * cp)
            part.update({f"I{k}": np.moveaxis(xp[:, :, ch], 2, 0).ravel(),
                         f"W{k}": np.moveaxis(w[:, :, ch], 2, 0).ravel(),
                         f"B{k}": b[ch],
                         f"O{k}": np.moveaxis(y[:, :, ch], 2, 0).ravel()})
        return part
    N, Ci, Co = kw["N"], kw["C_in"], kw["C_out"]
    x = rng.integers(-8, 8, size=(N, Ci))
    w = rng.integers(-4, 4, size=(Ci, Co))
    b = rng.integers(-64, 64, size=Co)
    y = _relu16(x @ w + b)
    rows = [slice(0, -(-N // 2)), slice(-(-N // 2), N)]
    cols = [slice(0, Co // 2), slice(Co // 2, Co)]
    part = {}
    for k, (a, d) in enumerate([(0, 0), (0, 1), (1, 0), (1, 1)]):
        part.update({f"X{k}": x[rows[a]].ravel(), f"W{k}": w[:, cols[d]].T
                     .ravel(), f"B{k}": b[cols[d]],
                     f"O{k}": y[rows[a], cols[d]].ravel()})
    return part


@functools.lru_cache(maxsize=None)
def _compiled(kind):
    build, kw = SMALL[kind]
    return Toolchain(cache_dir="").compile(build(arch=morpher_8x8(), **kw))


def _images(kind, seed):
    """(initial, final) bank images the reference gives for one seed."""
    ck = _compiled(kind)
    arrays = _reference(kind, SMALL[kind][1], seed)
    init = {f"bank{b.id}": np.zeros(b.words, np.int64) for b in ck.arch.banks}
    final = {k: v.copy() for k, v in init.items()}
    for name, p in ck.layout.placements.items():
        final[p.bank_array][p.base:p.base + p.words] = arrays[name]
        if not name.startswith("O"):
            init[p.bank_array][p.base:p.base + p.words] = arrays[name]
    return init, final


@pytest.mark.parametrize("kind", sorted(SMALL))
def test_layer_maps_one_partition_per_cluster(kind):
    ck = _compiled(kind)
    arch = ck.arch
    assert ck.II >= ck.mii and ck.II <= 32
    banks = {p.bank for p in ck.layout.placements.values()}
    assert banks == {b.id for b in arch.banks}          # all eight buses
    assert ck.mii == max(sum(1 for n in ck.dfg.nodes.values()
                             if n.is_mem and n.array == f"bank{b}")
                         for b in banks)               # bank-bound MII
    cluster_of = {pe: ci for ci, pes in enumerate(arch.clusters)
                  for pe in pes}
    for v, (pe, _t) in ck.mapping.place.items():
        n = ck.dfg.nodes[v]
        if n.is_mem:
            assert pe in arch.pes_of_bank(int(n.array[4:]))
    # every component of the DFG lies on one cluster
    for name, p in ck.layout.placements.items():
        k = int(name[1:])
        assert p.bank in arch.cluster_banks()[k]
    used = {cluster_of[pe] for pe, _t in ck.mapping.place.values()}
    assert used == set(range(4))
    again = CompiledKernel.from_json(ck.to_json())
    assert again.mapping.to_json_dict() == ck.mapping.to_json_dict()


def test_bank_ids_pack_apart_from_other_resources():
    """A cluster's banks keep their fabric ids (here 6 and 7 of a 2-bank
    fabric): each packs to a resource of its own."""
    arch = CGRAArch("c", 2, 2, banks=[MemBank(7, 64, (0,)),
                                      MemBank(6, 64, (3,))])
    u = Usage(arch, 3)
    packed = [u.tables.pack(k) for k in
              [("bank", b, s) for b in (6, 7) for s in range(3)]
              + [("lireg", p) for p in range(4)]
              + [("fu", p, s) for p in range(4) for s in range(3)]]
    assert len(set(packed)) == len(packed)


@pytest.mark.parametrize("kind", sorted(SMALL))
def test_layer_matches_numpy_reference(kind):
    """Test data, golden model, DFG oracle and the batched scan against
    the plain reference, two seeds."""
    ck = _compiled(kind)
    seeds = [3, 2 ** 40 + 7]
    for s in seeds:
        init, final = _images(kind, s)
        data = generate_test_data(ck.spec, s)
        for bank in init:
            np.testing.assert_array_equal(data.init_banks[bank], init[bank])
            np.testing.assert_array_equal(data.expected_banks[bank],
                                          final[bank])
    stacked = {b: np.stack([_images(kind, s)[0][b] for s in seeds])
               for b in _images(kind, seeds[0])[0]}
    oracle = reference_banks_batch(ck.dfg, stacked, ck.invocations,
                                   ck.mapped_iters, 16)
    sims = ck.run_batch([_images(kind, s)[0] for s in seeds])
    for row, s in enumerate(seeds):
        want = _images(kind, s)[1]
        for bank, words in want.items():
            np.testing.assert_array_equal(np.asarray(oracle[bank])[row],
                                          words)
            np.testing.assert_array_equal(sims[row][bank], words)
    ck.verify_batch(seeds)


@pytest.mark.parametrize("planes", ["resident", "built"])
@pytest.mark.parametrize("kind", sorted(SMALL))
def test_layer_vmem_body_matches_scan(monkeypatch, kind, planes):
    monkeypatch.setattr(simulator, "_vmem_planes", lambda *shapes: planes)
    ck = _compiled(kind)
    cfg = ck.cfg
    assert cfg.P == 64
    mem = np.stack([simulator._banks_to_mem(cfg, _images(kind, s)[0])
                    for s in (5, 6)])
    li = np.stack([cfg.livein_array(inv) for inv in ck.invocations])
    static = dict(II=cfg.II, P=cfg.P, RF=cfg.RF, bits=cfg.bits,
                  n_iters=ck.mapped_iters, n_cycles=simcache.bucket_cycles(
                      cfg.n_cycles(ck.mapped_iters)))
    args = (simulator._as_jnp(cfg), jnp.asarray(mem), jnp.asarray(li))
    scan = np.asarray(jax.jit(functools.partial(
        simulator._sim_body, **static))(*args))
    vmem = np.asarray(jax.jit(functools.partial(
        simulator._vmem_sim, interpret=True,
        pass_words=simulator._pass_words(cfg), **static))(*args))
    np.testing.assert_array_equal(vmem, scan)
    for row, s in enumerate((5, 6)):
        want = simulator._banks_to_mem(cfg, _images(kind, s)[1])
        np.testing.assert_array_equal(scan[row], want)


@pytest.mark.parametrize("kind", sorted(SMALL))
def test_layer_clean_and_cross_validated(kind):
    ck = _compiled(kind)
    assert errors(check_kernel(ck)) == []
    assert cross_validate(ck, seeds=(0, 1)) == 2


@pytest.mark.parametrize("P,W,II,n_inv,planes", [
    (64, 32769, 9, 2016, "resident"),   # the kws layers at their II
    (64, 32769, 16, 2016, "built"),     # the planes alone pass the budget
    (64, 32769, 32, 2016, "built"),
    (16, 8193, 13, 11532, "resident"),  # Table-I CONV at the paper's size
    (16, 8193, 32, 11532, "resident"),
])
def test_body_and_planes_by_shape(monkeypatch, P, W, II, n_inv, planes):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert simulator._body(False, 8, W, P, 8, 4, II, n_inv) == "vmem"
    assert simulator._vmem_planes(8, W, P, 8, 4, II, n_inv) == planes
    # a P=16 shape takes the body and the resident planes exactly where
    # its resident footprint fits the budget, as before
    if P == 16:
        assert simulator._vmem_bytes(8, W, P, 8, 4, II, n_inv) \
            <= simulator._VMEM_BUDGET
