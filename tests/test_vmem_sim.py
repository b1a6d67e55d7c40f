"""The simulator's VMEM-resident Pallas body (``simulator._vmem_sim``, the
body a TPU backend runs) against the scan body (``simulator._sim_body``)
word for word, in the Pallas interpreter on the CPU.

Small-dim Table-I kernels: base, unrolled and coalesced variants, many
short invocations and one long one, over the bucketed cycle count (padded
cycles past the store window).  Each runs at batch 1 on the kernel's own
test data, and at batch 8 on images and live-ins drawn over the whole
int16 range: there addresses leave their banks at both ends and are
clipped, and MULs overflow 16 bits.  The kernel's LOAD and STORE passes
cover one bank-sized window of the image (``simulator._pass_words``), as
a launch runs them; on a fabric whose banks differ in size and start off
the 128-lane grid too.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import simcache, simulator
from repro.core.adl import CGRAArch, MemBank
from repro.core.kernels_lib import table1_kernels
from repro.core.toolchain import Toolchain
from repro.core.verify import generate_test_data

# GEMM-U-C is left out only for its mapping time on the CPU; its body is
# the same as CONV-U-C-2's (one long coalesced invocation)
KERNELS = ["GEMM", "GEMM-U", "CONV", "CONV-U-C-1", "CONV-U-C-2"]


def _uneven_4x4(words) -> CGRAArch:
    """A 4x4 cluster with three banks of ``words`` words each: the two
    the kernels use on the boundary columns, then one more."""
    left = tuple(r * 4 for r in range(4))
    right = tuple(r * 4 + 3 for r in range(4))
    arch = CGRAArch(name="uneven-banks-4x4", rows=4, cols=4,
                    banks=[MemBank(0, 2 * words[0], left),
                           MemBank(1, 2 * words[1], right),
                           MemBank(2, 2 * words[2], left[:1])],
                    clusters=[list(range(16))])
    arch.validate()
    return arch


@functools.lru_cache(maxsize=None)
def _compiled(name, words=None):
    arch = _uneven_4x4(words) if words else None
    return Toolchain(cache_dir="").compile(
        table1_kernels(small=True, arch=arch)[name])


def _inputs(ck, batch, extreme, cfg):
    li = np.stack([cfg.livein_array(inv) for inv in ck.invocations])
    if not extreme:
        mem = np.stack([simulator._banks_to_mem(
            cfg, generate_test_data(ck.spec, s).init_banks)
            for s in range(batch)])
        return mem, li
    rng = np.random.default_rng(len(ck.name))
    mem = rng.integers(-2 ** 15, 2 ** 15, size=(batch, cfg.total_words),
                       dtype=np.int16)
    li = rng.integers(-2 ** 15, 2 ** 15, size=li.shape).astype(li.dtype)
    return mem, li


def _matches_scan(ck, batch, extreme, cfg=None):
    cfg = cfg or ck.cfg
    mem, li = _inputs(ck, batch, extreme, cfg)
    real = cfg.n_cycles(ck.mapped_iters)
    static = dict(II=cfg.II, P=cfg.P, RF=cfg.RF, bits=cfg.bits,
                  n_iters=ck.mapped_iters,
                  n_cycles=simcache.bucket_cycles(real))
    planes = simulator._as_jnp(cfg)
    args = (planes, jnp.asarray(mem), jnp.asarray(li))
    scan = np.asarray(jax.jit(functools.partial(
        simulator._sim_body, **static))(*args))
    vmem = np.asarray(jax.jit(functools.partial(
        simulator._vmem_sim, interpret=True,
        pass_words=simulator._pass_words(cfg), **static))(*args))
    assert vmem.dtype == scan.dtype and vmem.shape == scan.shape
    np.testing.assert_array_equal(vmem, scan)
    assert (scan != mem).any()          # the kernel did store something


@pytest.mark.parametrize("name", KERNELS)
@pytest.mark.parametrize("batch,extreme", [(1, False), (8, True)])
def test_vmem_body_matches_scan(name, batch, extreme):
    ck = _compiled(name)
    assert simulator._pass_words(ck.cfg) == 4096     # one 8 kB bank
    _matches_scan(ck, batch, extreme)


def _relaid(cfg, order, words):
    """``cfg`` with its banks laid out in the image in ``order`` (bank
    ids), so the bank the kernels store to need not start at word 0."""
    offsets, off = {}, 0
    for b in order:
        offsets[b], off = off, off + words[b]
    moved = {cfg.bank_offsets[b]: offsets[b] for b in order}
    mem_off = np.where(cfg.mem_words > 1,
                       np.vectorize(moved.get)(cfg.mem_off), 0)
    return dataclasses.replace(cfg, mem_off=mem_off.astype(np.int32),
                               bank_offsets=offsets, total_words=off + 1)


# banks of unequal size at offsets off the 128-lane grid:
# - 350, 100, 30 words at 0, 350, 450: the window of bank 1 (384 lanes,
#   for bank 0) would run past the image's 512 lanes from its offset
#   aligned down (256), and is pulled back to start at 128;
# - 100, 350, 200 words laid out in reverse, at 550, 200, 0: bank 1, the
#   widest, starts at 200, so its window needs 422 lanes (512), not its
#   own 350 (384); bank 0, which the kernels store to, has its window
#   pulled back from 512 to 256;
# - 300, 100, 30 words at 100, 0, 400: bank 0 runs from lane 100 to 400,
#   so its window takes the image's whole 512 lanes and every window
#   starts at lane 0.
@pytest.mark.parametrize("words,order,offsets,pass_words,image_lanes", [
    ((350, 100, 30), (0, 1, 2), (0, 350, 450), 384, 512),
    ((100, 350, 200), (2, 1, 0), (550, 200, 0), 512, 768),
    ((300, 100, 30), (1, 0, 2), (100, 0, 400), 512, 512),
], ids=["last_window_clamped", "widest_bank_unaligned", "window_is_image"])
@pytest.mark.parametrize("name", ["GEMM", "CONV-U-C-1"])
def test_vmem_body_matches_scan_uneven_banks(name, words, order, offsets,
                                             pass_words, image_lanes):
    ck = _compiled(name, words)
    cfg = _relaid(ck.cfg, order, words)
    assert cfg.bank_offsets == dict(enumerate(offsets))
    assert set(cfg.mem_off[cfg.mem_words > 1].tolist()) == set(offsets[:2])
    assert simulator._pass_words(cfg) == pass_words
    assert simulator._lanes(cfg.total_words) == image_lanes
    _matches_scan(ck, 8, True, cfg)


@pytest.mark.parametrize("backend,multi,n_inv,body", [
    ("tpu", False, 11532, "vmem"),       # Table-I CONV at the paper's size
    ("tpu", True, 11532, "scan"),        # stacked planes keep the scan
    ("tpu", False, 10 ** 6, "scan"),     # live-ins past the VMEM budget
    ("cpu", False, 1, "scan"),
])
def test_body_dispatch(monkeypatch, backend, multi, n_inv, body):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert simulator._body(multi, 8, 8193, 16, 8, 4, 13, n_inv) == body
    fits = simulator._vmem_bytes(8, 8193, 16, 8, 4, 13, n_inv) \
        <= simulator._VMEM_BUDGET
    assert fits == (n_inv < 10 ** 6)
