"""The simulator's VMEM-resident Pallas body (``simulator._vmem_sim``, the
body a TPU backend runs) against the scan body (``simulator._sim_body``)
word for word, in the Pallas interpreter on the CPU.

Small-dim Table-I kernels: base, unrolled and coalesced variants, many
short invocations and one long one, over the bucketed cycle count (padded
cycles past the store window).  Each runs at batch 1 on the kernel's own
test data, and at batch 8 on images and live-ins drawn over the whole
int16 range: there addresses leave their banks at both ends and are
clipped, and MULs overflow 16 bits.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import simcache, simulator
from repro.core.kernels_lib import table1_kernels
from repro.core.toolchain import Toolchain
from repro.core.verify import generate_test_data

# GEMM-U-C is left out only for its mapping time on the CPU; its body is
# the same as CONV-U-C-2's (one long coalesced invocation)
KERNELS = ["GEMM", "GEMM-U", "CONV", "CONV-U-C-1", "CONV-U-C-2"]


@functools.lru_cache(maxsize=None)
def _compiled(name):
    return Toolchain(cache_dir="").compile(table1_kernels(small=True)[name])


def _inputs(ck, batch, extreme):
    cfg = ck.cfg
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


@pytest.mark.parametrize("name", KERNELS)
@pytest.mark.parametrize("batch,extreme", [(1, False), (8, True)])
def test_vmem_body_matches_scan(name, batch, extreme):
    ck = _compiled(name)
    cfg = ck.cfg
    mem, li = _inputs(ck, batch, extreme)
    real = cfg.n_cycles(ck.mapped_iters)
    static = dict(II=cfg.II, P=cfg.P, RF=cfg.RF, bits=cfg.bits,
                  n_iters=ck.mapped_iters,
                  n_cycles=simcache.bucket_cycles(real))
    planes = simulator._as_jnp(cfg)
    args = (planes, jnp.asarray(mem), jnp.asarray(li))
    scan = np.asarray(jax.jit(functools.partial(
        simulator._sim_body, **static))(*args))
    vmem = np.asarray(jax.jit(functools.partial(
        simulator._vmem_sim, interpret=True, **static))(*args))
    assert vmem.dtype == scan.dtype and vmem.shape == scan.shape
    np.testing.assert_array_equal(vmem, scan)
    assert (scan != mem).any()          # the kernel did store something


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
