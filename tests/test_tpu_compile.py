"""Compiles for a described TPU v5e chip: the device programs of the main
path at real sizes go through the chip's own compiler, with no chip
attached.  Nothing runs; a compiler refusal (layout, VMEM, memory) fails
here instead of on the chip.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU compiler.  JAX's persistent compile cache is off
around the compiles: an entry compiled for a described chip cannot be read
back without one.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core import simcache
from repro.core.dfg import Op
from repro.core.kernels_lib import table1_kernels
from repro.core.refexec import _lowered
from repro.core.simulator import (_body, _build_batched, _host_planes,
                                  _signature, _stack_planes)
from repro.core.toolchain import Toolchain
from repro.kernels.gemm_os.kernel import gemm_os_pallas
from repro.models.zoo import build_model


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def chip_backend(monkeypatch):
    # the simulator and the oracle donate their image buffer only off the
    # CPU backend; compile the branch the chip takes
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def paper_gemm():
    return Toolchain(cache_dir="").compile(table1_kernels()["GEMM"])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            np.shape(a), jax.dtypes.canonicalize_dtype(np.asarray(a).dtype),
            sharding=sharding), tree)


def _compile_batch8(ck, sharding):
    """The batch-8 executable of a compiled kernel, as ``simulate_batch``
    launches it (LOAD and STORE passes over one bank's window), compiled
    for the described chip; its text."""
    cfg, n_inv = ck.cfg, len(ck.invocations)
    sig = _signature(cfg, ck.mapped_iters, simcache.bucket_cycles(
        cfg.n_cycles(ck.mapped_iters)), batch=8)
    mem = np.zeros((8, cfg.total_words), np.int16)
    li = np.zeros((n_inv, cfg.P, max(1, cfg.LI)), np.int32)
    assert _body(False, 8, cfg.total_words, cfg.P, cfg.RF, max(1, cfg.LI),
                 cfg.II, n_inv) == "vmem"         # fits the VMEM budget
    return _build_batched(sig).lower(
        _shapes(_host_planes(cfg), sharding),
        *_shapes((mem, li), sharding)).compile().as_text()


def test_simulator_paper_gemm_batch8(one_chip, chip_backend, paper_gemm):
    # the launch's ``pass_words``: one 4,096-word bank of the 8,320 lanes
    assert _signature(paper_gemm.cfg, 1, 1, 8).pass_words == 4096
    text = _compile_batch8(paper_gemm, one_chip)
    assert "tpu_custom_call" in text                    # the VMEM kernel
    assert "input_output_alias" in text                 # image donated


@pytest.mark.parametrize("name,invocations,steps", [
    ("CONV", 11532, 11532 * 36), ("GEMM-U-C", 1, 212992)])
def test_simulator_paper_long_launches(one_chip, chip_backend, name,
                                       invocations, steps):
    ck = Toolchain(cache_dir="").compile(table1_kernels()[name])
    n_cycles = simcache.bucket_cycles(ck.cfg.n_cycles(ck.mapped_iters))
    assert (len(ck.invocations), len(ck.invocations) * n_cycles) == \
        (invocations, steps)
    text = _compile_batch8(ck, one_chip)
    assert "tpu_custom_call" in text and "input_output_alias" in text


@pytest.mark.parametrize("build,ii_start,planes", [
    ("build_conv2d", None, "resident"),   # the kws cell's CONV1 at its II
    ("build_pwconv", 16, "built"),        # 64 PEs past the resident budget
])
def test_simulator_kws_layers_64_pes(one_chip, chip_backend, build, ii_start,
                                     planes):
    from repro.core.mapper import MapperOptions
    from repro.core.simulator import _vmem_planes
    from repro.frontend import layers
    ck = Toolchain(cache_dir="", options=MapperOptions(ii_start=ii_start)) \
        .compile(getattr(layers, build)())
    cfg = ck.cfg
    assert cfg.P == 64
    assert _vmem_planes(8, cfg.total_words, cfg.P, cfg.RF, max(1, cfg.LI),
                        cfg.II, len(ck.invocations)) == planes
    # the launch's ``pass_words``: one 4,096-word bank of the 32,896 lanes
    assert _signature(cfg, 1, 1, 8).pass_words == 4096
    text = _compile_batch8(ck, one_chip)
    assert "tpu_custom_call" in text and "input_output_alias" in text


def test_simulator_stacked_multi(one_chip, chip_backend, paper_gemm):
    cfg, n_inv = paper_gemm.cfg, len(paper_gemm.invocations)
    rf = simcache.bucket_rf(cfg.RF)
    LI = max(1, cfg.LI)
    planes = _stack_planes([_host_planes(cfg, rf)] * 2, [4, 4])
    sig = simcache.SimSignature(
        II=cfg.II, P=cfg.P, RF=rf, bits=cfg.bits,
        n_iters=paper_gemm.mapped_iters,
        n_cycles=simcache.bucket_cycles(cfg.n_cycles(
            paper_gemm.mapped_iters)),
        batch=8, LI=LI, multi=True)
    mem = np.zeros((8, cfg.total_words), np.int16)
    li = np.zeros((n_inv, 8, cfg.P, LI), np.int32)
    text = _build_batched(sig).lower(
        _shapes(planes, one_chip), *_shapes((mem, li), one_chip)).compile() \
        .as_text()
    assert "tpu_custom_call" not in text        # multi keeps the scan


def test_refexec_oracle_table1_conv(one_chip, chip_backend):
    """The oracle's VMEM kernel at B=8 for Table-I CONV-U-C-1 at the
    paper's dims and for the KWS DW layer, the largest DFG of both cells
    (324 nodes, 76 loads)."""
    from repro.frontend import layers
    for spec in (table1_kernels()["CONV-U-C-1"],
                 layers.build_dwconv_layer()):
        banks = tuple(sorted((f"bank{bid}", w) for bid, w in
                             spec.layout.bank_image_size().items()))
        li_names = tuple(sorted({n.livein for n in spec.dfg.nodes.values()
                                 if n.op == Op.LIVEIN}))
        fn = _lowered(spec.dfg, n_iters=spec.mapped_iters,
                      bits=spec.arch.datapath_bits, banks=banks,
                      li_names=li_names)
        stride = sum(w for _, w in banks) + 1
        mem0 = np.zeros((8 * stride,), np.int32)
        li = np.zeros((len(spec.invocations), len(li_names)), np.int32)
        text = fn.lower(*_shapes((mem0, li), one_chip)).compile().as_text()
        assert "tpu_custom_call" in text, spec.name


def test_rwkv6_1_6b_decode_batch4(one_chip):
    model = build_model(get_config("rwkv6-1.6b"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    caches = jax.eval_shape(functools.partial(model.init_cache, 4, 128))
    ids = jax.ShapeDtypeStruct((4, 1), jnp.int32)
    lens = jax.ShapeDtypeStruct((4,), jnp.int32)
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (params, caches, ids, ids, lens))
    compiled = jax.jit(model.decode).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16e9


def test_gemm_os_bf16(one_chip):
    a = jax.ShapeDtypeStruct((1024, 2048), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((2048, 1024), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(gemm_os_pallas).lower(a, b).compile()
    assert "tpu_custom_call" in compiled.as_text()
