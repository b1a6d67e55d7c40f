"""Functional verification flow (paper section IV-C).

Morpher instruments the application to record live-in variables (arrays,
outer-loop iteration variables) and live-out arrays by running it on a
general-purpose processor, then checks the post-simulation memory content
against the expected results.  The same three-step contract here:

  1. *test-data generation*: initialize bank images, record the live-in
     values of every host invocation, and compute expected live-outs with
     the kernel's golden (numpy) model;
  2. additionally cross-check the DFG itself by dataflow execution on the
     JAX DFG oracle (``repro.core.refexec``, every seed in one pass) —
     this separates "the DFG is the right program" from "the mapping
     executes the DFG correctly";
  3. simulate the mapped configuration cycle-by-cycle and compare the
     final memory images word-for-word.

The one engine is ``CompiledKernel.verify_batch`` (`repro.core.toolchain`),
and ``verify(seed)`` is its batch of one; this module provides the
test-data generator and the DFG oracle and cross-check it uses, plus the
deprecated ``verify_mapping`` shim.
"""
from __future__ import annotations

import os
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import obs
from .config_gen import SimConfig, generate_config
from .kernels_lib import KernelSpec
from .mapper import Mapping


def xval_enabled() -> bool:
    """Opt-in second oracle: ``MORPHER_XVAL=1`` routes every verify through
    the exported instruction stream + standalone interpreter
    (``repro.isa.xval``) in addition to the simulator comparison, so a
    verify pass additionally certifies the deployment artifact."""
    return os.environ.get("MORPHER_XVAL", "") == "1"


def check_enabled() -> bool:
    """Opt-in static gate: ``MORPHER_CHECK=1`` runs the ``repro.check``
    static legality checker at the top of every verify (and as a DSE
    pre-screen).  Clean compiled artifacts must be diagnostic-free — the
    PR-10 contract — so under this gate a verify additionally certifies
    the artifact's structural/temporal legality without extra simulation."""
    return os.environ.get("MORPHER_CHECK", "") == "1"


@dataclass
class TestData:
    init_banks: Dict[str, np.ndarray]
    expected_banks: Dict[str, np.ndarray]


@dataclass
class TestDataBatch:
    """All test vectors of one batched verification up front: bank images
    stacked along a leading seed axis, one row per seed."""
    seeds: List[int]
    init_banks: Dict[str, np.ndarray]       # [batch, words]
    expected_banks: Dict[str, np.ndarray]   # [batch, words]

    def init_row(self, i: int) -> Dict[str, np.ndarray]:
        return {k: v[i] for k, v in self.init_banks.items()}


def generate_test_data(spec: KernelSpec, seed: int = 0) -> TestData:
    rng = np.random.default_rng(seed)
    init = spec.init_banks(rng)
    expected = spec.golden(init)
    return TestData(init_banks=init, expected_banks=expected)


def generate_test_data_batch(spec: KernelSpec,
                             seeds: Sequence[int]) -> TestDataBatch:
    """Test vectors for every seed, stacked for the batched engine.

    Each row is drawn from that seed's own rng stream — bit-identical to
    ``generate_test_data(spec, seed)`` — so batched and sequential verify
    see the very same images; the numpy golden models are cheap, it is the
    DFG oracle and the simulator that are batch-vectorized downstream.
    """
    if not len(seeds):
        return TestDataBatch(seeds=[], init_banks={}, expected_banks={})
    datas = [generate_test_data(spec, s) for s in seeds]
    names = list(datas[0].init_banks)
    return TestDataBatch(
        seeds=list(seeds),
        init_banks={k: np.stack([np.asarray(d.init_banks[k])
                                 for d in datas]) for k in names},
        expected_banks={k: np.stack([np.asarray(d.expected_banks[k])
                                     for d in datas]) for k in names})


def reference_banks_batch(dfg, init_banks, invocations, mapped_iters: int,
                          bits: int) -> Dict[str, np.ndarray]:
    """The DFG oracle: final bank images after every invocation of the
    loop, for each row of ``init_banks`` ([batch, words] per bank) — one
    pass of the JAX-lowered DFG executor (``repro.core.refexec``) for the
    whole batch and invocation sweep.  Tests pin it word for word to
    ``DFG.reference_execute`` folded over the invocations, row by row;
    production calls only this."""
    from .refexec import oracle_body, reference_execute_jax
    rows = len(next(iter(init_banks.values()))) if init_banks else 0
    with obs.span("morpher.oracle", rows=rows,
                  body=oracle_body(dfg, init_banks),
                  steps=len(invocations) * mapped_iters):
        return reference_execute_jax(dfg, mapped_iters, init_banks,
                                     invocations, bits=bits)


def check_dfg_semantics_batch(spec: KernelSpec, data: TestDataBatch) -> None:
    """Step 2 over a whole seed batch in one vectorized oracle pass."""
    banks = reference_banks_batch(spec.dfg, data.init_banks,
                                  spec.invocations, spec.mapped_iters,
                                  spec.arch.datapath_bits)
    for name, exp in data.expected_banks.items():
        got = np.asarray(banks[name])
        exp = np.asarray(exp)
        if not np.array_equal(got, exp):
            row = int(np.nonzero(got != exp)[0][0])
            bad = np.nonzero(got[row] != exp[row])[0][:8]
            raise AssertionError(
                f"{spec.name}: DFG reference mismatch for seed "
                f"{data.seeds[row]} in {name} at words {bad.tolist()}: "
                f"got {got[row][bad]}, want {exp[row][bad]}")


def verify_mapping(spec: KernelSpec, mapping: Optional[Mapping] = None,
                   cfg: Optional[SimConfig] = None, seed: int = 0,
                   check_dfg: bool = True) -> Mapping:
    """Deprecated shim — use ``Toolchain.compile(spec).verify(seed)``.

    Returns the (possibly freshly computed) mapping; raises AssertionError
    on any mismatch, exactly as before.
    """
    warnings.warn(
        "verify_mapping(spec, ...) is deprecated; use "
        "repro.core.toolchain.Toolchain.compile(spec).verify(seed)",
        DeprecationWarning, stacklevel=2)
    from .mapper import MapperOptions
    from .toolchain import CompiledKernel, Toolchain
    # legacy semantics exactly: a fresh map with the old map_kernel default
    # (ii_max=64) and no artifact-cache involvement
    legacy = MapperOptions(ii_max=64)
    if mapping is None:
        ck = Toolchain(options=legacy, cache_dir="").compile(spec)
    else:
        ck = CompiledKernel(
            name=spec.name, arch=spec.arch, dfg=spec.dfg, layout=spec.layout,
            mapping=mapping, cfg=cfg or generate_config(mapping, spec.layout),
            mapped_iters=spec.mapped_iters, invocations=spec.invocations,
            meta=dict(spec.meta), options=legacy, cache_key="", spec=spec)
    ck.verify(seed=seed, check_dfg=check_dfg)
    return ck.mapping
