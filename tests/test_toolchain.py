"""Toolchain compile API: serializable CompiledKernel artifacts, the
content-addressed mapping cache, fan-out compiles, and the deprecation
shims for the old free-function flow."""
import json
import os

import numpy as np
import pytest

from repro.core.kernels_lib import build_conv, build_gemm
from repro.core.mapper import MapperOptions, map_kernel
from repro.core.toolchain import (CACHE_ENV, CompiledKernel, Toolchain,
                                  default_cache_dir, spec_cache_key)
from repro.core.verify import verify_mapping


def small_gemm():
    return build_gemm(TI=4, TK=4, TJ=4, unroll=1)


@pytest.fixture()
def tc(tmp_path):
    return Toolchain(options=MapperOptions(), cache_dir=str(tmp_path))


# ----------------------------------------------------------------- compile
def test_compile_produces_verified_artifact(tc):
    ck = tc.compile(small_gemm())
    assert ck.II >= ck.mii >= 1
    assert not ck.from_cache
    ck.verify()


def test_compile_many_matches_individual(tc):
    specs = [small_gemm(), build_conv(OH=5, OW=5, K=3, variant="base")]
    cks = tc.compile_many(specs, jobs=2)
    assert [ck.name for ck in cks] == [s.name for s in specs]
    solo = Toolchain(cache_dir="")
    for spec, ck in zip(specs, cks):
        assert ck.II == solo.compile(spec).II
    for ck in cks:
        ck.verify()     # process-pool results reassemble into working CKs


def test_compile_worker_never_imports_jax():
    # the parent may hold the chip; a pool worker that imported JAX could
    # initialise a backend and contend for it
    from pool_probe import compile_in_worker
    from repro.core import pool
    spec = small_gemm()
    payload = json.dumps({"dfg": spec.dfg.to_json_dict(),
                          "arch": json.loads(spec.arch.to_json()),
                          "layout": spec.layout.to_json_dict(),
                          "options": MapperOptions().to_json_dict()})
    outs = pool.process_map(compile_in_worker, [payload, payload])
    assert outs is not None, "no process pool: the fan-out went sequential"
    for out, jax_modules in outs:
        assert "mapping" in json.loads(out)
        assert jax_modules == []


def test_compile_many_dedups_identical_specs(tc):
    cks = tc.compile_many([small_gemm(), small_gemm()], jobs=2)
    assert cks[0] is cks[1]     # one compile served both indices


# ------------------------------------------------------------ serialization
def test_json_roundtrip_verifies_bit_exactly(tc):
    ck = tc.compile(small_gemm())
    art = ck.to_json()
    ck2 = CompiledKernel.from_json(art)
    assert ck2.spec is None          # no closures travel with the artifact
    ck2.verify(seed=3)               # DFG-reference oracle, bit-exact
    # simulating the same inputs through both artifacts is bit-identical
    init = ck.random_banks(seed=11)
    a = ck.run({k: v.copy() for k, v in init.items()})
    b = ck2.run({k: v.copy() for k, v in init.items()})
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    # and the re-serialized artifact is stable
    assert json.loads(ck2.to_json()) == json.loads(art)


def test_roundtrip_preserves_mapping_structure(tc):
    ck = tc.compile(small_gemm())
    ck2 = CompiledKernel.from_json(ck.to_json())
    assert ck2.II == ck.II and ck2.mii == ck.mii
    assert ck2.mapping.place == ck.mapping.place
    assert ck2.mapping.reg_assign == ck.mapping.reg_assign
    assert ck2.mapping.usage.map == ck.mapping.usage.map
    assert ck2.options == ck.options
    assert ck2.cache_key == ck.cache_key


# ------------------------------------------------------------------- cache
def test_cache_hit_skips_placement(tmp_path, monkeypatch):
    cache = str(tmp_path)
    ck = Toolchain(cache_dir=cache).compile(small_gemm())
    assert not ck.from_cache

    # a fresh Toolchain (empty memo) must satisfy the compile from disk
    # without ever invoking the mapper
    import repro.core.toolchain as toolchain_mod

    def boom(*a, **k):
        raise AssertionError("placement re-ran on a cache hit")

    monkeypatch.setattr(toolchain_mod, "map_kernel_opts", boom)
    ck2 = Toolchain(cache_dir=cache).compile(small_gemm())
    assert ck2.from_cache
    assert ck2.II == ck.II
    assert ck2.cache_key == ck.cache_key
    ck2.verify()                     # the cached artifact still verifies


def test_memo_returns_same_object(tc):
    a = tc.compile(small_gemm())
    b = tc.compile(small_gemm())
    assert a is b


def test_cache_key_sensitivity():
    opts = MapperOptions()
    base = spec_cache_key(small_gemm(), opts)
    assert base == spec_cache_key(small_gemm(), opts)  # deterministic
    assert base != spec_cache_key(build_gemm(TI=4, TK=4, TJ=4, unroll=2),
                                  opts)                 # DFG change
    assert base != spec_cache_key(small_gemm(),
                                  MapperOptions(ii_max=16))  # options change


def test_corrupt_cache_entry_recompiles(tmp_path):
    cache = str(tmp_path)
    tc1 = Toolchain(cache_dir=cache)
    ck = tc1.compile(small_gemm())
    path = os.path.join(cache, f"{ck.cache_key}.json")
    with open(path, "w") as f:
        f.write("{not json")
    ck2 = Toolchain(cache_dir=cache).compile(small_gemm())
    assert not ck2.from_cache        # fell back to a cold compile
    ck2.verify()


@pytest.mark.parametrize("mangle", ["truncate", "garbage", "wrong_schema",
                                    "empty"])
def test_damaged_cache_artifact_recompiles_and_heals(tmp_path, mangle):
    """_cache_load resilience: any unreadable artifact — truncated mid-JSON,
    binary garbage, schema-valid JSON missing artifact fields, or a zero-
    byte file — must fall through to a clean recompile AND be overwritten
    with a valid artifact that the next Toolchain loads."""
    cache = str(tmp_path)
    ck = Toolchain(cache_dir=cache).compile(small_gemm())
    path = os.path.join(cache, f"{ck.cache_key}.json")
    good = open(path, "r", encoding="utf-8").read()
    damaged = {
        "truncate": good[:len(good) // 2],
        "garbage": "\x00\xff not even close",
        "wrong_schema": json.dumps({"version": 1, "name": "x"}),
        "empty": "",
    }[mangle]
    with open(path, "w", encoding="utf-8") as f:
        f.write(damaged)

    ck2 = Toolchain(cache_dir=cache).compile(small_gemm())
    assert not ck2.from_cache            # damaged entry never served
    ck2.verify()
    # the damaged file was overwritten with a parseable, loadable artifact
    healed = open(path, "r", encoding="utf-8").read()
    CompiledKernel.from_json(healed).verify()
    ck3 = Toolchain(cache_dir=cache).compile(small_gemm())
    assert ck3.from_cache                # cache healed


def test_cache_write_failure_never_fails_the_compile(tmp_path, monkeypatch):
    """The cache is an optimization: an OSError while persisting the
    artifact (disk full, permissions) must not propagate out of compile."""
    import repro.core.toolchain as toolchain_mod

    def no_disk(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(toolchain_mod.os, "replace", no_disk)
    tc = Toolchain(cache_dir=str(tmp_path))
    ck = tc.compile(small_gemm())
    assert not ck.from_cache
    ck.verify()


def test_cache_load_does_not_mask_unrelated_errors(tmp_path, monkeypatch):
    """_cache_load's fall-through is for artifact-decode failures only; a
    genuine programming error inside artifact loading must still surface,
    not silently degrade every lookup into a recompile."""
    cache = str(tmp_path)
    ck = Toolchain(cache_dir=cache).compile(small_gemm())
    assert os.path.exists(os.path.join(cache, f"{ck.cache_key}.json"))

    def boom(s):
        raise RuntimeError("bug in artifact loading")

    monkeypatch.setattr(CompiledKernel, "from_json", staticmethod(boom))
    with pytest.raises(RuntimeError, match="bug in artifact loading"):
        Toolchain(cache_dir=cache).compile(small_gemm())


def test_cache_disabled_with_empty_dir():
    tc = Toolchain(cache_dir="")
    ck = tc.compile(small_gemm())
    assert not ck.from_cache
    assert tc._cache_path(ck.cache_key) is None


def test_cache_env_var_override(monkeypatch, tmp_path):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "envcache"))
    assert default_cache_dir() == str(tmp_path / "envcache")
    Toolchain().compile(small_gemm())
    assert os.path.isdir(str(tmp_path / "envcache"))


# ---------------------------------------------------------- legacy shims
#
# No in-repo caller uses map_kernel / verify_mapping anymore (src/, examples/
# and benchmarks/ all go through Toolchain.compile); the shims survive only
# for external callers and are exercised here.
def test_deprecated_map_kernel_shim_still_works():
    spec = small_gemm()
    with pytest.warns(DeprecationWarning):
        m = map_kernel(spec.dfg, spec.arch, spec.layout)
    assert m.II >= m.mii


@pytest.mark.parametrize("shim", ["map_kernel", "verify_mapping"])
def test_shims_emit_deprecation_warning_exactly_once(shim):
    """One call -> exactly one DeprecationWarning (no double-warn through
    the layered implementations, nothing swallowed)."""
    import warnings as _warnings
    spec = small_gemm()
    with _warnings.catch_warnings(record=True) as rec:
        _warnings.simplefilter("always")
        if shim == "map_kernel":
            map_kernel(spec.dfg, spec.arch, spec.layout)
        else:
            verify_mapping(spec)
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)
           and shim in str(w.message)]
    assert len(dep) == 1


def test_deprecated_verify_mapping_shim_still_works():
    spec = small_gemm()
    with pytest.warns(DeprecationWarning):
        m = map_kernel(spec.dfg, spec.arch, spec.layout)
    with pytest.warns(DeprecationWarning):
        m2 = verify_mapping(spec, mapping=m)
    assert m2.II == m.II


def test_map_kernel_shim_defaults_match_mapper_options():
    # the shim once defaulted ii_max=64 while MapperOptions said 32; the
    # two entry points must escalate identically
    import inspect
    sig = inspect.signature(map_kernel)
    assert sig.parameters["ii_max"].default == MapperOptions().ii_max


def test_mapper_options_roundtrip():
    opts = MapperOptions(ii_max=24, seeds=(5, 6), ii_start=4,
                         time_budget_s=1.5)
    assert MapperOptions.from_json_dict(opts.to_json_dict()) == opts
    # seeds coerce to tuple so options hash/compare structurally
    assert MapperOptions(seeds=[1, 2]) == MapperOptions(seeds=(1, 2))
