"""Batched verification engine: bit-exactness against the sequential flow
(the golden-equivalence contract), the shape-bucketed executable cache,
and the batched oracles.

The load-bearing test is the property sweep: for every library kernel
(six Table-I + four DSL-only) and >= 4 seeds, the bucketed batch-of-N
simulation (``run_batch``) must agree word-for-word with the exact-cycle
batch of one (``run``) — including a batch size that pads up to its
bucket boundary — and ``verify_batch`` and per-seed ``verify`` (its batch
of one) must pass."""
import numpy as np
import pytest

from repro.core import simcache
from repro.core.kernels_lib import build_gemm, table1_kernels
from repro.core.refexec import reference_execute_jax
from repro.core.simulator import simulate_batch
from repro.core.toolchain import CompiledKernel, Toolchain
from repro.core.verify import generate_test_data, generate_test_data_batch
from repro.frontend.library import dsl_kernels

from dfg_reference import reference_banks

SEEDS = [0, 1, 5, 11]


@pytest.fixture(scope="module")
def compiled_all():
    tc = Toolchain(cache_dir="")
    specs = {**table1_kernels(small=True), **dsl_kernels()}
    return dict(zip(specs, tc.compile_many(list(specs.values()))))


def test_batched_matches_sequential_word_for_word(compiled_all):
    """Golden equivalence: every (kernel, seed) pair simulates to the very
    same final memory in a bucketed batch-of-N launch as in the
    exact-cycle batch of one, and verifies clean both ways."""
    for name, ck in compiled_all.items():
        datas = [generate_test_data(ck.spec, s) for s in SEEDS]
        seq = [ck.run(d.init_banks) for d in datas]
        bat = ck.run_batch([d.init_banks for d in datas])
        assert len(bat) == len(SEEDS)
        for seed, a, b in zip(SEEDS, seq, bat):
            for bank in a:
                np.testing.assert_array_equal(
                    a[bank], b[bank],
                    err_msg=f"{name} seed {seed} {bank}")
        ck.verify_batch(SEEDS)          # and the full IV-C batched flow
        for s in SEEDS:
            ck.verify(seed=s)


def test_padded_bucket_is_masked_out(compiled_all):
    """batch=3 rounds up to the 4-bucket; the padded row must not leak
    into results."""
    ck = compiled_all["GEMM"]
    assert simcache.bucket_batch(3) == 4
    datas = [generate_test_data(ck.spec, s) for s in (2, 3, 4)]
    bat = ck.run_batch([d.init_banks for d in datas])
    assert len(bat) == 3
    for d, b in zip(datas, bat):
        seq = ck.run(d.init_banks)
        for bank in seq:
            np.testing.assert_array_equal(seq[bank], b[bank])
    ck.verify_batch([2, 3, 4])


def test_verify_batch_artifact_path(compiled_all):
    """A deserialized artifact (no golden-model closures) batch-verifies
    against the DFG reference oracle."""
    ck = CompiledKernel.from_json(compiled_all["GEMM"].to_json())
    assert ck.spec is None
    ck.verify_batch([0, 1, 2])


def test_verify_batch_empty_seeds(compiled_all):
    ck = compiled_all["GEMM"]
    assert ck.verify_batch([]) is ck
    assert simulate_batch(ck.cfg, [], ck.invocations, ck.mapped_iters) == []


def test_verify_many_mixes_specs_programs_and_artifacts():
    from repro.frontend.library import DSL_PROGRAMS
    tc = Toolchain(cache_dir="")
    spec = build_gemm(TI=4, TK=4, TJ=4, unroll=1)
    pre = tc.compile(spec)
    out = tc.verify_many([pre, DSL_PROGRAMS[0]], seeds=[0, 1])
    assert out[0] is pre
    assert out[1].name == DSL_PROGRAMS[0].name


def test_oracles_agree_with_scalar_reference(compiled_all):
    """The JAX-lowered DFG oracle reproduces the scalar reference
    (``DFG.reference_execute`` folded over the invocations) bit-for-bit."""
    for name in ("GEMM", "CONV", "dwconv", "requant-int8"):
        spec = compiled_all[name].spec
        inits = [generate_test_data(spec, s).init_banks for s in SEEDS]
        stacked = {k: np.stack([i[k] for i in inits]) for k in inits[0]}
        bits = spec.arch.datapath_bits
        want = [reference_banks(spec.dfg, i, spec.invocations,
                                spec.mapped_iters, bits) for i in inits]
        got_jx = reference_execute_jax(spec.dfg, spec.mapped_iters, stacked,
                                       spec.invocations, bits)
        for i, seed in enumerate(SEEDS):
            for bank in want[i]:
                np.testing.assert_array_equal(
                    want[i][bank], got_jx[bank][i],
                    err_msg=f"{name} seed {seed} {bank} (jax)")


def test_generate_test_data_batch_rows_match_per_seed():
    spec = build_gemm(TI=4, TK=4, TJ=4, unroll=1)
    db = generate_test_data_batch(spec, SEEDS)
    for i, s in enumerate(SEEDS):
        d = generate_test_data(spec, s)
        for bank in d.init_banks:
            np.testing.assert_array_equal(db.init_banks[bank][i],
                                          d.init_banks[bank])
            np.testing.assert_array_equal(db.expected_banks[bank][i],
                                          np.asarray(d.expected_banks[bank]))


def test_verify_batch_reports_seed_on_mismatch(compiled_all):
    """A corrupted configuration must fail with the offending seed named."""
    src = compiled_all["GEMM"]
    ck = CompiledKernel.from_json(src.to_json())
    ck.cfg.imm[:] = ck.cfg.imm + 1          # corrupt every immediate
    with pytest.raises(AssertionError, match="seed="):
        ck.verify_batch([0, 1])


# ----------------------------------------------------------- simcache unit
def test_bucket_batch_rounds_to_power_of_two():
    assert [simcache.bucket_batch(b) for b in (0, 1, 2, 3, 5, 8, 9)] == \
        [1, 1, 2, 4, 8, 8, 16]
    # degenerate and negative inputs clamp to the 1-bucket, and exact
    # powers of two are fixed points (no gratuitous doubling)
    assert simcache.bucket_batch(-3) == 1
    for p in (1, 2, 4, 64, 1024):
        assert simcache.bucket_batch(p) == p


def test_bucket_cycles_rounds_up_with_bounded_padding():
    for n in (1, 7, 13, 40, 100, 1000, 12345):
        b = simcache.bucket_cycles(n)
        assert b >= n
        assert b <= max(n * 1.125, n + 1), (n, b)
    # buckets quantize: nearby cycle counts share one boundary
    assert simcache.bucket_cycles(121) == simcache.bucket_cycles(127)


def test_bucket_cycles_edges():
    # <= 8 passes through exactly (tiny schedules never pad) except the
    # degenerate 0/negative, which clamps to 1 cycle
    assert [simcache.bucket_cycles(n) for n in (0, -1, 1, 2, 8)] == \
        [1, 1, 1, 2, 8]
    # the first bucketed value and an exact boundary stay put
    assert simcache.bucket_cycles(9) == 9
    assert simcache.bucket_cycles(16) == 16
    # idempotent: a bucket boundary is its own bucket
    for n in (9, 17, 40, 121, 12345):
        assert simcache.bucket_cycles(simcache.bucket_cycles(n)) == \
            simcache.bucket_cycles(n)


def test_bucket_rows_quantizes_like_cycles():
    # the stacked-batch row bucket uses the cycle quantization (<= 12.5%
    # padded rows), not bucket_batch's power of two: 40 rows must not
    # balloon to 64
    assert simcache.bucket_rows(40) == 40
    assert simcache.bucket_rows(41) < simcache.bucket_batch(41)
    for n in (1, 8, 9, 38, 100):
        assert simcache.bucket_rows(n) == simcache.bucket_cycles(n)


def test_bucket_rf_merges_provisioning_classes():
    # every library register-file size folds into the 16-wide class, so
    # rf4/rf8/rf16 search variants share one stacked executable
    assert {simcache.bucket_rf(rf) for rf in (1, 4, 8, 16)} == {16}
    # wider RFs round to the next power of two and are fixed points
    assert simcache.bucket_rf(17) == 32
    assert simcache.bucket_rf(32) == 32
    for rf in (1, 4, 16, 24, 64):
        assert simcache.bucket_rf(rf) >= rf


def test_executable_cache_reuses_signatures(compiled_all):
    simcache.clear()
    ck = compiled_all["GEMM"]
    data = [generate_test_data(ck.spec, s).init_banks for s in SEEDS]
    ck.run_batch(data)
    st = simcache.stats()
    assert st["entries"] == 1 and st["misses"] == 1
    ck.run_batch(data)                       # same signature: cache hit
    st = simcache.stats()
    assert st["entries"] == 1 and st["hits"] >= 1
    # batch=3 pads into the same 4-bucket -> same executable, another hit
    ck.run_batch(data[:3])
    assert simcache.stats()["entries"] == 1
