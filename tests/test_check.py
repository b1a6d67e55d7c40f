"""Static legality checker (repro.check): clean-library sweeps, one
pinned test per mutation class, report byte-determinism, the
MORPHER_CHECK=1 verify gate, and the DSE pre-screen."""
import dataclasses

import pytest

from repro.check import (RULES, assert_clean, check_kernel, errors,
                         report_json)
from repro.check.mutate import CLASSES, mutate_one, mutation_gate, run_corpus
from repro.core.adl import cluster_4x4
from repro.core.kernels_lib import table1_kernels
from repro.core.toolchain import Toolchain


@pytest.fixture(scope="module")
def toolchain():
    return Toolchain()


@pytest.fixture(scope="module")
def compiled_small(toolchain):
    """The six Table-I small kernels (shared compile, cache-warm)."""
    specs = table1_kernels(small=True)
    cks = toolchain.compile_many(list(specs.values()))
    return dict(zip(specs, cks))


# ------------------------------------------------------------- clean sweeps
def test_clean_library_two_archs_zero_diagnostics(toolchain):
    """The PR-10 contract: all ten library kernels, on two architectures,
    produce zero diagnostics."""
    from repro.dse.explore import kernel_suite
    torus = dataclasses.replace(cluster_4x4(),
                                name="morpher-cluster-4x4-torus", torus=True)
    for arch in (cluster_4x4(), torus):
        suite = kernel_suite(arch)
        assert len(suite) == 10
        cks = toolchain.compile_many(list(suite.values()))
        for ck in cks:
            diags = errors(check_kernel(ck))
            assert diags == [], (arch.name, ck.name,
                                 [str(d) for d in diags[:5]])


def test_assert_clean_passes_on_clean_artifact(compiled_small):
    for ck in compiled_small.values():
        assert_clean(ck)


def test_toolchain_check_api(toolchain):
    """Toolchain.check compiles (cache hit) and audits in one call."""
    spec = table1_kernels(small=True)["GEMM"]
    assert errors(toolchain.check(spec)) == []


# -------------------------------------------------- mutation corpus: pinned
@pytest.mark.parametrize("cls", sorted(CLASSES))
def test_mutation_class_caught_by_intended_rule(cls, compiled_small):
    """One pinned test per corruption class: the class's intended rule id
    fires on at least one seeded mutant, on every kernel that offers a
    mutation site."""
    from repro.check.mutate import _check_mutant
    layer, intended = CLASSES[cls]
    assert intended in RULES
    sites = 0
    for ck in compiled_small.values():
        made = mutate_one(ck, cls, seed=0, index=0)
        if made is None:
            continue
        sites += 1
        artifact, desc = made
        fired = {d.rule for d in _check_mutant(ck, layer, artifact)}
        assert intended in fired, (ck.name, cls, desc, sorted(fired))
    assert sites > 0, f"no kernel offered a site for class {cls!r}"


def test_mutation_gate_green(compiled_small):
    """The acceptance bar: score >= 0.95, every class caught, and any
    miss proven simulator-invisible (none expected)."""
    report = mutation_gate(list(compiled_small.values()), seed=0,
                           per_class=2)
    assert report.score >= 0.95
    assert report.live_misses == []


def test_corpus_is_seeded_and_reproducible(compiled_small):
    cks = [compiled_small["GEMM"]]
    a = run_corpus(cks, seed=7, per_class=1, probe_dead=False)
    b = run_corpus(cks, seed=7, per_class=1, probe_dead=False)
    assert [o.to_json_dict() for o in a.outcomes] == \
        [o.to_json_dict() for o in b.outcomes]


def test_probe_dead_simulates_exact_cycles(compiled_small):
    """The mutation probe judges a config over its real schedule length:
    each of its launches is one image for exactly the schedule's cycles,
    never the bucketed count whose padded cycles could commit a store
    moved past the last real cycle."""
    from repro.check.mutate import _probe_dead
    from repro.core import obs, simcache
    ck = compiled_small["GEMM"]
    real = ck.cfg.n_cycles(ck.mapped_iters)
    assert simcache.bucket_cycles(real) > real
    with obs.span("morpher.t.probe"):
        assert _probe_dead(ck, "config", ck.cfg)
        cfg, _desc = mutate_one(ck, "store_window", seed=0, index=0)
        assert not _probe_dead(ck, "config", cfg)
    top = obs.spans()[-1]                 # the probe span closes last
    launches = [r["attrs"] for r in obs.spans()
                if r["name"] == "morpher.sim.launch"
                and r["root"] == top["id"]]
    assert len(launches) >= 2
    for a in launches:
        assert (a["rows"], a["steps"]) == (1, real * len(ck.invocations))


# --------------------------------------------------------------- the report
def test_report_json_byte_deterministic(compiled_small):
    def build():
        return report_json({
            name: {"II": ck.II, "cache_key": ck.cache_key,
                   "diagnostics": check_kernel(ck)}
            for name, ck in compiled_small.items()})
    one, two = build(), build()
    assert one == two
    assert one.endswith("\n")
    import json
    payload = json.loads(one)
    assert payload["clean"] is True
    assert payload["n_errors"] == 0
    assert set(payload["rules"]) == set(RULES)


# ------------------------------------------------------ MORPHER_CHECK gate
def test_verify_gate_passes_clean(compiled_small, monkeypatch):
    monkeypatch.setenv("MORPHER_CHECK", "1")
    compiled_small["GEMM"].verify(seed=0)


def test_verify_gate_rejects_corrupt_artifact(compiled_small, monkeypatch):
    """Under MORPHER_CHECK=1 a corrupted artifact fails *statically*,
    naming the rule, before any simulation runs."""
    monkeypatch.setenv("MORPHER_CHECK", "1")
    ck = compiled_small["GEMM"]
    cfg, _desc = mutate_one(ck, "store_window", seed=0, index=0)
    bad = dataclasses.replace(ck, cfg=cfg)
    with pytest.raises(AssertionError, match="CFG-STORE-WINDOW"):
        bad.verify(seed=0)
    with pytest.raises(AssertionError, match="CFG-STORE-WINDOW"):
        bad.verify_batch(seeds=(0, 1))


def test_gate_off_by_default(compiled_small, monkeypatch):
    """Without MORPHER_CHECK=1 the corrupt artifact fails dynamically (or
    not at all) — the static gate must be opt-in."""
    monkeypatch.delenv("MORPHER_CHECK", raising=False)
    from repro.core.verify import check_enabled
    assert not check_enabled()


# ---------------------------------------------------------- DSE pre-screen
def test_dse_prescreen_flags_corrupt_point(compiled_small):
    from repro.dse.explore import _prescreen
    ck = compiled_small["GEMM"]
    assert _prescreen(ck) == ""
    cfg, _desc = mutate_one(ck, "opcode_clobber", seed=0, index=0)
    bad = dataclasses.replace(ck, cfg=cfg)
    msg = _prescreen(bad)
    assert "CFG-OPC-RANGE" in msg


def test_dse_evaluate_points_static_check(toolchain):
    """evaluate_points with the static pre-screen enabled: clean points
    keep status ok (the frontier is unchanged when nothing fires)."""
    from repro.dse import tiny_space
    from repro.dse.explore import evaluate_points
    points = list(tiny_space())[:1]
    res = evaluate_points(points, toolchain=toolchain, seeds=(0,),
                          suite_names=("GEMM", "CONV"), static_check=True)
    assert len(res) == 1
    for outcome in res[0].kernels.values():
        assert outcome.status in ("ok", "map_error", "layout_error"), \
            outcome
        assert outcome.status != "check_error"


# ----------------------------------- generator errors share the rule idiom
def test_config_conflict_message_carries_locus_and_rule(compiled_small):
    """Satellite: ConfigConflict messages read like checker diagnostics
    (slot/pe locus + rule id)."""
    from repro.core.config_gen import ConfigConflict, generate_config
    ck = next(c for c in compiled_small.values()
              if c.mapping.reg_assign)
    mapping, _desc = mutate_one(ck, "reg_clobber", seed=0, index=0)
    # drop the colored register entirely: generate_config must name the
    # locus and the MAP-REG-RANGE rule
    key = sorted(mapping.reg_assign)[0]
    del mapping.reg_assign[key]
    with pytest.raises(ConfigConflict, match=r"slot\d+/pe\d+.*MAP-REG-RANGE"):
        generate_config(mapping, ck.layout)


def test_stream_error_message_carries_locus_and_rule(compiled_small):
    from repro.isa.encode import manifest_dict, to_csv
    from repro.isa.interp import StreamError, parse_stream
    ck = compiled_small["GEMM"]
    csv_text = to_csv(ck.cfg)
    lines = csv_text.splitlines()
    dup = "\n".join(lines[:-1] + [lines[1], ""])  # duplicate first record
    with pytest.raises(StreamError, match=r"slot\d+/pe\d+.*STR-PARSE"):
        parse_stream(dup, manifest_dict(ck.cfg, ck.name))
