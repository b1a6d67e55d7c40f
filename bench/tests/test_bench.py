"""CPU tests of the benchmark's yardstick: work, peaks, trace reduction,
reference, control and faults, and that cells are found by name.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

The runs here use the fixture configuration in ``bench/tests/data`` (two
fabrics of ``dse_wide4x4_pop12`` with its kernels), copied with the
benchmark into a temporary checkout, so that nothing is added to the
repository's own ``bench``.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "bench", "tests", "data")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import reference, tracefile, work  # noqa: E402
from bench.harness import load_cell, resolve  # noqa: E402

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
ENV.pop("JAX_COMPILATION_CACHE_DIR", None)


# ------------------------------------------------------------ work, peaks
def test_bytes_per_row_cycle_pinned():
    # per PE at 16 bits: config 1 + 2 + (3+2+4) = 12, state 2*((4+2+2)*2+1)
    # = 34, live-ins 1*2 = 2, memory 2*2 = 4 -> 52 bytes, times 4 PEs
    assert work.bytes_per_row_cycle(P=4, RF=2, LI=1, bits=16) == 208
    # at 8 bits: 11 + 18 + 1 + 2 = 32 per PE
    assert work.bytes_per_row_cycle(P=4, RF=2, LI=1, bits=8) == 128
    # the paper's 4x4 cluster as Table I maps it (RF 8, 4 live-ins)
    assert work.bytes_per_row_cycle(P=16, RF=8, LI=4, bits=16) == 1408


def test_peaks_known_and_unknown():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError):
        work.peaks("cpu")


# -------------------------------------------------------- trace reduction
def _events(device, host):
    return tracefile.Events({"/device:TPU:0": device}, host)


def test_summarize_hand_computed():
    ms = 1e6
    ev = _events(
        [("jit__unknown", 1 * ms, 4 * ms), ("jit_run", 3 * ms, 5 * ms),
         ("jit_run", 7 * ms, 12 * ms)],
        [("bench.window", 0.0, 10 * ms),
         ("bench.verify_batch:GEMM", 0.5 * ms, 6 * ms),
         ("bench.sim", 1 * ms, 4.5 * ms),
         ("bench.compile_many", 6 * ms, 8 * ms)])
    s = tracefile.summarize(ev)
    assert s.window_s == pytest.approx(0.010)
    # busy: [1,5] and [7,10] inside the window
    assert s.busy_s == pytest.approx(0.007)
    assert s.module_s["jit__unknown"] == pytest.approx(0.003)
    assert s.module_s["jit_run"] == pytest.approx(0.005)
    # idle [0,1]: 0.5 ms before any span, 0.5 ms in verify_batch; idle
    # [5,7]: 1 ms in verify_batch, 1 ms in compile_many
    assert s.gaps == pytest.approx({"bench.window": 0.0005,
                                    "bench.verify_batch:GEMM": 0.0015,
                                    "bench.compile_many": 0.001})
    assert tracefile.idle_share(s) == pytest.approx(30.0)
    # device busy inside each host span: [1,5] in verify_batch, [1,4.5]
    # in sim, [7,8] in compile_many
    assert s.span_busy == pytest.approx({"bench.verify_batch:GEMM": 0.004,
                                         "bench.sim": 0.0035,
                                         "bench.compile_many": 0.001})
    assert tracefile.span_share(s, "bench.sim") == pytest.approx(35.0)
    assert tracefile.span_share(s, "bench.oracle") is None
    assert tracefile.idle_share(None) is None
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit_run", pytest.approx(0.005)]


def test_summarize_recorded_trace():
    """A short window traced on one TPU v5e, reduced to plain events."""
    with open(os.path.join(DATA, "trace_small.json"), encoding="utf-8") as f:
        rec = json.load(f)
    s = tracefile.summarize(tracefile.Events.from_json(
        json.dumps(rec["events"])))
    want = rec["expect"]
    assert s.devices == 1
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0.0 < s.busy_s < s.window_s
    assert s.module_s == pytest.approx(want["modules"], rel=1e-9)
    assert s.gaps == pytest.approx(want["gaps"], rel=1e-9)
    assert sum(s.gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_module_name_strips_run_id():
    assert tracefile.module_name("jit_run(1302419100373586987)") == "jit_run"
    assert tracefile.module_name("jit__unknown") == "jit__unknown"


# ------------------------------------------------------------- reference
def _tiny_config():
    with open(os.path.join(DATA, "tiny_two_fabrics.json"),
              encoding="utf-8") as f:
        return json.load(f)


def test_reference_matches_program_golden():
    """The plain reference and the kernel library's own golden models
    agree on data, layout and result for every kernel of the fixture."""
    from repro.core.verify import generate_test_data
    from bench.harness import build_fabric
    cfg = _tiny_config()
    for fab in cfg["fabrics"]:
        arch = build_fabric(fab)
        for k in cfg["kernels"]:
            spec = resolve(k["call"])(**k["kwargs"], arch=arch)
            for seed in (0, 7, 2**40 + 3):
                data = generate_test_data(spec, seed)
                init, final = reference.images(k, fab, seed, 16)
                assert reference.words_wrong(data.init_banks, init) == 0
                assert reference.words_wrong(data.expected_banks, final) == 0


def test_control_precision_differs():
    """An 8-bit datapath, the precision below the configurations' int16,
    leaves other final images."""
    cfg = _tiny_config()
    fab = cfg["fabrics"][0]
    wrong = 0
    for k in cfg["kernels"]:
        _, f16 = reference.images(k, fab, 5, 16)
        _, f8 = reference.images(k, fab, 5, 8)
        wrong += reference.words_wrong(f8, f16)
    assert wrong > 0


def test_benchmark_cells_resolve():
    """Every cell of BENCHMARK.json has its workload, configuration, kernel
    references and metric readers, found by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        assert cell.workload["config"] == w["config"]
        for k in cell.config["kernels"]:
            reference.kernel_module(k["kind"])
            resolve(k["call"])
        for f in cell.config["fabrics"]:
            resolve(f["call"])
        for m in cell.per_layer:
            assert os.path.isfile(os.path.join(
                ROOT, "bench", "metrics", m["name"] + ".py"))
        names = {m["name"] for m in bench["end_to_end"]
                 if w["name"] in m.get("workloads", [w["name"]])}
        assert names == {"setup_s", cell.metric["name"]}


# ------------------------------------------------------------ whole runs
@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark with the fixture cells placed beside the
    others, and the program beside it."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    shutil.copy(os.path.join(DATA, "tiny_two_fabrics.json"),
                root / "bench" / "configs")
    for name in ("tiny.batch.json", "tiny.stacked.json"):
        shutil.copy(os.path.join(DATA, name), root / "bench" / "workloads")
    return root


def _run(checkout, *args, env=None):
    p = subprocess.run(
        [sys.executable, str(checkout / "bench" / "tests" / "cpu_run.py"),
         *map(str, args)], cwd=checkout, env=env or ENV,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_no_accelerator_exits_nonzero(checkout):
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "table1.verify8",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=checkout, env=ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
    assert "accelerator" in p.stderr


def test_new_files_found_by_name(checkout):
    """A configuration and workloads placed beside the others run with no
    code edit, and come out correct."""
    res, err = _run(checkout, "tiny.batch", 2**33 + 5, 0)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 4 * 2
    assert set(res["metrics"]) == {"verify_cycles_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert all(v["value"] == 0 for v in res["checks"].values())
    assert "oracle_words_wrong" in res["checks"]
    assert "inside it xla_builds=0" in err and "cache_loads=0" in err
    assert err.rstrip().splitlines()[-1].startswith("check ")


def test_traced_run_reports_device_window(checkout):
    res, _ = _run(checkout, "tiny.stacked", 11, 0,
                  env=dict(ENV, BENCH_TEST_TRACE="1"))
    assert res["correct"] is True
    assert res["device"]["window_s"] > 0
    assert "busy_s" in res["device"] and "breakdown" in res


@pytest.mark.parametrize("fault,check", [
    ("unchanged", "image_words_wrong"),
    ("half_batch", "pairs_unverified"),
    ("altered_word", "image_words_wrong"),
    ("oracle_skipped", "oracle_rows_unverified"),
    ("control", "image_words_wrong"),
])
def test_broken_timed_path_is_not_correct(checkout, fault, check):
    """With the program's own comparison off, each fault planted in what
    the launches give back, the DFG oracle left out, and the 8-bit control
    in the simulator's place, is caught by the reference."""
    cell = "tiny.stacked" if fault in ("half_batch", "control") \
        else "tiny.batch"
    res, _ = _run(checkout, cell, 17, 0, fault)
    assert res["correct"] is False
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]
