"""CPU tests of the readers of the program's own spans: the simulator's
launch counters and its device time per launched step.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_program_readers.py

The span records and the trace summary are made by hand: a warm unit that
ends before the window, then two launches in it, as the program's
``morpher.sim.launch`` spans would record them.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import programspans, tracefile  # noqa: E402
from bench.harness import RunData, load_reader  # noqa: E402

MS = 1_000_000      # ns


def _launch(t0, t1, steps, rows, real_row_steps, pretiled, root):
    return {"name": "morpher.sim.launch", "t0_ns": t0, "t1_ns": t1,
            "id": t0, "parent": root, "root": root,
            "attrs": {"multi": False, "invocations": 1, "steps": steps,
                      "rows": rows, "real_rows": rows,
                      "row_steps": steps * rows,
                      "real_row_steps": real_row_steps,
                      "pretiled": pretiled, "built": False}}


def _unit(t0, root):
    """One verify_batch of two kernels' worth: a tiled launch of 1,000
    steps (900 real) and an untiled one of 3,000 steps (2,700 real), at 8
    rows each, inside a root span."""
    return [
        {"name": "morpher.testdata", "t0_ns": t0, "t1_ns": t0 + MS,
         "id": root + 1, "parent": root, "root": root, "attrs": {}},
        _launch(t0 + 2 * MS, t0 + 10 * MS, 1000, 8, 900 * 8, True, root),
        _launch(t0 + 11 * MS, t0 + 40 * MS, 3000, 8, 2700 * 8, False, root),
        {"name": "morpher.verify_batch", "t0_ns": t0, "t1_ns": t0 + 41 * MS,
         "id": root, "parent": None, "root": root,
         "attrs": {"kernel": "K", "seeds": 8}},
    ]


# the warm unit at 0-41 ms, two units in the window (100-141, 150-191 ms);
# the window ends 1 ms after the last span, the benchmark's own spans are
# ignored
RECORDS = (_unit(0, 1000) + _unit(100 * MS, 2000) + _unit(150 * MS, 3000)
           + [{"name": "bench.sim", "t0_ns": 0, "t1_ns": 10 ** 12, "id": 9,
               "parent": None, "root": 9, "attrs": {}}])
WINDOW_S = 0.092


def _summary(module_s):
    return tracefile.Summary(window_s=WINDOW_S, busy_s=0.08,
                             module_s=module_s, gaps={}, devices=1)


def _run(trace):
    return RunData(workload="table1.verify8", device_kind="TPU v5 lite",
                   window_s=WINDOW_S, records=[], trace=trace, spans={})


@pytest.fixture()
def spans(monkeypatch):
    monkeypatch.setattr(programspans, "records", lambda: list(RECORDS))


def test_window_rule_drops_the_warm_unit(spans):
    kept = programspans.in_window(RECORDS, WINDOW_S)
    assert sorted({r["root"] for r in kept}) == [2000, 3000]
    assert all(r["name"].startswith("morpher.") for r in kept)
    assert len(programspans.launches(_run(None))) == 4


def test_padded_step_share(spans):
    # 2 x (900 + 2700) x 8 real of 2 x (1000 + 3000) x 8 launched
    read = load_reader("sim_padded_step_share.verify")
    assert read(_run(None)) == pytest.approx(10.0)


def test_untiled_step_share(spans):
    read = load_reader("sim_untiled_step_share.verify")
    assert read(_run(None)) == pytest.approx(75.0)


def test_step_us(spans):
    read = load_reader("sim_step_us.verify")
    trace = _summary({"jit_morpher_sim": 0.06, "jit_morpher_sim_multi": 0.02,
                      "jit_morpher_refexec": 0.005})
    # 0.08 s over 8,000 launched steps
    assert read(_run(trace)) == pytest.approx(10.0)
    assert read(_run(None)) is None
    assert read(_run(_summary({"jit__unknown": 0.08}))) is None


@pytest.mark.parametrize("name", ["sim_step_us.verify",
                                  "sim_padded_step_share.verify",
                                  "sim_untiled_step_share.verify"])
def test_no_launch_span_reads_none(monkeypatch, name):
    trace = _summary({"jit_morpher_sim": 0.08})
    no_launch = [r for r in RECORDS if r["name"] != "morpher.sim.launch"]
    monkeypatch.setattr(programspans, "records", lambda: no_launch)
    assert load_reader(name)(_run(trace)) is None
    monkeypatch.setattr(programspans, "records", lambda: [])
    assert load_reader(name)(_run(trace)) is None


def test_program_without_span_ring_gives_no_records(monkeypatch):
    import repro.core
    monkeypatch.delattr(repro.core, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.obs", None)
    assert programspans.records() == []
