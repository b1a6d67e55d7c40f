"""CPU tests of the readers of the cell this configuration brought,
``kws_dscnn.verify8``.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_kws_readers.py

The span records and the trace summary are made by hand: a warm unit that
ends before the window, then two units in it, as the program's
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
from bench.capture import SimRecord  # noqa: E402
from bench.harness import RunData, load_reader  # noqa: E402

MS = 1_000_000      # ns
WINDOW_S = 0.092


def _launch(t0, t1, steps, root, pes=64, body="vmem"):
    attrs = {"multi": False, "invocations": 1, "steps": steps, "rows": 8,
             "real_rows": 8, "row_steps": steps * 8,
             "real_row_steps": steps * 8, "body": body, "pretiled": False,
             "built": False, "pes": pes, "vmem_bytes": 30 << 20}
    if pes is None:                      # a program without the attr
        del attrs["pes"], attrs["vmem_bytes"]
    return {"name": "morpher.sim.launch", "t0_ns": t0, "t1_ns": t1,
            "id": t0, "parent": root, "root": root, "attrs": attrs}


def _unit(t0, root, **kw):
    """One unit: a launch of 1,000 steps and one of 3,000, inside a root
    span."""
    return [
        _launch(t0 + 11 * MS, t0 + 20 * MS, 1000, root, **kw),
        _launch(t0 + 21 * MS, t0 + 40 * MS, 3000, root, **kw),
        {"name": "morpher.verify_batch", "t0_ns": t0 + 10 * MS,
         "t1_ns": t0 + 41 * MS, "id": root, "parent": None, "root": root,
         "attrs": {"kernel": "K", "seeds": 8}},
    ]


def _records(**kw):
    # warm unit 0-41 ms; the window holds 100-141 and 150-191 ms
    return (_unit(0, 1000, **kw) + _unit(100 * MS, 2000, **kw)
            + _unit(150 * MS, 3000, **kw))


def _summary(module_s):
    return tracefile.Summary(window_s=WINDOW_S, busy_s=0.069,
                             module_s=module_s, gaps={}, devices=1,
                             span_busy={"bench.oracle": 0.023})


def _run(trace=None, records=()):
    return RunData(workload="kws_dscnn.verify8", device_kind="TPU v5 lite",
                   window_s=WINDOW_S, records=list(records), trace=trace,
                   spans={})


SIM = {"jit_morpher_sim": 0.016, "jit_morpher_refexec": 0.02}


@pytest.mark.parametrize("name, recs, trace, want", [
    # 16 ms of simulator modules over 8,000 launched steps
    ("sim_step_us.kws", _records(), _summary(SIM), 2.0),
    ("sim_step_us.kws", _records(), None, None),
    # ... and over 8,000 steps x 64 PEs
    ("sim_pe_step_ns.kws", _records(), _summary(SIM), 31.25),
    ("sim_pe_step_ns.kws", _records(pes=16), _summary(SIM), 125.0),
    # a program whose launches carry no ``pes`` attr gives nothing
    ("sim_pe_step_ns.kws", _records(pes=None), _summary(SIM), None),
    ("sim_pe_step_ns.kws", _records(), _summary({}), None),
    ("sim_vmem_step_share.kws", _records(), None, 100.0),
    ("sim_vmem_step_share.kws", _records(body="scan"), None, 0.0),
    ("sim_vmem_step_share.kws", [], None, None),
    ("oracle_device_share.kws", [], _summary(SIM), 25.0),
    ("oracle_device_share.kws", [], None, None),
    ("device_idle_share.kws", [], _summary(SIM), 25.0),
    ("device_idle_share.kws", [], None, None),
])
def test_reader(monkeypatch, name, recs, trace, want):
    monkeypatch.setattr(programspans, "records", lambda: list(recs))
    got = load_reader(name)(_run(trace))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_roofline_share_at_64_pes():
    """bench/work.py's bytes per row-cycle at P=64, RF 8, LI 4, int16:
    64 x 88 = 5,632; 1,000 cycles x 8 rows over 819 GB/s x 92 ms."""
    rec = SimRecord(call=0, cfg=None, init=[{}] * 8, final=[{}] * 8,
                    cycles=1000, P=64, RF=8, LI=4, bits=16)
    got = load_reader("sim_roofline_share.kws")(_run(records=[rec]))
    assert got == pytest.approx(100.0 * 5632 * 8000 / (819e9 * WINDOW_S))
    assert load_reader("sim_roofline_share.kws")(_run()) is None
