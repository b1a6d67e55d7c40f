"""CPU test of the reader of the simulator's ``body`` launch attr,
``sim_vmem_step_share.verify``.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_vmem_reader.py

The span records are made by hand: a warm unit that ends before the
window, then two units in it, each with a scan launch of 1,000 steps and a
VMEM-kernel launch of 3,000 steps, as the program's ``morpher.sim.launch``
spans would record them.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import programspans  # noqa: E402
from bench.harness import RunData, load_reader  # noqa: E402

MS = 1_000_000      # ns
NAME = "sim_vmem_step_share.verify"
WINDOW_S = 0.092


def _launch(t0, t1, steps, root, body):
    return {"name": "morpher.sim.launch", "t0_ns": t0, "t1_ns": t1,
            "id": t0, "parent": root, "root": root,
            "attrs": {"multi": False, "invocations": 1, "steps": steps,
                      "rows": 8, "real_rows": 8, "row_steps": steps * 8,
                      "real_row_steps": steps * 8, "body": body,
                      "pretiled": body == "scan", "built": False}}


def _unit(t0, root, first_body):
    return [
        _launch(t0 + 2 * MS, t0 + 10 * MS, 1000, root, "scan"),
        _launch(t0 + 11 * MS, t0 + 40 * MS, 3000, root, first_body),
        {"name": "morpher.verify_batch", "t0_ns": t0, "t1_ns": t0 + 41 * MS,
         "id": root, "parent": None, "root": root,
         "attrs": {"kernel": "K", "seeds": 8}},
    ]


# the warm unit (0-41 ms) ran only the scan and falls outside the window;
# the two units in it (100-141, 150-191 ms) each ran 3,000 of 4,000 steps
# in the kernel
RECORDS = (_unit(0, 1000, "scan") + _unit(100 * MS, 2000, "vmem")
           + _unit(150 * MS, 3000, "vmem"))


def _run():
    return RunData(workload="table1.verify8", device_kind="TPU v5 lite",
                   window_s=WINDOW_S, records=[], trace=None, spans={})


def _without_body(recs):
    return [dict(r, attrs={k: v for k, v in r["attrs"].items()
                           if k != "body"}) for r in recs]


@pytest.mark.parametrize("recs, want", [
    (RECORDS, 75.0),
    # a program whose launches carry no ``body`` attr ran only the scan
    (_without_body(RECORDS), 0.0),
    # no launch spans, or no span ring at all: nothing to read
    ([r for r in RECORDS if r["name"] != "morpher.sim.launch"], None),
    ([], None),
], ids=["kernel_and_scan", "no_body_attr", "no_launch_spans", "no_records"])
def test_vmem_step_share(monkeypatch, recs, want):
    monkeypatch.setattr(programspans, "records", lambda: list(recs))
    got = load_reader(NAME)(_run())
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
