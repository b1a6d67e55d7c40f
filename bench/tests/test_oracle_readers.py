"""CPU tests of the readers of the DFG oracle's span attrs,
``oracle_step_ns.*`` and ``oracle_vmem_step_share.*``.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_oracle_readers.py

The span records and the trace summary are made by hand: a warm unit that
ends before the window, then two units in it, each with two
``morpher.oracle`` spans of 1,000 and 3,000 steps, as
``verify.reference_banks_batch`` would record them.
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
WINDOW_S = 0.092


def _oracle(t0, t1, steps, root, body):
    attrs = {"rows": 8, "body": body, "steps": steps}
    if body is None:                     # a program without the attrs
        attrs = {"rows": 8}
    return {"name": "morpher.oracle", "t0_ns": t0, "t1_ns": t1, "id": t0,
            "parent": root, "root": root, "attrs": attrs}


def _unit(t0, root, body, first="scan"):
    return [
        _oracle(t0 + 2 * MS, t0 + 10 * MS, 1000, root,
                first if body is not None else None),
        _oracle(t0 + 11 * MS, t0 + 20 * MS, 3000, root, body),
        {"name": "morpher.verify_batch", "t0_ns": t0, "t1_ns": t0 + 41 * MS,
         "id": root, "parent": None, "root": root,
         "attrs": {"kernel": "K", "seeds": 8}},
    ]


def _records(body, first="scan"):
    # the warm unit (0-41 ms) falls outside the window; the window holds
    # the units at 100-141 and 150-191 ms, 8,000 oracle steps in all
    return (_unit(0, 1000, "scan", "scan") + _unit(100 * MS, 2000, body, first)
            + _unit(150 * MS, 3000, body, first))


def _summary(module_s):
    return tracefile.Summary(window_s=WINDOW_S, busy_s=0.069,
                             module_s=module_s, gaps={}, devices=1,
                             span_busy={"bench.oracle": 0.023})


def _run(trace=None):
    return RunData(workload="table1.verify8", device_kind="TPU v5 lite",
                   window_s=WINDOW_S, records=[], trace=trace, spans={})


MODULES = {"jit_morpher_sim": 0.016, "jit_morpher_refexec": 0.002}


@pytest.mark.parametrize("cell", ["verify", "kws"])
@pytest.mark.parametrize("metric, recs, trace, want", [
    # 2 ms of oracle modules over 8,000 steps
    ("oracle_step_ns", _records("vmem"), _summary(MODULES), 250.0),
    ("oracle_step_ns", _records("vmem"), None, None),
    ("oracle_step_ns", _records("vmem"), _summary({"jit_morpher_sim": 1}),
     None),
    # a program whose oracle spans carry no ``steps`` gives nothing
    ("oracle_step_ns", _records(None), _summary(MODULES), None),
    ("oracle_step_ns", [], _summary(MODULES), None),
    # 6,000 of the window's 8,000 steps ran the kernel
    ("oracle_vmem_step_share", _records("vmem"), None, 75.0),
    ("oracle_vmem_step_share", _records("vmem", "vmem"), None, 100.0),
    ("oracle_vmem_step_share", _records("scan"), None, 0.0),
    ("oracle_vmem_step_share", _records(None), None, None),
    ("oracle_vmem_step_share", [], None, None),
])
def test_oracle_reader(monkeypatch, cell, metric, recs, trace, want):
    monkeypatch.setattr(programspans, "records", lambda: list(recs))
    got = load_reader(f"{metric}.{cell}")(_run(trace))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
