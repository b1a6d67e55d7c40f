"""CPU test of the reader of the simulator's ``pass_words`` launch attr,
``sim_image_pass_share.verify`` (and ``.kws``, which loads it).

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_pass_share_reader.py

The span records are made by hand: a warm unit that ends before the
window, then two units in it, each with a scan launch of 1,000 steps and
two VMEM-kernel launches, of 1,000 steps over a 32,896-lane image in
4,096-lane passes and of 3,000 steps over an 8,320-lane image in
4,096-lane passes, as the program's ``morpher.sim.launch`` spans would
record them.
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
WINDOW_S = 0.092


def _launch(t0, t1, steps, root, body, image_lanes, pass_words):
    return {"name": "morpher.sim.launch", "t0_ns": t0, "t1_ns": t1,
            "id": t0, "parent": root, "root": root,
            "attrs": {"multi": False, "invocations": 1, "steps": steps,
                      "rows": 8, "real_rows": 8, "row_steps": steps * 8,
                      "real_row_steps": steps * 8, "body": body,
                      "pretiled": body == "scan", "built": False,
                      "image_lanes": image_lanes, "pass_words": pass_words}}


def _unit(t0, root, pass_words):
    return [
        _launch(t0 + 2 * MS, t0 + 10 * MS, 1000, root, "scan", 8320, 8320),
        _launch(t0 + 11 * MS, t0 + 20 * MS, 1000, root, "vmem", 32896,
                pass_words),
        _launch(t0 + 21 * MS, t0 + 40 * MS, 3000, root, "vmem", 8320,
                pass_words),
        {"name": "morpher.verify_batch", "t0_ns": t0, "t1_ns": t0 + 41 * MS,
         "id": root, "parent": None, "root": root,
         "attrs": {"kernel": "K", "seeds": 8}},
    ]


# the warm unit (0-41 ms) passed over whole images and falls outside the
# window; the units in it (100-141, 150-191 ms) pass over 4,096 lanes:
# (1,000 x 4,096 / 32,896 + 3,000 x 4,096 / 8,320) / 4,000 of 100
RECORDS = (_unit(0, 1000, 32896) + _unit(100 * MS, 2000, 4096)
           + _unit(150 * MS, 3000, 4096))
WANT = 100.0 * (1000 * 4096 / 32896 + 3000 * 4096 / 8320) / 4000


def _run():
    return RunData(workload="table1.verify8", device_kind="TPU v5 lite",
                   window_s=WINDOW_S, records=[], trace=None, spans={})


def _without(recs, *keys):
    return [dict(r, attrs={k: v for k, v in r["attrs"].items()
                           if k not in keys}) for r in recs]


@pytest.mark.parametrize("name", ["sim_image_pass_share.verify",
                                  "sim_image_pass_share.kws"])
@pytest.mark.parametrize("recs, want", [
    (RECORDS, WANT),
    # a program whose kernel launches carry no ``pass_words`` passes over
    # the whole image
    (_without(RECORDS, "pass_words", "image_lanes"), 100.0),
    # only scan launches, or a program that has only the scan: no kernel
    (_without(RECORDS, "body"), None),
    ([r for r in RECORDS if r["attrs"].get("body") != "vmem"], None),
    ([], None),
], ids=["windowed", "no_pass_words_attr", "no_body_attr", "scan_only",
        "no_records"])
def test_image_pass_share(monkeypatch, name, recs, want):
    monkeypatch.setattr(programspans, "records", lambda: list(recs))
    got = load_reader(name)(_run())
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
