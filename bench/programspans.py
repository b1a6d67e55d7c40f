"""The program's own host spans (``repro.core.obs``) in a run's window.

The program keeps a ring of span records, ``{"name", "t0_ns", "t1_ns",
"id", "parent", "root", "attrs"}`` on ``time.perf_counter_ns()``; each
simulator launch is a ``morpher.sim.launch`` span whose attrs count what it
launched (``steps``, ``rows``, ``row_steps``, ``real_row_steps``,
``pretiled``, ``built``, ...).

The window rule: keep the records whose ``t0_ns`` lies at or after the end
of the last ``morpher.*`` record less ``run.window_s``.  Nothing of the
program runs after the window (the plain reference imports none of it), so
the last record closes in the window's last unit, and the warm-up unit,
which ends before the trace starts, falls outside.

A program without the ring (one that has no ``repro.core.obs``) gives no
records, and every reader built on them gives None.
"""
from __future__ import annotations

from typing import List

PREFIX = "morpher."
LAUNCH = "morpher.sim.launch"


def records() -> List[dict]:
    """The program's span records, or [] where it keeps none."""
    try:
        from repro.core import obs
    except ImportError:
        return []
    return obs.spans()


def in_window(recs: List[dict], window_s: float) -> List[dict]:
    """The program's records that started inside the window (see above)."""
    ours = [r for r in recs if r["name"].startswith(PREFIX)]
    if not ours:
        return []
    cut = max(r["t1_ns"] for r in ours) - window_s * 1e9
    return [r for r in ours if r["t0_ns"] >= cut]


def launches(run) -> List[dict]:
    """The attrs of every simulator launch in the run's window."""
    return [r["attrs"] for r in in_window(records(), run.window_s)
            if r["name"] == LAUNCH]
