"""Simulator: per cent of the launched scan steps whose body gathered its
configuration slot every cycle instead of streaming pre-tiled planes (the
tiled streams would pass ``_TILE_BYTES_LIMIT``): steps of the
``morpher.sim.launch`` spans with ``pretiled`` false, over all their steps.
Window rule (``bench/programspans.py``): the launch spans that start at or
after the end of the program's last span less the window.  None without
launch spans."""
from bench.programspans import launches


def read(run):
    done = launches(run)
    steps = sum(a["steps"] for a in done)
    if not steps:
        return None
    return 100.0 * sum(a["steps"] for a in done if not a["pretiled"]) / steps
