"""Simulator: device microseconds per launched scan step.  The device time
of the XLA modules named ``jit_morpher_sim*`` in the traced window
(``run.trace.module_s``), over the scan steps the window's
``morpher.sim.launch`` spans launched (bucketed cycles x invocations, any
batch).  Window rule (``bench/programspans.py``): the launch spans that
start at or after the end of the program's last span less the window.
None without launch spans or without the named modules."""
from bench.programspans import launches

MODULE = "jit_morpher_sim"


def read(run):
    if run.trace is None:
        return None
    steps = sum(a["steps"] for a in launches(run))
    device_s = sum(s for name, s in run.trace.module_s.items()
                   if name.startswith(MODULE))
    if not steps or not device_s:
        return None
    return device_s / steps * 1e6
