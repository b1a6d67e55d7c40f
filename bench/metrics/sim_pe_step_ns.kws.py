"""Simulator: device nanoseconds per launched step and simulated PE: the
device time of the XLA modules named ``jit_morpher_sim*`` in the traced
window over the sum of ``steps`` x ``pes`` of the window's
``morpher.sim.launch`` spans, so fabrics of different sizes compare (the
4x4's 0.3465 us per step is 21.7 ns per PE).  Window rule
(``bench/programspans.py``).  None without launch spans that carry
``pes`` or without the named modules."""
from bench.programspans import launches

MODULE = "jit_morpher_sim"


def read(run):
    if run.trace is None:
        return None
    pe_steps = sum(a["steps"] * a["pes"] for a in launches(run)
                   if "pes" in a)
    device_s = sum(s for name, s in run.trace.module_s.items()
                   if name.startswith(MODULE))
    if not pe_steps or not device_s:
        return None
    return device_s / pe_steps * 1e9
