"""Simulator: per cent of the launched scan steps whose launch ran the
VMEM-resident Pallas kernel instead of the per-cycle scan: steps of the
``morpher.sim.launch`` spans whose ``body`` attr is ``"vmem"``, over all
their steps.  A launch without the attr (a program that has only the scan)
counts as the scan.  Window rule (``bench/programspans.py``): the launch
spans that start at or after the end of the program's last span less the
window.  None without launch spans."""
from bench.programspans import launches


def read(run):
    done = launches(run)
    steps = sum(a["steps"] for a in done)
    if not steps:
        return None
    return 100.0 * sum(a["steps"] for a in done
                       if a.get("body") == "vmem") / steps
