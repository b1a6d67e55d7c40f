"""DFG oracle: per cent of the oracle steps whose call ran the
VMEM-resident Pallas kernel instead of the double scan: ``steps`` of the
``morpher.oracle`` spans whose ``body`` attr is ``"vmem"``, over all
their ``steps``.  Window rule (``bench/programspans.py``): the spans that
start at or after the end of the program's last span less the window.
None without oracle spans that carry ``steps`` (a program that does not
count them)."""
from bench import programspans

SPAN = "morpher.oracle"


def read(run):
    done = [r["attrs"] for r in programspans.in_window(programspans.records(),
                                                       run.window_s)
            if r["name"] == SPAN]
    steps = sum(a.get("steps", 0) for a in done)
    if not steps:
        return None
    return 100.0 * sum(a["steps"] for a in done
                       if a.get("body") == "vmem") / steps
