"""DFG oracle: device nanoseconds per oracle step (one invocation x loop
iteration, all rows of the call at once).  The device time of the XLA
modules named ``jit_morpher_refexec*`` in the traced window
(``run.trace.module_s``), over the ``steps`` (invocations x mapped
iterations) of the window's ``morpher.oracle`` spans.  Window rule
(``bench/programspans.py``): the spans that start at or after the end of
the program's last span less the window.  None without oracle spans that
carry ``steps`` (a program that does not count them) or without the named
modules."""
from bench import programspans

MODULE = "jit_morpher_refexec"
SPAN = "morpher.oracle"


def read(run):
    if run.trace is None:
        return None
    steps = sum(r["attrs"].get("steps", 0)
                for r in programspans.in_window(programspans.records(),
                                                run.window_s)
                if r["name"] == SPAN)
    device_s = sum(s for name, s in run.trace.module_s.items()
                   if name.startswith(MODULE))
    if not steps or not device_s:
        return None
    return device_s / steps * 1e9
