"""Simulator launch: per cent of the traced window in which the device was
busy inside the benchmark's ``bench.sim`` spans, the calls into
``simulate_batch`` / ``simulate_multi`` (``bench/capture.py``).  The span is
the launch's own call, so the share holds whatever XLA names the
simulator's programs."""
from bench.tracefile import span_share


def read(run):
    return span_share(run.trace, "bench.sim")
