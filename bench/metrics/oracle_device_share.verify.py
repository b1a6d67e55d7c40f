"""DFG oracle: per cent of the traced window in which the device was busy
inside the benchmark's ``bench.oracle`` spans, the calls into
``verify.reference_banks_batch`` (``bench/capture.py``), which runs the
``refexec`` executables."""
from bench.tracefile import span_share


def read(run):
    return span_share(run.trace, "bench.oracle")
