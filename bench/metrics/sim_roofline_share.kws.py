"""Simulator: per cent of peak HBM bandwidth that the simulated row-cycles
of the window needed at P=64 (``bench/work.py``), over the window."""
from bench.work import sim_roofline_share


def read(run):
    return sim_roofline_share(run)
