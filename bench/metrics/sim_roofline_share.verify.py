"""Simulator: per cent of peak HBM bandwidth that the simulated row-cycles
of the window needed (bench/work.py), over the window.  Real cycles and real
rows only, so bucket padding is lost throughput."""
from bench.work import sim_roofline_share


def read(run):
    return sim_roofline_share(run)
