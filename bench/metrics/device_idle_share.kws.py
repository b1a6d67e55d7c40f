"""Device: per cent of the traced window in which no operation ran on it."""
from bench.tracefile import idle_share


def read(run):
    return idle_share(run.trace)
