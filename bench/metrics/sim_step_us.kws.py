"""Simulator: device microseconds per launched step on the 64-PE fabric,
read as ``sim_step_us.verify`` reads it (the ``jit_morpher_sim*`` modules of
the traced window over the steps of its ``morpher.sim.launch`` spans)."""
from bench.harness import load_reader

read = load_reader("sim_step_us.verify")
