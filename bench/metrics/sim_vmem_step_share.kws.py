"""Simulator: per cent of the launched steps on the 64-PE fabric that ran
the VMEM-resident Pallas kernel, read as ``sim_vmem_step_share.verify``
reads it (the ``body`` attr of the window's ``morpher.sim.launch``
spans)."""
from bench.harness import load_reader

read = load_reader("sim_vmem_step_share.verify")
