"""DFG oracle: per cent of the oracle steps on the 64-PE layers that ran
the VMEM-resident Pallas kernel, read as ``oracle_vmem_step_share.verify``
reads it (the ``body`` and ``steps`` attrs of the window's
``morpher.oracle`` spans)."""
from bench.harness import load_reader

read = load_reader("oracle_vmem_step_share.verify")
