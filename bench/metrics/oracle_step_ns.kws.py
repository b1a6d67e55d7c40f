"""DFG oracle: device nanoseconds per oracle step on the 64-PE layers,
read as ``oracle_step_ns.verify`` reads it (the ``jit_morpher_refexec*``
modules of the traced window over the ``steps`` of its ``morpher.oracle``
spans)."""
from bench.harness import load_reader

read = load_reader("oracle_step_ns.verify")
