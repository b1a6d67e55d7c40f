"""Process-pool probe: runs the toolchain's compile worker in a pool worker
and reports which JAX modules that worker process has loaded."""
import sys

from repro.core.toolchain import _compile_worker


def compile_in_worker(payload: str):
    out = _compile_worker(payload)
    return out, sorted(m for m in sys.modules
                       if m == "jax" or m.startswith("jax."))
