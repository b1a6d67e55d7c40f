"""Broken timed paths, for the tests and the precision control.

Each entry is a ``prepare(runner)`` hook for ``harness.run_cell``: it turns
the program's own image comparison off, so that only the benchmark's
reference can catch what it plants, and plants one fault in what the
simulator launches give back.  The benchmark's own runs use none of them.
"""
from __future__ import annotations

import numpy as np

CONTROL_BITS = 8          # the datapath width below the configuration's 16


def _without_own_check(runner) -> None:
    from repro.core import toolchain
    toolchain._check_batch = lambda *args, **kwargs: None


def unchanged(runner) -> None:
    """Every launch returns its initial images: a step that leaves its
    state as it found it."""
    _without_own_check(runner)
    runner.recorder.fault = lambda cfg, inits, finals: [
        {b: np.asarray(v).copy() for b, v in banks.items()} for banks in inits]


def half_batch(runner) -> None:
    """Half of every launch's rows are left out."""
    _without_own_check(runner)
    runner.recorder.fault = lambda cfg, inits, finals: \
        list(finals)[:max(1, len(finals) // 2)]


def altered_word(runner) -> None:
    """One word of one image is altered where the launch produces it."""
    _without_own_check(runner)

    def fault(cfg, inits, finals):
        finals = [dict(f) for f in finals]
        bank = sorted(finals[0])[0]
        words = np.asarray(finals[0][bank]).copy()
        words[0] ^= 1
        finals[0][bank] = words
        return finals
    runner.recorder.fault = fault


def oracle_skipped(runner) -> None:
    """The DFG oracle is never run: the cross-check of the kernel's
    dataflow graph against its golden model is left out."""
    _without_own_check(runner)
    from repro.core import verify
    verify.check_dfg_semantics_batch = lambda *args, **kwargs: None


def control(runner) -> None:
    """The plain reference in the simulator's place, on an 8-bit datapath
    (the precision below the configuration's int16), fed the entry's own
    initial images."""
    from bench.reference import kernel_module, wrapper
    _without_own_check(runner)

    def replace(cfg, inits):
        pair = next(p for p, ck, _ in runner.calls[-1].entries
                    if ck is not None and ck.cfg is cfg)
        lay, kw = pair.kernel["layout"], pair.kernel["kwargs"]
        compute = kernel_module(pair.kernel["kind"]).compute
        out = []
        for banks in inits:
            final = {b: np.asarray(v, np.int64).copy()
                     for b, v in banks.items()}
            arrays = {n: final[b][o:o + w].copy()
                      for n, (b, o, w) in lay.items()}
            for n, v in compute(arrays, kw, wrapper(CONTROL_BITS)).items():
                b, o, w = lay[n]
                final[b][o:o + w] = v
            out.append(final)
        return out
    runner.recorder.replace = replace


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered_word": altered_word, "oracle_skipped": oracle_skipped,
          "control": control}
