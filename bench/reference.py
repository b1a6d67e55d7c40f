"""The plain reference: the bank images a configuration's kernel must leave.

For one (fabric, kernel) pair of a configuration and one seed it draws the
test data as the seed gives it, places each array where the configuration's
layout says, and computes the final image with the kernel's module under
``bench/kernels``.  It imports nothing of the program and reads nothing the
program made: sizes, layout and bank widths all come from the
configuration file.
"""
from __future__ import annotations

import importlib

import numpy as np


def wrapper(bits: int):
    """Two's-complement wrap to ``bits``: the datapath's arithmetic."""
    half, full = 1 << (bits - 1), 1 << bits

    def wrap(x):
        return ((np.asarray(x, np.int64) + half) & (full - 1)) - half
    return wrap


def kernel_module(kind: str):
    return importlib.import_module(f"bench.kernels.{kind}")


def _place(banks, layout, arrays):
    for name, values in arrays.items():
        bank, base, words = layout[name]
        banks[bank][base:base + words] = values


def images(kernel: dict, fabric: dict, seed: int, bits: int):
    """(initial, final) bank images of one seed: {bank name: int64 words}."""
    mod = kernel_module(kernel["kind"])
    arrays = mod.draw(np.random.default_rng(seed), kernel["kwargs"])
    init = {b: np.zeros(w, np.int64) for b, w in fabric["banks"].items()}
    _place(init, kernel["layout"], arrays)
    final = {b: v.copy() for b, v in init.items()}
    _place(final, kernel["layout"],
           mod.compute(arrays, kernel["kwargs"], wrapper(bits)))
    return init, final


def words_wrong(got: dict, want: dict) -> int:
    """Words of ``got`` that differ from ``want``, over every bank of
    ``want``; a missing bank or a bank of the wrong width counts whole."""
    n = 0
    for bank, w in want.items():
        g = got.get(bank)
        if g is None or len(g) != len(w):
            n += len(w)
            continue
        n += int(np.count_nonzero(np.asarray(g, np.int64) != w))
    return n
