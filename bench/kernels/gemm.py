"""GEMM tile: O[TI,TJ] += W[TI,TK] @ I[TK,TJ] (Table I GEMM, GEMM-U, GEMM-U-C)."""
import numpy as np


def draw(rng, kw):
    ti, tk, tj = kw["TI"], kw["TK"], kw["TJ"]
    w = rng.integers(-8, 8, size=ti * tk)
    i = rng.integers(-8, 8, size=tk * tj)
    return {"W": w, "I": i, "O": np.zeros(ti * tj, np.int64)}


def compute(arrays, kw, wrap):
    ti, tk, tj = kw["TI"], kw["TK"], kw["TJ"]
    w = arrays["W"].reshape(ti, tk)
    i = arrays["I"].reshape(tk, tj)
    o = arrays["O"].reshape(ti, tj)
    return {"O": wrap(o + w @ i).reshape(-1)}
