"""Pointwise (1x1) convolution, bias and ReLU (a DS-CNN block's second
layer): O[p,o] = relu(b[o] + sum_c X[p,c] * W[c,o]) over N positions, split
into a ``pos_parts`` x (``partitions`` / ``pos_parts``) grid of position
blocks (the first blocks one larger where N does not divide) by
output-channel blocks.  Partition k = a * (partitions / pos_parts) + d
holds position block a of X as [p][c] (``X<k>``), output-channel block d
of W transposed to [o][c] (``W<k>``) and of the bias (``B<k>``), and its
output as [p][o] (``O<k>``)."""
import numpy as np


def _blocks(kw):
    n, a = kw["partitions"], kw["pos_parts"]
    N, d = kw["N"], n // a
    base, extra = divmod(N, a)
    sizes = [base + (i < extra) for i in range(a)]
    starts = np.cumsum([0] + sizes[:-1])
    co = kw["C_out"] // d
    return [(slice(starts[i], starts[i] + sizes[i]),
             slice(j * co, (j + 1) * co))
            for i in range(a) for j in range(d)]


def draw(rng, kw):
    x = rng.integers(-8, 8, size=(kw["N"], kw["C_in"]))
    w = rng.integers(-4, 4, size=(kw["C_in"], kw["C_out"]))
    b = rng.integers(-64, 64, size=kw["C_out"])
    out = {}
    for k, (rows, cols) in enumerate(_blocks(kw)):
        out[f"X{k}"] = x[rows].reshape(-1)
        out[f"B{k}"] = b[cols]
        out[f"W{k}"] = w[:, cols].T.reshape(-1)
        out[f"O{k}"] = np.zeros((rows.stop - rows.start) * len(b[cols]),
                                np.int64)
    return out


def compute(arrays, kw, wrap):
    N, Ci, Co = kw["N"], kw["C_in"], kw["C_out"]
    x = np.zeros((N, Ci), np.int64)
    w = np.zeros((Ci, Co), np.int64)
    b = np.zeros(Co, np.int64)
    blocks = _blocks(kw)
    for k, (rows, cols) in enumerate(blocks):
        x[rows] = arrays[f"X{k}"].reshape(-1, Ci)
        w[:, cols] = arrays[f"W{k}"].reshape(-1, Ci).T
        b[cols] = arrays[f"B{k}"]
    y = np.maximum(wrap(x @ w + b), 0)
    return {f"O{k}": y[rows, cols].reshape(-1)
            for k, (rows, cols) in enumerate(blocks)}
