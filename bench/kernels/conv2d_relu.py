"""Strided 2-D convolution of a one-channel input, TF SAME padding, bias and
ReLU (the KWS DS-CNN's first layer): O[i,j,c] = relu(b[c] + sum X[s*i+kh,
s*j+kw] * W[kh,kw,c]), its output channels split evenly over
``partitions`` clusters.  Partition k holds a copy of the padded input
(``I<k>``), its channels' weights as [c][kh][kw] (``W<k>``), bias
(``B<k>``) and output as [c][i][j] (``O<k>``)."""
import numpy as np


def _dims(kw):
    H, W, KH, KW, s = kw["H"], kw["W"], kw["KH"], kw["KW"], kw["stride"]
    OH, OW = -(-H // s), -(-W // s)
    ph, pw = max((OH - 1) * s + KH - H, 0), max((OW - 1) * s + KW - W, 0)
    return OH, OW, ((ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2))


def draw(rng, kw):
    n, C = kw["partitions"], kw["C_out"]
    x = rng.integers(-8, 8, size=(kw["H"], kw["W"]))
    w = rng.integers(-4, 4, size=(kw["KH"], kw["KW"], C))
    b = rng.integers(-64, 64, size=C)
    OH, OW, pads = _dims(kw)
    xp = np.pad(x, pads)
    cp = C // n
    out = {}
    for k in range(n):
        ch = slice(k * cp, (k + 1) * cp)
        out[f"I{k}"] = xp.reshape(-1)
        out[f"B{k}"] = b[ch]
        out[f"W{k}"] = np.transpose(w[:, :, ch], (2, 0, 1)).reshape(-1)
        out[f"O{k}"] = np.zeros(cp * OH * OW, np.int64)
    return out


def compute(arrays, kw, wrap):
    n, C, s = kw["partitions"], kw["C_out"], kw["stride"]
    KH, KW = kw["KH"], kw["KW"]
    OH, OW, pads = _dims(kw)
    PH = kw["H"] + sum(pads[0])
    xp = arrays["I0"].reshape(PH, -1)
    cp = C // n
    w = np.concatenate([arrays[f"W{k}"].reshape(cp, KH, KW)
                        for k in range(n)])                 # [C, KH, KW]
    b = np.concatenate([arrays[f"B{k}"] for k in range(n)])
    y = np.zeros((OH, OW, C), np.int64)
    for i in range(OH):
        for j in range(OW):
            win = xp[s * i:s * i + KH, s * j:s * j + KW]
            y[i, j] = np.einsum("hw,chw->c", win, w)
    y = np.maximum(wrap(y + b), 0)
    return {f"O{k}": np.transpose(y[:, :, k * cp:(k + 1) * cp],
                                  (2, 0, 1)).reshape(-1)
            for k in range(n)}
