"""Depthwise KxK convolution, stride 1, TF SAME padding, bias and ReLU (a
DS-CNN block's first layer): O[i,j,c] = relu(b[c] + sum X[i+k1, j+k2, c] *
W[k1,k2,c]), its channels split evenly over ``partitions`` clusters.
Partition k holds its channels' padded input planes as [c][row][col]
(``I<k>``), weights as [c][k1][k2] (``W<k>``), bias (``B<k>``) and output
as [c][i][j] (``O<k>``)."""
import numpy as np


def draw(rng, kw):
    n, H, W, C, K = kw["partitions"], kw["H"], kw["W"], kw["C"], kw["K"]
    x = rng.integers(-8, 8, size=(H, W, C))
    w = rng.integers(-4, 4, size=(K, K, C))
    b = rng.integers(-64, 64, size=C)
    p = (K - 1) // 2
    xp = np.pad(x, ((p, K - 1 - p), (p, K - 1 - p), (0, 0)))
    cp = C // n
    out = {}
    for k in range(n):
        ch = slice(k * cp, (k + 1) * cp)
        out[f"I{k}"] = np.transpose(xp[:, :, ch], (2, 0, 1)).reshape(-1)
        out[f"B{k}"] = b[ch]
        out[f"W{k}"] = np.transpose(w[:, :, ch], (2, 0, 1)).reshape(-1)
        out[f"O{k}"] = np.zeros(cp * H * W, np.int64)
    return out


def compute(arrays, kw, wrap):
    n, H, W, C, K = kw["partitions"], kw["H"], kw["W"], kw["C"], kw["K"]
    cp = C // n
    xp = np.concatenate([arrays[f"I{k}"].reshape(cp, H + K - 1, W + K - 1)
                         for k in range(n)])              # [C, PH, PW]
    w = np.concatenate([arrays[f"W{k}"].reshape(cp, K, K) for k in range(n)])
    b = np.concatenate([arrays[f"B{k}"] for k in range(n)])
    y = np.zeros((C, H, W), np.int64)
    for i in range(H):
        for j in range(W):
            y[:, i, j] = np.einsum("chw,chw->c", xp[:, i:i + K, j:j + K], w)
    y = np.maximum(wrap(y + b[:, None, None]), 0)
    return {f"O{k}": y[k * cp:(k + 1) * cp].reshape(-1) for k in range(n)}
