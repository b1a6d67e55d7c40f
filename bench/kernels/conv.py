"""Valid 2-D convolution, one channel: O[i,j] += sum I[i+k1,j+k2] * W[k1,k2]
(Table I CONV, CONV-U-C-1, CONV-U-C-2)."""
import numpy as np


def _dims(kw):
    oh, ow, k = kw["OH"], kw["OW"], kw["K"]
    return oh, ow, k, oh + k - 1, ow + k - 1


def draw(rng, kw):
    oh, ow, k, ih, iw = _dims(kw)
    i = rng.integers(-8, 8, size=ih * iw)
    w = rng.integers(-4, 4, size=k * k)
    return {"I": i, "W": w, "O": np.zeros(oh * ow, np.int64)}


def compute(arrays, kw, wrap):
    oh, ow, k, ih, iw = _dims(kw)
    i = arrays["I"].reshape(ih, iw)
    w = arrays["W"].reshape(k, k)
    o = arrays["O"].reshape(oh, ow).copy()
    for k1 in range(k):
        for k2 in range(k):
            o = o + i[k1:k1 + oh, k2:k2 + ow] * w[k1, k2]
    return {"O": wrap(o).reshape(-1)}
