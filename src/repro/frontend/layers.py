"""Whole DNN layers on a multi-cluster fabric: the layers of an edge model at
their published sizes, each spread over the target's logical clusters.

Each builder splits one layer into one partition per cluster and traces
every partition as a disjoint body (``KernelContext.partition``), whose
arrays lie in that cluster's own two banks (the first holds the streamed
activations and the bias, the second the weights and the output, so each
bus carries half of the accesses).  The mapper
places each partition on its cluster (``core.mapper``), so every bank bus of
the fabric carries its cluster's share of the traffic.  On a one-cluster
fabric the same builders give one partition; ``partitions`` asks for fewer
partitions than clusters.

  conv2d   strided 2-D convolution of a single-channel input, SAME padding
  dwconv   depthwise KxK convolution, stride 1, SAME padding
  pwconv   pointwise (1x1) convolution over a flattened position axis

Every layer ends with bias and ReLU on the fabric (batch norm folded into
weights and bias, as at inference), accumulates in the datapath's
wraparound arithmetic, and takes outer-loop address bases from the host as
live-ins, one set per partition.  Where a layer accumulates across mapped
iterations it keeps the sum in a register and stores ``relu(sum + bias)``
every iteration: the last store holds the whole sum.

Test data follow Table I's style: activations in [-8, 8), weights in
[-4, 4), biases in [-64, 64), drawn as whole-layer tensors (inputs HWC,
weights HWIO) in that order, then padded, split and copied into each
partition's arrays by the host.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.adl import CGRAArch, morpher_8x8
from ..core.kernels_lib import KernelSpec, _bank_arrays, _wrap16
from ..core.layout import ArrayDecl, DataLayout, assign_layout
from .tracer import KernelContext, unroll


def _same_pad(size: int, k: int, stride: int) -> Tuple[int, int, int]:
    """(output size, leading pad, trailing pad) of TF SAME padding."""
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return out, total // 2, total - total // 2


def _partitioned_layout(arch: CGRAArch,
                        parts: Sequence[Sequence[Tuple[str, int, int]]]
                        ) -> DataLayout:
    """Partition k's arrays ``(name, words, side)`` in cluster k's banks:
    side 0 is the cluster's first bank, side 1 its second."""
    banks = arch.cluster_banks()
    if len(banks) < len(parts) or any(len(b) < 2 for b in banks):
        raise ValueError(f"{arch.name}: {len(parts)} partitions need as "
                         f"many clusters with two banks each")
    order = [b for k in range(len(parts)) for b in banks[k][:2]]
    return assign_layout(arch, [
        ArrayDecl(name, words, bank_pref=2 * k + side)
        for k, part in enumerate(parts) for name, words, side in part],
        banks=order)


def _clusters(arch: CGRAArch) -> int:
    """Partitions a layer takes by default: one per cluster."""
    return max(1, len(arch.clusters))


def _relu_wrap(x: np.ndarray) -> np.ndarray:
    return np.maximum(_wrap16(x), 0)


def _io(layout: DataLayout,
        fill: Callable[[Dict[str, np.ndarray], np.random.Generator], None],
        compute: Callable[[Dict[str, np.ndarray]], Dict[str, np.ndarray]],
        names: Sequence[str]):
    """``init_banks`` and ``golden`` of a partitioned layer: ``fill`` draws
    the partitions' arrays, ``compute`` maps the arrays read back from the
    banks to the output arrays."""
    def put(banks, name, values):
        p = layout.placements[name]
        banks[p.bank_array][p.base:p.base + p.words] = \
            np.asarray(values).reshape(-1)

    def init(rng: np.random.Generator) -> Dict[str, np.ndarray]:
        arrays: Dict[str, np.ndarray] = {}
        fill(arrays, rng)
        banks = _bank_arrays(layout)
        for name, values in arrays.items():
            put(banks, name, values)
        return banks

    def golden(banks: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        out = {k: v.copy() for k, v in banks.items()}
        arrays = {}
        for name in names:
            p = layout.placements[name]
            arrays[name] = banks[p.bank_array][p.base:p.base + p.words]
        for name, values in compute(arrays).items():
            put(out, name, values)
        return out

    return init, golden


# ======================================================================
# Strided 2-D convolution, one input channel, SAME padding, bias + ReLU
# ======================================================================
def build_conv2d(H: int = 49, W: int = 10, C_out: int = 64, KH: int = 10,
                 KW: int = 4, stride: int = 2, partitions: int = 0,
                 arch: Optional[CGRAArch] = None) -> KernelSpec:
    """O[c,i,j] = relu(b[c] + sum_{kh,kw} X[s*i+kh, s*j+kw] * W[kh,kw,c]).

    Output channels split evenly over the clusters.  Partition k holds a
    copy of the padded input and its channels' weights ([c][kh][kw]), bias
    and output ([c][i][j]); the mapped loop runs over the kernel rows (KW
    MACs unrolled) with live-ins for one output position and channel.
    """
    arch = arch or morpher_8x8()
    n = partitions or _clusters(arch)
    if C_out % n:
        raise ValueError(f"C_out={C_out} does not split over {n} clusters")
    CP = C_out // n
    OH, top, bottom = _same_pad(H, KH, stride)
    OW, left, right = _same_pad(W, KW, stride)
    PH, PW = H + top + bottom, W + left + right
    layout = _partitioned_layout(arch, [
        [(f"I{k}", PH * PW, 0), (f"B{k}", CP, 0),
         (f"W{k}", CP * KH * KW, 1), (f"O{k}", CP * OH * OW, 1)]
        for k in range(n)])

    ctx = KernelContext("conv2d", layout)
    for k in range(n):
        with ctx.partition():
            I, B, Wt, O = ctx.arrays(f"I{k}", f"B{k}", f"W{k}", f"O{k}")
            x, w = ctx.livein(f"x{k}"), ctx.livein(f"w{k}")
            o, b = ctx.livein(f"o{k}"), ctx.livein(f"b{k}")
            r = ctx.counter(step=PW, stop=(KH - 1) * PW, name="row")
            q = ctx.counter(step=KW, name="wrow")
            xa, wa = I.addr(x + r), Wt.addr(w + q)
            acc = ctx.running_sum(ctx.treesum(
                I.at(xa + kw) * Wt.at(wa + kw) for kw in unroll(KW)))
            O[o] = ctx.relu(acc + B[b])
    dfg = ctx.build()

    def fill(arrays, rng):
        X = rng.integers(-8, 8, size=(H, W))
        Wv = rng.integers(-4, 4, size=(KH, KW, C_out))
        Bv = rng.integers(-64, 64, size=C_out)
        Xp = np.pad(X, ((top, bottom), (left, right)))
        for k in range(n):
            ch = slice(k * CP, (k + 1) * CP)
            arrays[f"I{k}"] = Xp
            arrays[f"B{k}"] = Bv[ch]
            arrays[f"W{k}"] = np.moveaxis(Wv[:, :, ch], 2, 0)
            arrays[f"O{k}"] = np.zeros(CP * OH * OW, np.int64)

    def compute(arrays):
        out = {}
        for k in range(n):
            Xp = arrays[f"I{k}"].reshape(PH, PW)
            Wv = arrays[f"W{k}"].reshape(CP, KH, KW)
            acc = np.zeros((CP, OH, OW), np.int64)
            for kh in range(KH):
                for kw in range(KW):
                    win = Xp[kh:kh + stride * OH:stride,
                             kw:kw + stride * OW:stride]
                    acc += Wv[:, kh, kw, None, None] * win[None]
            acc += arrays[f"B{k}"][:, None, None]
            out[f"O{k}"] = _relu_wrap(acc)
        return out

    names = [f"{a}{k}" for k in range(n) for a in "IBW"]
    init, golden = _io(layout, fill, compute, names)
    invocations = [
        {key: val for k in range(n) for key, val in (
            (f"x{k}", stride * i * PW + stride * j), (f"w{k}", c * KH * KW),
            (f"o{k}", (c * OH + i) * OW + j), (f"b{k}", c))}
        for c in range(CP) for i in range(OH) for j in range(OW)]
    return KernelSpec(
        name=dfg.name, dfg=dfg, arch=arch, layout=layout, mapped_iters=KH,
        invocations=invocations, golden=golden, init_banks=init,
        meta=dict(H=H, W=W, C_out=C_out, KH=KH, KW=KW, stride=stride,
                  partitions=n, liveins_per_inv=4 * n))


# ======================================================================
# Depthwise KxK convolution, stride 1, SAME padding, bias + ReLU
# ======================================================================
def build_dwconv_layer(H: int = 25, W: int = 5, C: int = 64, K: int = 3,
                       partitions: int = 0,
                       arch: Optional[CGRAArch] = None) -> KernelSpec:
    """O[c,i,j] = relu(b[c] + sum_{k1,k2} X[i+k1, j+k2, c] * W[k1,k2,c])
    over the input padded by the host.

    Channels split evenly over the clusters; partition k holds its
    channels' padded input planes ([c][row][col]), weights ([c][k1][k2]),
    bias and output ([c][i][j]).  The mapped loop runs along an output row
    (all K*K MACs unrolled) with live-ins for the row and channel.
    """
    arch = arch or morpher_8x8()
    n = partitions or _clusters(arch)
    if C % n:
        raise ValueError(f"C={C} does not split over {n} clusters")
    CP = C // n
    _, top, bottom = _same_pad(H, K, 1)
    _, left, right = _same_pad(W, K, 1)
    PH, PW = H + top + bottom, W + left + right
    layout = _partitioned_layout(arch, [
        [(f"I{k}", CP * PH * PW, 0), (f"B{k}", CP, 0),
         (f"W{k}", CP * K * K, 1), (f"O{k}", CP * H * W, 1)]
        for k in range(n)])

    ctx = KernelContext("dwconv-layer", layout)
    for k in range(n):
        with ctx.partition():
            I, B, Wt, O = ctx.arrays(f"I{k}", f"B{k}", f"W{k}", f"O{k}")
            x, w = ctx.livein(f"x{k}"), ctx.livein(f"w{k}")
            o, b = ctx.livein(f"o{k}"), ctx.livein(f"b{k}")
            j = ctx.counter(stop=W - 1, name="j")
            xa, wa = I.addr(x + j), Wt.addr(w)
            y = ctx.treesum(I.at(xa + (k1 * PW + k2))
                            * Wt.at(wa + (k1 * K + k2))
                            for k1 in unroll(K) for k2 in unroll(K))
            O[o + j] = ctx.relu(y + B[b])
    dfg = ctx.build()

    def fill(arrays, rng):
        X = rng.integers(-8, 8, size=(H, W, C))
        Wv = rng.integers(-4, 4, size=(K, K, C))
        Bv = rng.integers(-64, 64, size=C)
        Xp = np.pad(X, ((top, bottom), (left, right), (0, 0)))
        for k in range(n):
            ch = slice(k * CP, (k + 1) * CP)
            arrays[f"I{k}"] = np.moveaxis(Xp[:, :, ch], 2, 0)
            arrays[f"B{k}"] = Bv[ch]
            arrays[f"W{k}"] = np.moveaxis(Wv[:, :, ch], 2, 0)
            arrays[f"O{k}"] = np.zeros(CP * H * W, np.int64)

    def compute(arrays):
        out = {}
        for k in range(n):
            Xp = arrays[f"I{k}"].reshape(CP, PH, PW)
            Wv = arrays[f"W{k}"].reshape(CP, K, K)
            acc = np.zeros((CP, H, W), np.int64)
            for k1 in range(K):
                for k2 in range(K):
                    acc += Wv[:, k1, k2, None, None] * Xp[:, k1:k1 + H,
                                                          k2:k2 + W]
            acc += arrays[f"B{k}"][:, None, None]
            out[f"O{k}"] = _relu_wrap(acc)
        return out

    names = [f"{a}{k}" for k in range(n) for a in "IBW"]
    init, golden = _io(layout, fill, compute, names)
    invocations = [
        {key: val for k in range(n) for key, val in (
            (f"x{k}", (c * PH + i) * PW), (f"w{k}", c * K * K),
            (f"o{k}", (c * H + i) * W), (f"b{k}", c))}
        for c in range(CP) for i in range(H)]
    return KernelSpec(
        name=dfg.name, dfg=dfg, arch=arch, layout=layout, mapped_iters=W,
        invocations=invocations, golden=golden, init_banks=init,
        meta=dict(H=H, W=W, C=C, K=K, partitions=n, liveins_per_inv=4 * n))


# ======================================================================
# Pointwise (1x1) convolution, bias + ReLU
# ======================================================================
def _split(total: int, parts: int) -> List[Tuple[int, int]]:
    """(start, size) of ``parts`` near-equal pieces, the larger first."""
    base, extra = divmod(total, parts)
    out, start = [], 0
    for p in range(parts):
        size = base + (p < extra)
        out.append((start, size))
        start += size
    return out


def build_pwconv(N: int = 125, C_in: int = 64, C_out: int = 64,
                 pos_parts: int = 2, unroll_by: int = 4, partitions: int = 0,
                 arch: Optional[CGRAArch] = None) -> KernelSpec:
    """O[p,o] = relu(b[o] + sum_c X[p,c] * W[c,o]) over N positions.

    Partition (a, d) of a ``pos_parts`` x (clusters / pos_parts) grid holds
    position block a of X ([p][c]), output-channel block d of W (transposed,
    [o][c]) and of the bias, and its output block ([p][o]).  The mapped
    loop runs over the input channels, ``unroll_by`` MACs per iteration,
    with live-ins for one output; partitions with fewer positions repeat
    their last output, which rewrites the same value.
    """
    arch = arch or morpher_8x8()
    n = partitions or _clusters(arch)
    if n % pos_parts or C_out % (n // pos_parts) or C_in % unroll_by:
        raise ValueError(f"{N}x{C_in}->{C_out} does not split into "
                         f"{pos_parts} position blocks over {n} clusters")
    chp = n // pos_parts
    CO = C_out // chp
    pos = _split(N, pos_parts)
    grid = [(a, d) for a in range(pos_parts) for d in range(chp)]
    layout = _partitioned_layout(arch, [
        [(f"B{k}", CO, 0), (f"X{k}", pos[a][1] * C_in, 0),
         (f"W{k}", CO * C_in, 1), (f"O{k}", pos[a][1] * CO, 1)]
        for k, (a, d) in enumerate(grid)])

    ctx = KernelContext("pwconv", layout)
    for k in range(n):
        with ctx.partition():
            X, B, Wt, O = ctx.arrays(f"X{k}", f"B{k}", f"W{k}", f"O{k}")
            x, w = ctx.livein(f"x{k}"), ctx.livein(f"w{k}")
            o, b = ctx.livein(f"o{k}"), ctx.livein(f"b{k}")
            q = ctx.counter(step=unroll_by, stop=C_in - unroll_by, name="c")
            xa, wa = X.addr(x + q), Wt.addr(w + q)
            acc = ctx.running_sum(ctx.treesum(
                X.at(xa + u) * Wt.at(wa + u) for u in unroll(unroll_by)))
            O[o] = ctx.relu(acc + B[b])
    dfg = ctx.build()

    def fill(arrays, rng):
        Xv = rng.integers(-8, 8, size=(N, C_in))
        Wv = rng.integers(-4, 4, size=(C_in, C_out))
        Bv = rng.integers(-64, 64, size=C_out)
        for k, (a, d) in enumerate(grid):
            p0, npos = pos[a]
            ch = slice(d * CO, (d + 1) * CO)
            arrays[f"X{k}"] = Xv[p0:p0 + npos]
            arrays[f"B{k}"] = Bv[ch]
            arrays[f"W{k}"] = Wv[:, ch].T
            arrays[f"O{k}"] = np.zeros(npos * CO, np.int64)

    def compute(arrays):
        out = {}
        for k, (a, d) in enumerate(grid):
            npos = pos[a][1]
            Xv = arrays[f"X{k}"].reshape(npos, C_in)
            Wv = arrays[f"W{k}"].reshape(CO, C_in)
            out[f"O{k}"] = _relu_wrap(Xv @ Wv.T + arrays[f"B{k}"][None, :])
        return out

    names = [f"{a}{k}" for k in range(n) for a in "XBW"]
    init, golden = _io(layout, fill, compute, names)
    npos_max = max(size for _, size in pos)
    invocations = []
    for p in range(npos_max):
        for oc in range(CO):
            inv = {}
            for k, (a, d) in enumerate(grid):
                pk = min(p, pos[a][1] - 1)
                inv.update({f"x{k}": pk * C_in, f"w{k}": oc * C_in,
                            f"o{k}": pk * CO + oc, f"b{k}": oc})
            invocations.append(inv)
    return KernelSpec(
        name=dfg.name, dfg=dfg, arch=arch, layout=layout,
        mapped_iters=C_in // unroll_by, invocations=invocations,
        golden=golden, init_banks=init,
        meta=dict(N=N, C_in=C_in, C_out=C_out, pos_parts=pos_parts,
                  unroll=unroll_by, partitions=n, liveins_per_inv=4 * n))
