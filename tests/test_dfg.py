"""DFG IR + kernel-library semantics: the DFG oracle must reproduce the
numpy golden model for every Table-I kernel variant."""
import numpy as np
import pytest

from repro.core.dfg import DFG, DFGBuilder, Op, Operand, wrap
from repro.core.kernels_lib import build_conv, build_gemm, table1_kernels
from repro.core.verify import (check_dfg_semantics_batch,
                               generate_test_data_batch)


def test_wrap16():
    assert wrap(32767) == 32767
    assert wrap(32768) == -32768
    assert wrap(-32769) == 32767
    assert wrap(65536) == 0


def test_builder_and_topo():
    b = DFGBuilder("t")
    c1 = b.const(1)
    k = b.add(Operand(0, 0), c1)
    b.dfg.nodes[k].operands = (Operand(k, dist=1, init=-1), Operand(c1))
    st = b.store("bank0", k, k)
    dfg = b.build()
    order = dfg.topo_order()
    assert order.index(c1) < order.index(k) < order.index(st)


def test_carried_init_semantics():
    # k = k_prev + 1, init -1: iteration n must produce n
    b = DFGBuilder("ind")
    c1 = b.const(1)
    k = b.add(Operand(0, 0), c1)
    b.dfg.nodes[k].operands = (Operand(k, dist=1, init=-1), Operand(c1))
    b.store("bank0", k, k)
    dfg = b.build()
    mem = dfg.reference_execute(5, {"bank0": [0] * 8}, {})
    assert mem["bank0"][4] == 4


@pytest.mark.parametrize("name", ["GEMM", "GEMM-U", "GEMM-U-C",
                                  "CONV", "CONV-U-C-1", "CONV-U-C-2"])
def test_kernel_dfg_matches_golden(name):
    spec = table1_kernels(small=True)[name]
    data = generate_test_data_batch(spec, [3])
    check_dfg_semantics_batch(spec, data)   # raises on mismatch


def test_node_counts_paper_ballpark():
    full = table1_kernels(small=False)
    paper = {"GEMM": 26, "GEMM-U": 58, "GEMM-U-C": 79,
             "CONV": 27, "CONV-U-C-1": 100, "CONV-U-C-2": 153}
    for name, spec in full.items():
        ours = spec.dfg.n_nodes
        assert 0.3 * paper[name] <= ours <= 1.5 * paper[name], \
            f"{name}: {ours} vs paper {paper[name]}"


def test_small_and_full_same_structure():
    # identical loop structure; +-2 nodes of slack for base-offset adds
    # (the full-dims O tile fills a whole bank, shifting the data layout)
    small = table1_kernels(small=True)
    full = table1_kernels(small=False)
    for name in small:
        assert abs(small[name].dfg.n_nodes - full[name].dfg.n_nodes) <= 2, \
            (name, small[name].dfg.n_nodes, full[name].dfg.n_nodes)
