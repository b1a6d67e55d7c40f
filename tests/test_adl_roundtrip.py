"""ADL round-trip property tests: ``from_json(to_json(arch)) == arch``
over randomly drawn valid architectures — torus and mesh topologies, shuffled
non-contiguous bank ids, heterogeneous per-PE op sets, optional
clustering — plus canonical-form stability of the serialized JSON."""
import json

import pytest

pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (pip install -e '.[test]')")
from hypothesis import given, settings, strategies as st

from repro.core.adl import CGRAArch, MemBank, cluster_4x4

ALL_OPS = sorted(json.loads(cluster_4x4().to_json())["fu_ops"])


@st.composite
def arch_strategy(draw):
    rows = draw(st.integers(1, 8))
    cols = draw(st.integers(1, 8))
    n_pes = rows * cols
    n_banks = draw(st.integers(0, 4))
    # unique, possibly non-contiguous ids in arbitrary declaration order
    ids = draw(st.lists(st.integers(0, 31), min_size=n_banks,
                        max_size=n_banks, unique=True))
    banks = [MemBank(bid,
                     draw(st.sampled_from((1024, 4096, 8192))),
                     tuple(sorted(draw(st.sets(st.integers(0, n_pes - 1),
                                               min_size=1, max_size=4)))))
             for bid in ids]
    per_pe = draw(st.dictionaries(
        st.integers(0, n_pes - 1),
        st.sets(st.sampled_from(ALL_OPS), min_size=1).map(frozenset),
        max_size=3))
    clusters = [list(range(n_pes))] if draw(st.booleans()) else []
    return CGRAArch(
        name=draw(st.sampled_from(("hyp-a", "hyp-b"))),
        rows=rows, cols=cols,
        datapath_bits=draw(st.sampled_from((8, 16, 32))),
        regfile_size=draw(st.integers(1, 16)),
        livein_regs=draw(st.integers(0, 8)),
        # a 1-wide torus wraps a PE onto itself, which validate() refuses
        banks=banks, torus=rows >= 2 and cols >= 2 and draw(st.booleans()),
        per_pe_ops=per_pe, clusters=clusters)


@settings(max_examples=60, deadline=None)
@given(arch_strategy())
def test_adl_json_roundtrip_property(arch):
    arch.validate()
    again = CGRAArch.from_json(arch.to_json())
    assert again == arch
    # the serialized form is canonical: stable across a round trip
    assert again.to_json() == arch.to_json()


@settings(max_examples=20, deadline=None)
@given(arch_strategy(), st.integers(0, 3))
def test_adl_bank_lookup_is_by_id(arch, k):
    """pes_of_bank returns the declared PEs of the *id*, regardless of
    where the bank sits in the declaration list."""
    if not arch.banks:
        return
    b = arch.banks[k % len(arch.banks)]
    assert arch.pes_of_bank(b.id) == b.pes
