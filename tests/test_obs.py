"""Host spans and launch counters (``repro.core.obs``) and the names of the
simulator's and the oracle's device programs.

Spans nest by thread, share the id of their outermost span as ``root`` and
stay within the ring's bound; every simulator launch records what it
launched (scan steps, rows, real row-steps, which body ran, tiling,
whether it built the executable); one ``verify_batch`` opens the spans of its layers under one
root; and the lowered programs carry stable module names."""
import re

import numpy as np
import pytest

from repro.core import obs, simcache, simulator
from repro.core.kernels_lib import build_gemm
from repro.core.toolchain import Toolchain, verify_stacked
from repro.core.verify import generate_test_data

VERIFY_SPANS = {"morpher.verify_batch", "morpher.testdata", "morpher.oracle",
                "morpher.sim.planes", "morpher.sim.launch",
                "morpher.compare"}


@pytest.fixture(scope="module")
def ck():
    return Toolchain(cache_dir="").compile(
        build_gemm(TI=4, TK=4, TJ=4, unroll=1))


def _tree(name):
    """The records under the newest span called ``name``, by name."""
    recs = obs.spans()
    top = [r for r in recs if r["name"] == name][-1]
    under = [r for r in recs if r["root"] == top["root"]]
    return top, under


def _last(name):
    return [r for r in obs.spans() if r["name"] == name][-1]


# ------------------------------------------------------------- the ring
def test_spans_nest_under_one_root():
    with obs.span("morpher.t.outer", k="x") as attrs:
        with obs.span("morpher.t.mid"):
            with obs.span("morpher.t.inner"):
                pass
        with obs.span("morpher.t.sibling"):
            pass
        attrs["added"] = 3
    with obs.span("morpher.t.next"):
        pass
    recs = {r["name"]: r for r in obs.spans()[-5:]}
    outer, mid = recs["morpher.t.outer"], recs["morpher.t.mid"]
    assert outer["parent"] is None and outer["root"] == outer["id"]
    assert outer["attrs"] == {"k": "x", "added": 3}
    assert mid["parent"] == outer["id"]
    assert recs["morpher.t.inner"]["parent"] == mid["id"]
    assert recs["morpher.t.sibling"]["parent"] == outer["id"]
    for n in ("morpher.t.mid", "morpher.t.inner", "morpher.t.sibling"):
        assert recs[n]["root"] == outer["id"]
        assert outer["t0_ns"] <= recs[n]["t0_ns"] <= recs[n]["t1_ns"] \
            <= outer["t1_ns"]
    nxt = recs["morpher.t.next"]
    assert nxt["parent"] is None and nxt["root"] == nxt["id"] != outer["id"]
    # a closed span is appended when it closes: inner before its parents
    names = [r["name"] for r in obs.spans()[-5:]]
    assert names.index("morpher.t.inner") < names.index("morpher.t.mid") \
        < names.index("morpher.t.outer")


def test_span_closes_on_error_and_ring_is_bounded():
    with pytest.raises(ValueError):
        with obs.span("morpher.t.raises"):
            raise ValueError("x")
    assert obs.spans()[-1]["name"] == "morpher.t.raises"
    with obs.span("morpher.t.after"):
        pass
    assert obs.spans()[-1]["parent"] is None      # the stack was popped
    for i in range(obs.RING_MAX + 10):
        with obs.span("morpher.t.fill", i=i):
            pass
    recs = obs.spans()
    assert len(recs) == obs.RING_MAX
    assert recs[0]["attrs"]["i"] == 10
    assert recs[-1]["attrs"]["i"] == obs.RING_MAX + 9


# ----------------------------------------------------- launch counters
def test_launch_counters_match_shapes(ck, monkeypatch):
    cfg, n_inv = ck.cfg, len(ck.invocations)
    real = cfg.n_cycles(ck.mapped_iters)
    steps = simcache.bucket_cycles(real) * n_inv
    banks = [generate_test_data(ck.spec, s).init_banks for s in (1, 2, 3)]
    shapes = (4, cfg.total_words, cfg.P, cfg.RF, max(1, cfg.LI), cfg.II,
              n_inv)
    body = simulator._body(False, *shapes)
    simcache.clear()
    tiled = ck.run_batch(banks)
    first = _last("morpher.sim.launch")["attrs"]
    assert first == {"multi": False, "invocations": n_inv, "steps": steps,
                     "rows": 4, "real_rows": 3, "row_steps": steps * 4,
                     "real_row_steps": real * n_inv * 3, "body": body,
                     "pes": cfg.P,
                     "vmem_bytes": simulator._vmem_bytes(*shapes),
                     "image_lanes": simulator._lanes(cfg.total_words),
                     "pass_words": simulator._lanes(cfg.total_words),
                     "pretiled": True, "built": True}
    ck.run_batch(banks)
    assert _last("morpher.sim.launch")["attrs"]["built"] is False

    # under a tiling cap this small the body gathers the slot every cycle
    monkeypatch.setattr(simulator, "_TILE_BYTES_LIMIT", 1)
    simcache.clear()
    untiled = ck.run_batch(banks)
    attrs = _last("morpher.sim.launch")["attrs"]
    assert attrs["pretiled"] is False and attrs["built"] is True
    for a, b in zip(tiled, untiled):
        for bank in a:
            np.testing.assert_array_equal(a[bank], b[bank])
    simcache.clear()


def test_launch_body_follows_dispatch(ck, monkeypatch):
    """On a TPU backend the launch runs the VMEM kernel (here in the
    Pallas interpreter) and says so; the scan's tiling flag is off."""
    import functools

    import jax
    cfg, n_inv = ck.cfg, len(ck.invocations)
    banks = [generate_test_data(ck.spec, s).init_banks for s in (1, 2, 3)]
    simcache.clear()
    scan = ck.run_batch(banks)
    assert _last("morpher.sim.launch")["attrs"]["body"] == "scan"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(simulator, "_vmem_sim", functools.partial(
        simulator._vmem_sim, interpret=True))
    assert simulator._body(False, 4, cfg.total_words, cfg.P, cfg.RF,
                           max(1, cfg.LI), cfg.II, n_inv) == "vmem"
    simcache.clear()
    vmem = ck.run_batch(banks)
    attrs = _last("morpher.sim.launch")["attrs"]
    assert (attrs["body"], attrs["pretiled"]) == ("vmem", False)
    # each LOAD and STORE passes over one 4,096-word bank, not the image
    assert (attrs["pass_words"], attrs["image_lanes"]) == (4096, 8320)
    for a, b in zip(scan, vmem):
        for bank in a:
            np.testing.assert_array_equal(a[bank], b[bank])
    simcache.clear()


def test_multi_launch_counters(ck):
    cfg, n_inv = ck.cfg, len(ck.invocations)
    real = cfg.n_cycles(ck.mapped_iters)
    steps = simcache.bucket_cycles(real) * n_inv
    b = [generate_test_data(ck.spec, s).init_banks for s in range(5)]
    simulator.simulate_multi([(cfg, b[:2], ck.invocations),
                              (cfg, b[2:], ck.invocations)],
                             ck.mapped_iters)
    attrs = _last("morpher.sim.launch")["attrs"]
    rows = simcache.bucket_rows(5)
    assert attrs["multi"] is True and attrs["steps"] == steps
    assert attrs["body"] == "scan"
    assert (attrs["rows"], attrs["real_rows"]) == (rows, 5)
    assert attrs["row_steps"] == steps * rows
    assert attrs["real_row_steps"] == real * n_inv * 5


def test_run_launches_through_simcache(ck):
    """The per-seed ``run`` is one exact-cycle launch through ``simcache``
    and the launch span, and ``verify`` is ``verify_batch`` of one."""
    n_inv = len(ck.invocations)
    init = generate_test_data(ck.spec, 0).init_banks
    simcache.clear()
    ck.run(init)
    attrs = _last("morpher.sim.launch")["attrs"]
    steps = ck.cfg.n_cycles(ck.mapped_iters) * n_inv
    assert (attrs["rows"], attrs["real_rows"]) == (1, 1)
    assert attrs["steps"] == attrs["real_row_steps"] == steps
    assert attrs["built"] is True
    assert simcache.stats() == {"entries": 1, "hits": 0, "misses": 1}
    ck.run(init)
    assert _last("morpher.sim.launch")["attrs"]["built"] is False
    assert simcache.stats() == {"entries": 1, "hits": 1, "misses": 1}
    last = obs.spans()[-1]["id"]
    ck.verify(seed=3)
    tops = [r for r in obs.spans()
            if r["name"] == "morpher.verify_batch" and r["id"] > last]
    assert [r["attrs"] for r in tops] == [{"kernel": ck.name, "seeds": 1}]
    simcache.clear()


# -------------------------------------------------------- span trees
def test_verify_batch_spans_share_one_root(ck):
    ck.verify_batch([4, 5, 6])
    top, under = _tree("morpher.verify_batch")
    assert top["attrs"] == {"kernel": ck.name, "seeds": 3}
    assert {r["name"] for r in under} == VERIFY_SPANS
    assert _last("morpher.oracle")["attrs"] == {
        "rows": 3, "body": "scan",
        "steps": len(ck.invocations) * ck.mapped_iters}
    for r in under:
        if r is not top:
            assert top["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] <= top["t1_ns"]


def test_gates_and_stacked_verify_spans(ck, monkeypatch):
    monkeypatch.setenv("MORPHER_CHECK", "1")
    monkeypatch.setenv("MORPHER_XVAL", "1")
    ck.verify_batch([7])
    _, under = _tree("morpher.verify_batch")
    assert {r["name"] for r in under} == VERIFY_SPANS | {"morpher.check",
                                                         "morpher.xval"}
    verify_stacked([ck, ck], [1, 2])
    top, under = _tree("morpher.verify_stacked")
    assert top["attrs"] == {"kernels": 2, "seeds": 2}
    launches = [r for r in under if r["name"] == "morpher.sim.launch"]
    assert [r["attrs"]["multi"] for r in launches
            if r["parent"] == top["id"]] == [True]
    # the rest are the cross-validation's exact-cycle runs, one per
    # (kernel, seed), each a batch of one under the xval span
    xval = [r for r in under if r["name"] == "morpher.xval"]
    rest = [r for r in launches if r["parent"] != top["id"]]
    assert len(xval) == 1 and len(rest) == 4
    assert all(r["parent"] == xval[0]["id"] and r["attrs"]["rows"] == 1
               for r in rest)


def test_compile_many_spans():
    tc = Toolchain(cache_dir="")
    specs = [build_gemm(TI=4, TK=4, TJ=4, unroll=1),
             build_gemm(TI=4, TK=4, TJ=6, unroll=1)]
    tc.compile_many(specs, jobs=1)
    top, under = _tree("morpher.compile_many")
    assert top["attrs"] == {"specs": 2, "cache_hits": 0}
    names = sorted(r["name"] for r in under)
    assert names == ["morpher.compile_many"] + ["morpher.config_gen"] * 2 \
        + ["morpher.map"] * 2
    tc.compile_many(specs, jobs=1)
    top, under = _tree("morpher.compile_many")
    assert top["attrs"]["cache_hits"] == 2 and len(under) == 1


# ------------------------------------------------ device program names
def _module(lowered) -> str:
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


def test_device_programs_have_stable_names(ck):
    import jax.numpy as jnp

    from repro.core.dfg import Op
    from repro.core.refexec import _lowered
    cfg, n_inv = ck.cfg, len(ck.invocations)
    sig = simulator._signature(cfg, ck.mapped_iters,
                               cfg.n_cycles(ck.mapped_iters), batch=2)
    mem = jnp.zeros((2, cfg.total_words), jnp.int16)
    li = jnp.zeros((n_inv, cfg.P, max(1, cfg.LI)), jnp.int32)
    planes = simulator._as_jnp(cfg)
    assert _module(simulator._build_batched(sig).lower(planes, mem, li)) \
        == "jit_morpher_sim"

    rf = simcache.bucket_rf(cfg.RF)
    LI = max(1, cfg.LI)
    multi = simcache.SimSignature(
        II=cfg.II, P=cfg.P, RF=rf, bits=cfg.bits, n_iters=ck.mapped_iters,
        n_cycles=sig.n_cycles, batch=2, LI=LI, multi=True)
    stacked = simulator._stack_planes(
        [simulator._host_planes(cfg, rf)] * 2, [1, 1])
    li2 = jnp.zeros((n_inv, 2, cfg.P, LI), jnp.int32)
    assert _module(simulator._build_batched(multi).lower(
        stacked, mem, li2)) == "jit_morpher_sim_multi"

    spec = ck.spec
    banks = tuple(sorted((f"bank{bid}", w) for bid, w in
                         spec.layout.bank_image_size().items()))
    li_names = tuple(sorted({n.livein for n in spec.dfg.nodes.values()
                             if n.op == Op.LIVEIN}))
    fn = _lowered(spec.dfg, n_iters=spec.mapped_iters,
                  bits=spec.arch.datapath_bits, banks=banks,
                  li_names=li_names)
    stride = sum(w for _, w in banks) + 1
    assert _module(fn.lower(
        jnp.zeros((2 * stride,), jnp.int32),
        jnp.zeros((n_inv, len(li_names)), jnp.int32))) \
        == "jit_morpher_refexec"
