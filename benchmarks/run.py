"""Benchmark harness entry point: one function per paper table/figure.

  table1          -- the paper's Table I (II/MII/util/time/speedup, 6 kernels)
  mapper_sweep    -- II vs MII across cluster variants (the architecture-
                     exploration use-case of the ADL)
  kernel_micro    -- Pallas kernels: us/call in interpret mode (correctness
                     harness timing; real perf comes from the roofline)
  toolchain_cache -- cold vs warm Toolchain.compile over the Table-I kernel
                     set (the content-addressed artifact cache)
  dse_sweep       -- tiny design-space sweep (repro.dse): 4 architecture
                     variants x the ten-kernel library; rows are modeled
                     suite latency per variant (deterministic), so the
                     regression gate tracks mapper/cost-model quality
  dse_search      -- cross-architecture stacked simulation (simulate_multi)
                     vs one launch per (variant, kernel): evaluated points
                     per second, the DSE search evaluator's perf core
  check_static    -- static legality audit (repro.check) throughput over
                     the kernel library, vs one batch-1 dynamic verify

Each benchmark prints ``name,us_per_call,derived`` CSV rows *and* returns
machine-readable rows; ``main`` writes one ``BENCH_<name>.json`` artifact
per benchmark (schema: ``{"bench", "schema", "git_sha", "rows": [{"name",
"us", "derived": {...}}]}``) so the perf trajectory is tracked PR-over-PR.

CLI:  python -m benchmarks.run [--only toolchain_cache,frontend_trace]
                               [--out DIR]
      python -m benchmarks.run --check-regression before.json after.json
                               [--tol 0.15]
The output directory defaults to ``$MORPHER_BENCH_DIR`` or the cwd; the
regression comparator accepts files or directories of BENCH artifacts and
exits nonzero when any benchmark row slows beyond the tolerance.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

BENCH_SCHEMA = 1


def _row(name: str, us: float, **derived) -> Dict:
    return {"name": name, "us": round(us, 1), "derived": derived}


def _simcache_derived(st: Optional[Dict] = None) -> Dict:
    """The executable-cache counters every verify/DSE bench row carries
    (how many XLA builds the run paid vs how many launches it served) —
    informational only, the regression comparator gates ``us``."""
    from repro.core import simcache
    st = st if st is not None else simcache.stats()
    return {"sim_cache_entries": st["entries"], "sim_cache_hits": st["hits"],
            "sim_cache_misses": st["misses"]}


def _print_rows(rows: List[Dict]) -> None:
    for r in rows:
        d = ";".join(f"{k}={v}" for k, v in r["derived"].items())
        print(f"{r['name']},{r['us']:.0f},{d}")


def _git_sha() -> Optional[str]:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            text=True, stderr=subprocess.DEVNULL).strip()
    except (OSError, subprocess.CalledProcessError):
        return None


def bench_table1() -> List[Dict]:
    from . import table1
    return table1.main()


def bench_mapper_sweep() -> List[Dict]:
    from repro.core.adl import cluster_4x4
    from repro.core.kernels_lib import build_gemm
    from repro.core.mapper import MapError, MapperOptions
    from repro.core.toolchain import Toolchain

    # use_cache=False: this benchmark measures real mapper search time
    tc = Toolchain(options=MapperOptions(ii_max=24, seeds=(0, 1, 2, 3),
                                         time_budget_s=60))
    rows = []
    for rf in (4, 8, 16):
        for unroll in (1, 2, 4):
            arch = cluster_4x4(regfile=rf)
            spec = build_gemm(TI=6, TK=8, TJ=6, unroll=unroll, arch=arch)
            t0 = time.time()
            try:
                ck = tc.compile(spec, use_cache=False)
                rows.append(_row(f"mapper_rf{rf}_u{unroll}",
                                 (time.time() - t0) * 1e6, II=ck.II,
                                 MII=ck.mii,
                                 util=round(ck.utilization, 3)))
            except MapError:
                rows.append(_row(f"mapper_rf{rf}_u{unroll}",
                                 (time.time() - t0) * 1e6, unmapped=1))
    _print_rows(rows)
    return rows


def bench_kernel_micro() -> List[Dict]:
    import jax.numpy as jnp
    from repro.kernels.gemm_os.ops import gemm_os
    from repro.kernels.decode_attn.ops import decode_attn

    rng = np.random.default_rng(0)
    rows = []
    a = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    gemm_os(a, b, interpret=True).block_until_ready()
    t0 = time.time()
    for _ in range(3):
        gemm_os(a, b, interpret=True).block_until_ready()
    rows.append(_row("gemm_os_256_interpret", (time.time() - t0) / 3 * 1e6,
                     flops=2 * 256 ** 3))

    q = jnp.asarray(rng.normal(size=(2, 8, 64)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(2, 2, 512, 64)), jnp.float32)
    lens = jnp.asarray([512, 300])
    decode_attn(q, kv, kv, lens, bs=128, interpret=True).block_until_ready()
    t0 = time.time()
    for _ in range(3):
        decode_attn(q, kv, kv, lens, bs=128,
                    interpret=True).block_until_ready()
    rows.append(_row("decode_attn_interpret", (time.time() - t0) / 3 * 1e6,
                     kv=512))
    _print_rows(rows)
    return rows


def bench_toolchain_cache() -> List[Dict]:
    """Cold vs warm compile of the Table-I kernel set through the content-
    addressed artifact cache (small dims, identical DFG structure)."""
    from repro.core.kernels_lib import table1_kernels
    from repro.core.mapper import MapperOptions
    from repro.core.toolchain import Toolchain

    # no per-kernel wall-clock budget: the cold pass measures full mapper
    # cost, and budgets misfire under CPU oversubscription anyway
    opts = MapperOptions(seeds=tuple(range(8)))
    cache = tempfile.mkdtemp(prefix="morpher-cache-bench-")
    try:
        specs = list(table1_kernels(small=True).values())
        t0 = time.time()
        Toolchain(options=opts, cache_dir=cache).compile_many(specs)
        cold = time.time() - t0
        # fresh Toolchain: no in-process memo, artifacts come off disk
        t0 = time.time()
        warm_cks = Toolchain(options=opts, cache_dir=cache).compile_many(
            list(table1_kernels(small=True).values()))
        warm = time.time() - t0
        assert all(ck.from_cache for ck in warm_cks)
        rows = [_row("toolchain_cache", cold * 1e6,
                     warm_us=round(warm * 1e6), kernels=len(specs),
                     speedup=round(cold / warm, 1))]
        _print_rows(rows)
        return rows
    finally:
        shutil.rmtree(cache, ignore_errors=True)


def bench_frontend_trace() -> List[Dict]:
    """Front-end tracing overhead: time to trace each Table-I kernel
    through the ``repro.frontend`` DSL vs a warm-cache Toolchain.compile
    of the same kernel (target: trace < 5% of the warm compile)."""
    from repro.core.adl import cluster_4x4
    from repro.core.kernels_lib import build_conv, build_gemm
    from repro.core.mapper import MapperOptions
    from repro.core.toolchain import Toolchain

    # arch is shared across kernels (as in any real sweep): what's timed
    # below is tracing + spec assembly, not ADL construction
    g = dict(TI=6, TK=8, TJ=6, arch=cluster_4x4())
    c = dict(OH=5, OW=5, K=3, arch=cluster_4x4())
    builders = {
        "GEMM": lambda: build_gemm(**g, unroll=1),
        "GEMM-U": lambda: build_gemm(**g, unroll=4),
        "GEMM-U-C": lambda: build_gemm(**g, unroll=4, coalesced=True),
        "CONV": lambda: build_conv(**c, variant="base"),
        "CONV-U-C-1": lambda: build_conv(**c, variant="uc1"),
        "CONV-U-C-2": lambda: build_conv(**c, variant="uc2"),
    }
    opts = MapperOptions()
    cache = tempfile.mkdtemp(prefix="morpher-frontend-bench-")
    try:
        Toolchain(options=opts, cache_dir=cache).compile_many(
            [b() for b in builders.values()])       # warm the disk cache
        rows = []
        for name, build in builders.items():
            trace_us = float("inf")
            for _ in range(20):                      # best-of: shields noise
                t0 = time.perf_counter()
                spec = build()
                trace_us = min(trace_us, (time.perf_counter() - t0) * 1e6)
            warm_us = float("inf")
            for _ in range(10):
                tc = Toolchain(options=opts, cache_dir=cache)  # no memo
                t0 = time.perf_counter()
                ck = tc.compile(spec)
                warm_us = min(warm_us, (time.perf_counter() - t0) * 1e6)
                assert ck.from_cache
            rows.append(_row(f"trace_{name}", trace_us,
                             warm_compile_us=round(warm_us),
                             nodes=spec.dfg.n_nodes,
                             ratio=round(trace_us / warm_us, 3)))
        _print_rows(rows)
        return rows
    finally:
        shutil.rmtree(cache, ignore_errors=True)


def bench_dse_sweep() -> List[Dict]:
    """Tiny design-space sweep end to end: every ``tiny`` architecture
    variant compiles + verifies the ten-kernel library and is scored by
    the cost model.  Rows carry modeled (deterministic) latency, so the
    regression comparator gates mapping quality rather than wall clock;
    the sweep wall time is printed for the log only."""
    from repro.core.mapper import MapperOptions
    from repro.core.toolchain import Toolchain
    from repro.dse import get_space, run_sweep, sweep_bench_rows

    cache = tempfile.mkdtemp(prefix="morpher-dse-bench-")
    try:
        tc = Toolchain(options=MapperOptions(ii_max=20), cache_dir=cache)
        t0 = time.time()
        results = run_sweep(get_space("tiny"), toolchain=tc)
        print(f"# tiny sweep wall time {time.time() - t0:.1f}s "
              f"({len(results)} variants)")
        rows = sweep_bench_rows(results)
        for r in rows:
            r["derived"].update(_simcache_derived())
        _print_rows(rows)
        return rows
    finally:
        shutil.rmtree(cache, ignore_errors=True)


def bench_dse_search() -> List[Dict]:
    """Cross-architecture batched simulation throughput — the DSE search
    evaluator's perf core.  A cohort of homogeneous 4x4 wide-space
    variants (spanning RF 4/8/16 — the provisioning axis a search
    explores hardest) compiles a kernel subset (off the clock, warm
    cache), then the same verification batches are simulated two ways:

      exhaustive  one XLA launch per (variant, kernel) with exact-shape
                  executables — the per-arch dispatch a sweep pays
      stacked     variants sharing a shape bucket (``stack_signature``:
                  cycle, row and register-file widths bucketed) stack
                  their config planes into one executable
                  (``simulate_multi``) — one launch per group

    Outputs are asserted word-for-word identical, then each path is
    timed *cold* (``simcache.clear()`` + ``jax.clear_caches()`` first,
    best of 2): evaluating a fresh cohort is the search's steady state —
    every generation meets new shape buckets — and executable builds,
    not launches, dominate that cost on the compute-bound CPU backend.
    RF bucketing collapses the per-RF executable classes (builds_* in
    the row), which is where the >= 2x pinned by the committed
    before/after baselines comes from.  Warm launches are reported too
    (warm_*): stacked pays row/RF padding there, the price of the merged
    executables — the cold win is the net.  Note the cache clears force
    benches run after this one in the same process to retrace."""
    import jax

    from repro.core import simcache
    from repro.core.mapper import MapperOptions
    from repro.core.simulator import simulate_multi, stack_signature
    from repro.core.toolchain import Toolchain, _batch_oracle
    from repro.dse import get_space, kernel_suite

    points = [p for p in get_space("wide")
              if p.rows == 4 and p.cols == 4 and p.het == "none"][:12]
    kernels = ("GEMM", "CONV", "dwconv", "requant-int8")
    seeds = list(range(4))
    cache = tempfile.mkdtemp(prefix="morpher-dse-search-bench-")
    try:
        tc = Toolchain(options=MapperOptions(ii_max=20), cache_dir=cache)
        units = []                                # (ck, init_banks_batch)
        for p in points:
            suite = kernel_suite(p.build())
            cks = tc.compile_many([suite[k] for k in kernels],
                                  allow_unmapped=True)
            units += [(ck, _batch_oracle(ck, seeds, check_dfg=False)[0])
                      for ck in cks if ck is not None]

        def exhaustive():
            return [ck.run_batch(init) for ck, init in units]

        def stacked():
            groups: Dict[tuple, List[int]] = {}
            for i, (ck, _init) in enumerate(units):
                sig = stack_signature(ck.cfg, ck.mapped_iters,
                                      len(ck.invocations))
                groups.setdefault(sig, []).append(i)
            outs: List = [None] * len(units)
            for sig in sorted(groups):
                idxs = groups[sig]
                finals = simulate_multi(
                    [(units[i][0].cfg, units[i][1], units[i][0].invocations)
                     for i in idxs],
                    n_iters=units[idxs[0]][0].mapped_iters)
                for i, f in zip(idxs, finals):
                    outs[i] = f
            return outs

        a, b = exhaustive(), stacked()       # warm traces + bit-exactness
        for fa, fb in zip(a, b):             # per unit: [seed][bank] arrays
            for da, db in zip(fa, fb):
                assert set(da) == set(db)
                for k in da:
                    np.testing.assert_array_equal(np.asarray(da[k]),
                                                  np.asarray(db[k]))
        warm_exh = warm_flat = float("inf")  # best of 2: shields noise
        for _ in range(2):
            t0 = time.time()
            exhaustive()
            warm_exh = min(warm_exh, time.time() - t0)
            t0 = time.time()
            stacked()
            warm_flat = min(warm_flat, time.time() - t0)

        def cold(fn):
            simcache.clear()
            jax.clear_caches()
            t0 = time.time()
            fn()
            return time.time() - t0

        exh = flat = float("inf")
        builds_exh = builds_flat = 0
        for _ in range(2):
            exh = min(exh, cold(exhaustive))
            builds_exh = simcache.stats()["misses"]
            flat = min(flat, cold(stacked))
            builds_flat = simcache.stats()["misses"]

        rows = [_row("dse_search_eval", flat * 1e6,
                     points=len(points), kernels=len(kernels),
                     seeds=len(seeds), units=len(units),
                     builds_exhaustive=builds_exh,
                     builds_stacked=builds_flat,
                     evals_per_s=round(len(points) / flat, 1),
                     exhaustive_us=round(exh * 1e6),
                     exhaustive_evals_per_s=round(len(points) / exh, 1),
                     speedup=round(exh / flat, 2),
                     warm_us=round(warm_flat * 1e6),
                     warm_exhaustive_us=round(warm_exh * 1e6),
                     **_simcache_derived())]
        _print_rows(rows)
        return rows
    finally:
        shutil.rmtree(cache, ignore_errors=True)


def bench_isa_export() -> List[Dict]:
    """Instruction-stream backend throughput over the ten-kernel library:
    export (encode all three artifacts) and the standalone-interpreter
    cross-validation against ``simulate()`` (one seed).  The export row's
    wall time is what an ``--emit-streams`` deploy pays per kernel; the
    xval row is the cost of the second oracle inside a verify fleet."""
    from repro.core.kernels_lib import table1_kernels
    from repro.core.toolchain import Toolchain
    from repro.frontend.library import dsl_kernels
    from repro.isa.encode import encode_kernel
    from repro.isa.xval import cross_validate, stream_for

    specs = {**table1_kernels(small=True), **dsl_kernels()}
    cks = Toolchain(cache_dir="").compile_many(list(specs.values()))
    insns = sum(ck.cfg.II * ck.cfg.P for ck in cks)

    for ck in cks:                       # warm: imports, one sim trace each
        encode_kernel(ck)
        cross_validate(ck, seeds=(0,))

    exp = float("inf")                   # best of 3: shields against noise
    for _ in range(3):
        t0 = time.time()
        arts = [encode_kernel(ck) for ck in cks]
        exp = min(exp, time.time() - t0)
    streams = [stream_for(ck) for ck in cks]
    xval = float("inf")
    for _ in range(3):
        t0 = time.time()
        for ck, st in zip(cks, streams):
            cross_validate(ck, seeds=(0,), stream=st)
        xval = min(xval, time.time() - t0)

    rows = [_row("isa_export", exp * 1e6, kernels=len(cks), insns=insns,
                 bytes=sum(len(t) for a in arts for t in a.values()),
                 insns_per_s=round(insns / exp)),
            _row("isa_xval", xval * 1e6, kernels=len(cks), seeds=1,
                 kernels_per_s=round(len(cks) / xval, 1))]
    _print_rows(rows)
    return rows


def bench_check_static() -> List[Dict]:
    """Static legality audit throughput (repro.check) over the Table-I
    (small dims) + DSL kernel set: all three layers (mapping, config,
    re-derived instruction stream) per kernel, best of 3.  The derived
    ``verify_us`` column is one batch-1 dynamic verify over the same set
    — the cost the MORPHER_CHECK=1 pre-screen lets a fleet skip for
    artifacts that are corrupt on paper."""
    from repro.check import check_kernel, errors
    from repro.core.kernels_lib import table1_kernels
    from repro.core.toolchain import Toolchain
    from repro.frontend.library import dsl_kernels

    specs = {**table1_kernels(small=True), **dsl_kernels()}
    cks = Toolchain(cache_dir="").compile_many(list(specs.values()))
    for ck in cks:                       # warm: imports + one XLA trace each
        assert not errors(check_kernel(ck))
        ck.verify(seed=0)

    chk = float("inf")                   # best of 3: shields against noise
    for _ in range(3):
        t0 = time.time()
        n_diags = sum(len(check_kernel(ck)) for ck in cks)
        chk = min(chk, time.time() - t0)
    ver = float("inf")
    for _ in range(3):
        t0 = time.time()
        for ck in cks:
            ck.verify(seed=0)
        ver = min(ver, time.time() - t0)

    rows = [_row("check_static", chk * 1e6, kernels=len(cks),
                 diagnostics=n_diags,
                 kernels_per_s=round(len(cks) / chk, 1),
                 verify_us=round(ver * 1e6),
                 verify_ratio=round(ver / chk, 1))]
    _print_rows(rows)
    return rows


def bench_serve_decode() -> List[Dict]:
    """End-to-end CGRA-backed serving on shrunken configs: build a
    ServePlan (feasible tiles, compile_many, one site spot-checked
    bit-exactly against the cycle-accurate simulator), then run a seeded
    Poisson traffic episode through the engine on plan-derived latency.
    Rows carry the *modeled* episode duration and throughput —
    byte-deterministic given the seed, so the regression comparator gates
    plan/cost-model quality, not host wall clock."""
    import jax
    from repro.configs.registry import serve_smoke_config
    from repro.core.toolchain import Toolchain
    from repro.models.zoo import build_model
    from repro.serve.engine import Engine
    from repro.serve.plan import CGRAExecutionModel, build_serve_plan
    from repro.serve.traffic import (TrafficConfig, report_bench_rows,
                                     run_traffic)

    cache = tempfile.mkdtemp(prefix="morpher-serve-bench-")
    rows: List[Dict] = []
    try:
        tc = Toolchain(cache_dir=cache)
        for arch_id in ("llama3.2-1b", "rwkv6-1.6b"):
            cfg = serve_smoke_config(arch_id)
            plan = build_serve_plan(cfg, toolchain=tc)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            eng = Engine(model, params, batch=4, max_len=48,
                         exec_model=CGRAExecutionModel(plan))
            report = run_traffic(
                eng, TrafficConfig(seed=0, n_requests=12,
                                   arrival_rate=100.0), cfg.vocab)
            rows += report_bench_rows(report,
                                      name=f"serve_decode_{arch_id}",
                                      sites=len(plan.sites),
                                      tiles=len(plan.kernels))
        _print_rows(rows)
        return rows
    finally:
        shutil.rmtree(cache, ignore_errors=True)


BENCHES = {
    "table1": ("Table I (paper reproduction)", bench_table1),
    "frontend_trace": ("frontend DSL tracing overhead (vs warm compile)",
                       bench_frontend_trace),
    "mapper_sweep": ("mapper sweep (ADL design-space exploration)",
                     bench_mapper_sweep),
    "kernel_micro": ("Pallas kernel micro (interpret mode)",
                     bench_kernel_micro),
    "toolchain_cache": ("toolchain artifact cache (cold vs warm)",
                        bench_toolchain_cache),
    "dse_sweep": ("tiny design-space sweep (repro.dse, modeled latency)",
                  bench_dse_sweep),
    "dse_search": ("cross-architecture stacked simulation throughput "
                   "(evaluated points per second)", bench_dse_search),
    "serve_decode": ("CGRA-backed serving traffic episode (modeled)",
                     bench_serve_decode),
    "isa_export": ("instruction-stream export + interpreter xval",
                   bench_isa_export),
    "check_static": ("static legality audit throughput (repro.check)",
                     bench_check_static),
}


def check_regression(before: str, after: str, tol: float = 0.15) -> int:
    """Compare two BENCH_<name>.json artifacts (or two directories of
    them): any row whose ``us`` grew by more than ``tol`` (relative) is a
    throughput regression.  Returns a nonzero exit status if any row
    regressed; rows present on only one side are reported but never fail.
    """
    def load_rows(path: str) -> Dict[str, Dict]:
        files = (sorted(os.path.join(path, f) for f in os.listdir(path)
                        if f.startswith("BENCH_") and f.endswith(".json"))
                 if os.path.isdir(path) else [path])
        rows: Dict[str, Dict] = {}
        for fn in files:
            with open(fn, "r", encoding="utf-8") as f:
                d = json.load(f)
                for r in d["rows"]:
                    # key by (bench, row): same-named rows from different
                    # benchmarks must not shadow each other
                    rows[f"{d['bench']}/{r['name']}"] = r
        return rows

    b_rows, a_rows = load_rows(before), load_rows(after)
    failed = []
    for name in sorted(set(b_rows) | set(a_rows)):
        if name not in b_rows:
            print(f"NEW       {name}: {a_rows[name]['us']}us")
            continue
        if name not in a_rows:
            print(f"REMOVED   {name} (was {b_rows[name]['us']}us)")
            continue
        b_us, a_us = b_rows[name]["us"], a_rows[name]["us"]
        if b_us is None or a_us is None:
            # informational rows (e.g. an unmapped table1 kernel) carry
            # no duration; report, never gate
            print(f"{'n/a':9s} {name}: {b_us}us -> {a_us}us")
            continue
        rel = (a_us - b_us) / b_us if b_us else 0.0
        verdict = "REGRESSED" if rel > tol else "ok"
        print(f"{verdict:9s} {name}: {b_us:.0f}us -> {a_us:.0f}us "
              f"({rel:+.1%}, tol {tol:.0%})")
        if rel > tol:
            failed.append(name)
    if failed:
        print(f"# {len(failed)} row(s) regressed beyond {tol:.0%}: "
              f"{', '.join(failed)}")
        return 1
    print("# no regressions")
    return 0


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names "
                         f"(default: all of {', '.join(BENCHES)})")
    ap.add_argument("--out", default=None,
                    help="directory for BENCH_<name>.json artifacts "
                         "(default: $MORPHER_BENCH_DIR or cwd)")
    ap.add_argument("--check-regression", nargs=2,
                    metavar=("BEFORE", "AFTER"),
                    help="compare two BENCH json files (or directories of "
                         "them) instead of running benchmarks; exits "
                         "nonzero if any row slowed beyond --tol")
    ap.add_argument("--tol", type=float, default=0.15,
                    help="relative slowdown tolerated by "
                         "--check-regression (default 0.15)")
    args = ap.parse_args(argv)
    if args.check_regression:
        raise SystemExit(check_regression(*args.check_regression,
                                          tol=args.tol))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = list(BENCHES) if not args.only else [
        n.strip() for n in args.only.split(",") if n.strip()]
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown benchmark(s): {', '.join(unknown)}")
    out_dir = args.out or os.environ.get("MORPHER_BENCH_DIR") or "."
    os.makedirs(out_dir, exist_ok=True)
    sha = _git_sha()
    for name in names:
        title, fn = BENCHES[name]
        print(f"# === {title} ===")
        rows = fn()
        path = os.path.join(out_dir, f"BENCH_{name}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"bench": name, "schema": BENCH_SCHEMA,
                       "git_sha": sha, "rows": rows}, f, indent=1)
            f.write("\n")
        print(f"# wrote {path}")


if __name__ == "__main__":
    main()
