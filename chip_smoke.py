"""Smoke run of the Morpher flow's device paths on one TPU, at real sizes.

    python chip_smoke.py [--seed N]

Everything runs in this one process, which holds the chip.  The compile
workers the toolchain fans out to are pure Python and never import JAX
(checked in the ``flow`` phase).  Phases, in order:

  flow     the six Table-I kernels at the paper's dimensions on
           ``cluster_4x4``, compiled by ``Toolchain.compile_many`` on the
           worker pool and verified over 8 seeds by ``verify_batch``: the
           cycle-accurate simulator and the ``refexec`` DFG oracle run on
           the chip, both held word for word to the numpy golden models.
           The ISA interpreter is cross-validated against ``simulate()``
           on the small GEMM.
  stacked  the DSE cohort of ``bench_dse_search`` (12 homogeneous 4x4
           wide-space points x {GEMM, CONV, dwconv, requant-int8} x 4
           seeds) through ``verify_stacked``, with at least one
           multi-architecture (``multi=True``) launch.
  serve    rwkv6-1.6b at its published widths, weights drawn from
           ``--seed``: a ``ServePlan`` is built and spot-checked, then an
           ``Engine(batch=4, max_len=128)`` answers 4 requests, and the
           greedy tokens of one are checked against ``model.train_logits``.

Without a TPU it exits nonzero and prints no result.  The last line of its
output is one JSON object: ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``.  The mapping cache lives in
``<checkout>/.morpher_cache`` and JAX's compile cache in
``<checkout>/.jax_cache`` unless ``$JAX_COMPILATION_CACHE_DIR`` is set.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
MAPPING_CACHE = os.path.join(ROOT, ".morpher_cache")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# serve phase traffic: requests, new tokens each, prompt length range
REQUESTS, MAX_NEW, PROMPT_LEN = 4, 16, (32, 64)


def worker_jax_modules(_):
    """Runs in a pool worker: the JAX modules that worker has imported."""
    return sorted(m for m in sys.modules if m == "jax" or m.startswith("jax."))


class CompileLog:
    """XLA builds and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.builds, self.hits, self.written = [], 0, 0
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
            self._hit = True
        elif event == "/jax/compilation_cache/cache_misses":
            self.written += 1

    def _on_duration(self, event, duration, **_kw):
        if event == BACKEND_COMPILE:
            if not self._hit:
                self.builds.append(duration)
            self._hit = False

    def snapshot(self):
        return len(self.builds), self.hits, self.written

    def since(self, snap) -> str:
        n, hits, written = snap
        new = self.builds[n:]
        return (f"xla_builds={len(new)} build_s={sum(new):.3f} "
                f"builds_under_1s={sum(d < 1.0 for d in new)} "
                f"cache_hits={self.hits - hits} "
                f"cache_writes={self.written - written}")


def phase_flow(log, seeds, specs, small_gemm):
    from repro.core import pool, simcache
    from repro.core.toolchain import Toolchain

    tc = Toolchain(cache_dir=MAPPING_CACHE)
    t0 = time.time()
    cks = tc.compile_many(list(specs.values()))
    cold = sum(not ck.from_cache for ck in cks)
    print(f"flow: compile_many {len(cks)} kernels in "
          f"{time.time() - t0:.3f} s ({cold} mapped, "
          f"{len(cks) - cold} from the mapping cache)")
    if cold > 1:
        assert tc.last_fleet_report is not None, \
            "compile_many ran the sequential fallback, not the worker pool"
    probe = pool.process_map(worker_jax_modules, range(4))
    assert probe is not None, "no worker pool in this process"
    assert not any(probe), f"pool workers imported JAX: {probe}"
    print(f"flow: {len(probe)} pool tasks ran without importing JAX")

    for name, ck in zip(specs, cks):
        builds = simcache.stats()["misses"]
        snap = log.snapshot()
        t0 = time.time()
        ck.verify_batch(seeds, check_dfg=True)
        dt = time.time() - t0
        # a simcache miss is an XLA build or a persistent-cache load
        src = ("built" if simcache.stats()["misses"] > builds
               else "simcache")
        cycles = ck.cfg.n_cycles(ck.mapped_iters) * len(ck.invocations)
        print(f"flow {name}: II={ck.II} cycles={cycles} seeds={len(seeds)} "
              f"host_s={dt:.3f} executable={src} {log.since(snap)}")

    t0 = time.time()
    xck = tc.cross_validate(small_gemm, seeds=seeds[:1])
    print(f"flow xval {xck.name}: II={xck.II} interpreter == simulate() "
          f"in {time.time() - t0:.3f} s")


def phase_stacked(seeds, points, kernels):
    from repro.core import simcache
    from repro.core.mapper import MapperOptions
    from repro.core.toolchain import Toolchain, verify_stacked
    from repro.dse import kernel_suite

    tc = Toolchain(options=MapperOptions(ii_max=20), cache_dir=MAPPING_CACHE)
    specs = [kernel_suite(p.build())[k] for p in points for k in kernels]
    t0 = time.time()
    cks = [ck for ck in tc.compile_many(specs, allow_unmapped=True)
           if ck is not None]
    print(f"stacked: {len(cks)}/{len(specs)} (point, kernel) pairs mapped "
          f"in {time.time() - t0:.3f} s")
    before = set(simcache.signatures())
    t0 = time.time()
    verify_stacked(cks, seeds)
    dt = time.time() - t0
    multi = [s for s in simcache.signatures() if s.multi]
    assert multi, "no group took the stacked multi=True executable"
    new = [s for s in simcache.signatures() if s not in before]
    print(f"stacked: {len(cks)} kernels x {len(seeds)} seeds bit-exact in "
          f"{dt:.3f} s; multi=True signatures={len(multi)} "
          f"new executables={len(new)}")


def phase_serve(seed, cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.toolchain import Toolchain
    from repro.models.zoo import build_model
    from repro.serve.engine import Engine, Request
    from repro.serve.plan import CGRAExecutionModel, build_serve_plan

    t0 = time.time()
    plan = build_serve_plan(cfg, toolchain=Toolchain(cache_dir=MAPPING_CACHE),
                            spot_check=False)
    checked = plan.spot_check(seeds=(seed,))
    print(f"serve: plan {len(plan.sites)} sites / {len(plan.kernels)} tiles, "
          f"spot-checked {checked} in {time.time() - t0:.3f} s")

    model = build_model(cfg)
    t0 = time.time()
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed)))
    print(f"serve: {cfg.name} params from PRNGKey({seed}) in "
          f"{time.time() - t0:.3f} s")

    eng = Engine(model, params, batch=4, max_len=128,
                 exec_model=CGRAExecutionModel(plan))
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(
                0, cfg.vocab, size=int(rng.integers(PROMPT_LEN[0],
                                                    PROMPT_LEN[1] + 1))),
                    max_new=MAX_NEW) for i in range(REQUESTS)]
    first = {}
    t0 = time.time()
    for r in reqs:
        assert eng.admit(r)
        slot = next(i for i, s in enumerate(eng.slots) if s is r)
        first[r.rid] = int(eng.last_tok[slot])
    t_admit = time.time() - t0
    steps = 0
    t0 = time.time()
    while any(not r.done for r in reqs):
        eng.step()
        steps += 1
    t_decode = time.time() - t0
    for r in reqs:
        toks = [first[r.rid]] + r.out
        assert len(r.out) == MAX_NEW, (r.rid, len(r.out))
        assert all(0 <= t < cfg.vocab for t in toks), (r.rid, toks)
    print(f"serve: {REQUESTS} requests (prompts "
          f"{[len(r.prompt) for r in reqs]}) admitted in {t_admit:.3f} s, "
          f"{steps} decode steps in {t_decode:.3f} s, modeled CGRA clock "
          f"{eng.clock_s * 1e3:.3f} ms")

    # the engine's greedy tokens against the full forward pass over the
    # same sequence: logit of the engine's token within bf16 tolerance of
    # the row maximum, which is exact argmax wherever the margin exceeds it
    r = reqs[0]
    toks = [first[r.rid]] + r.out
    seq = np.concatenate([r.prompt, toks[:-1]]).astype(np.int32)
    logits, _ = jax.jit(model.train_logits)(params, jnp.asarray(seq[None]))
    f = np.asarray(logits[0, len(r.prompt) - 1:], np.float32)
    top = f.max(axis=1)
    got = f[np.arange(len(toks)), toks]
    tol = 3e-2 + 3e-2 * np.abs(top)
    assert np.all(np.isfinite(f))
    gap = top - got
    assert np.all(gap <= tol), (gap.tolist(), tol.tolist())
    exact = int(np.sum(np.asarray(toks) == f.argmax(axis=1)))
    print(f"serve: request {r.rid}: {len(toks)} engine tokens consistent "
          f"with train_logits ({exact} exact argmax, max gap "
          f"{gap.max():.5f})")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    print(f"device: {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}")
    log = CompileLog()

    from repro.configs.registry import get_config
    from repro.core.adl import cluster_4x4
    from repro.core.kernels_lib import table1_kernels
    from repro.dse import get_space

    seeds = [args.seed + i for i in range(8)]
    phases = [
        ("flow", lambda: phase_flow(
            log, seeds, table1_kernels(arch=cluster_4x4()),
            table1_kernels(small=True)["GEMM"])),
        ("stacked", lambda: phase_stacked(
            seeds[:4],
            [p for p in get_space("wide")
             if p.rows == 4 and p.cols == 4 and p.het == "none"][:12],
            ("GEMM", "CONV", "dwconv", "requant-int8"))),
        ("serve", lambda: phase_serve(args.seed,
                                      get_config("rwkv6-1.6b"))),
    ]
    t_all = time.time()
    for name, run in phases:
        snap = log.snapshot()
        t0 = time.time()
        run()
        print(f"phase {name}: {time.time() - t0:.3f} s; {log.since(snap)}")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"all phases: {time.time() - t_all:.3f} s; "
          f"peak_bytes_in_use={peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
