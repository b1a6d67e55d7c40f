"""One cell of ``BENCHMARK.json``: from its files to its result line.

A cell is a workload file ``bench/workloads/<cell>.json`` (the traffic: entry,
seeds per call, mapping cold or cached), the configuration file it names
``bench/configs/<config>.json`` (fabrics, kernels, layouts, datapath width)
and the per-layer metrics that ``BENCHMARK.json`` lists for it, each read by
``bench/metrics/<metric>.py``.  Nothing here names a cell, a configuration
or a metric: a new one is new files.

A run:

1. set-up: imports, the chip, the fabrics and kernel specs, the mappings
   (from ``<checkout>/.morpher_cache`` where the workload maps them once),
   and one whole unit of work on seeds of its own, which builds or loads
   every executable the window will run;
2. the window: whole units, one after another, until ``--seconds`` have
   passed; the rate is all the work of those units over all their time;
3. after it: the peak device memory, then the plain reference
   (``bench/reference.py``) over every (pair, seed) the window verified,
   compared word for word with the images the entry's own simulator
   launches and its own DFG oracle produced.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
MAPPING_CACHE = os.path.join(ROOT, ".morpher_cache")
# the counts a workload's end-to-end rate can be taken over
COUNTS = ("simulated_cycles", "design_points")
# the numbers compared with the plain reference, each with the limit 0
CHECKS = ("image_words_wrong", "input_words_wrong", "pairs_unverified",
          "oracle_words_wrong", "oracle_rows_unverified", "entry_errors")
SEED_SPACE = 1 << 62


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def bench_file(kind: str, name: str, ext: str = ".json") -> str:
    path = os.path.join(BENCH, kind, name + ext)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    return path


@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    per_layer: List[dict]

    @property
    def metric(self) -> dict:
        return self.workload["metric"]


def load_cell(name: str) -> Cell:
    """The cell's workload, its configuration, and the per-layer metrics
    ``BENCHMARK.json`` lists for it (by its ``workloads``, or else by the
    end-to-end metric it moves)."""
    workload = load_json(bench_file("workloads", name))
    config = load_json(bench_file("configs", workload["config"]))
    if workload["metric"]["counts"] not in COUNTS:
        raise ValueError(f"{name}: counts must be one of {COUNTS}")
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    moves = {workload["metric"]["name"], "setup_s"}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moves)]
    return Cell(name, workload, config, per_layer)


def resolve(path: str):
    """``module:attribute`` -> the program object it names."""
    mod, _, attr = path.partition(":")
    return getattr(importlib.import_module(mod), attr)


def build_fabric(entry: dict):
    obj = resolve(entry["call"])(**entry["kwargs"])
    return getattr(obj, entry["method"])() if "method" in entry else obj


@dataclass
class Pair:
    """One (fabric, kernel) of a configuration: the unit a seed verifies."""
    fabric: dict
    kernel: dict
    spec: object

    @property
    def name(self) -> str:
        return f"{self.fabric['name']}/{self.kernel['name']}"


@dataclass
class Call:
    """One call into the timed entry: which pairs, which seeds."""
    index: int
    entries: list = field(default_factory=list)   # (pair, ck, seeds)
    error: str = ""


@dataclass
class RunData:
    """What a per-layer metric reader gets."""
    workload: str
    device_kind: str
    window_s: float
    records: list                 # capture.SimRecord of the window
    trace: Optional[object]       # tracefile.Summary, or None
    spans: Dict[str, float]       # host seconds per benchmark span


class Spans:
    """The benchmark's own host spans: seconds per name, and a
    ``TraceAnnotation`` on the profiler's clock while tracing."""

    def __init__(self, tracing: bool):
        self.tracing = tracing
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str, label: str = ""):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(
                f"bench.{name}" + (f":{label}" if label else ""))
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.seconds[name] = (self.seconds.get(name, 0.0)
                                      + time.perf_counter() - t0)


class Runner:
    """Drives one cell through the program's own entry points."""

    def __init__(self, cell: Cell, seed: int):
        import numpy as np
        from repro.core import simulator, verify
        from repro.core.mapper import MapperOptions

        from bench.capture import Recorder

        self.cell = cell
        self.wl = cell.workload
        self.bits = cell.config["datapath_bits"]
        self.rng = np.random.default_rng(seed % (1 << 64))
        self.options = MapperOptions(
            ii_max=cell.config["mapper"]["ii_max"],
            seeds=tuple(cell.config["mapper"]["seeds"]))
        fabrics = [(f, build_fabric(f)) for f in cell.config["fabrics"]]
        self.pairs = [Pair(f, k, resolve(k["call"])(**k["kwargs"], arch=arch))
                      for f, arch in fabrics for k in cell.config["kernels"]]
        self.recorder = Recorder(simulator, verify)
        self.calls: List[Call] = []
        self.spans = Spans(False)
        self.cks = None
        self.units = 0
        if self.wl["mapping"] == "cached":
            self.cks = self.compile(MAPPING_CACHE)
            missing = [p.name for p, ck in zip(self.pairs, self.cks)
                       if ck is None]
            if missing:
                raise RuntimeError(f"pairs that do not map: {missing}")
        elif self.wl["mapping"] != "cold":
            raise ValueError(f"mapping must be cached or cold, not "
                             f"{self.wl['mapping']!r}")

    def compile(self, cache_dir: str):
        from repro.core.toolchain import Toolchain
        tc = Toolchain(options=self.options, cache_dir=cache_dir)
        return tc.compile_many([p.spec for p in self.pairs],
                               allow_unmapped=True)

    def seeds(self) -> List[int]:
        return [int(s) for s in self.rng.integers(
            0, SEED_SPACE, size=self.wl["seeds_per_call"])]

    def _call(self, pairs_cks, fn, seeds):
        call = Call(len(self.calls))
        self.calls.append(call)
        self.recorder.call = call.index
        call.entries = [(p, ck, seeds) for p, ck in pairs_cks]
        try:
            fn()
        except AssertionError as e:
            # the program's own comparison refused an image: a failure of
            # the timed entry, which the reference will see as well
            call.error = str(e)[:500]

    def unit(self) -> None:
        """One unit of work: (map, then) verify every pair once, with the
        DFG oracle on."""
        from repro.core.toolchain import verify_stacked
        cks = self.cks
        if cks is None:
            with self.spans("compile_many"):
                cks = self.compile("")
        live = [(p, ck) for p, ck in zip(self.pairs, cks) if ck is not None]
        if self.wl["entry"] == "verify_batch":
            for p, ck in live:
                seeds = self.seeds()
                with self.spans("verify_batch", p.kernel["name"]):
                    self._call([(p, ck)], lambda: ck.verify_batch(
                        seeds, check_dfg=True), seeds)
        elif self.wl["entry"] == "verify_stacked":
            seeds = self.seeds()
            with self.spans("verify_stacked"):
                self._call(live, lambda: verify_stacked(
                    [ck for _, ck in live], seeds, check_dfg=True),
                    seeds)
        else:
            raise ValueError(f"unknown entry {self.wl['entry']!r}")
        self.units += 1
        # pairs that did not map in a cold unit are never verified: the
        # comparison counts them as unverified
        for p, ck in zip(self.pairs, cks):
            if ck is None:
                self.calls[-1].entries.append((p, None, self.seeds()))

    def window(self, seconds: float) -> float:
        """Whole units until ``seconds`` have passed; returns the window."""
        self.calls.clear()
        self.recorder.clear()
        self.units = 0
        t0 = time.perf_counter()
        with self.spans("window"):
            while True:
                self.unit()
                if time.perf_counter() - t0 >= seconds:
                    break
        return time.perf_counter() - t0

    def count(self) -> float:
        kind = self.wl["metric"]["counts"]
        if kind == "simulated_cycles":
            return float(sum(r.cycles * r.rows
                             for r in self.recorder.records))
        return float(len(self.cell.config["fabrics"]) * self.units)

    # ------------------------------------------------------------ checking
    def compare(self) -> dict:
        """The plain reference against every image the window verified:
        the simulator's input and final images, and the DFG oracle's final
        images.  A row that either never produced is unverified."""
        from bench.reference import images, words_wrong
        out = {"attempted": 0, "failed": 0}
        out.update(dict.fromkeys(CHECKS, 0))
        rec_of = self.recorder
        for call in self.calls:
            out["entry_errors"] += bool(call.error)
            for pair, ck, seeds in call.entries:
                rec = orc = None
                if ck is not None:
                    rec = rec_of.find(call.index, ck.cfg)
                    orc = rec_of.find_oracle(call.index,
                                             (pair.spec.dfg, ck.dfg))
                for row, seed in enumerate(seeds):
                    out["attempted"] += 1
                    init, final = images(pair.kernel, pair.fabric, seed,
                                         self.bits)
                    bad = bool(call.error)
                    if rec is None or row >= len(rec.final):
                        out["pairs_unverified"] += 1
                        bad = True
                    else:
                        iw = words_wrong(rec.init[row], init)
                        fw = words_wrong(rec.final[row], final)
                        out["input_words_wrong"] += iw
                        out["image_words_wrong"] += fw
                        bad |= bool(iw or fw)
                    if orc is None or row >= len(orc.final):
                        out["oracle_rows_unverified"] += 1
                        bad = True
                    else:
                        ow = words_wrong(orc.final[row], final)
                        out["oracle_words_wrong"] += ow
                        bad |= bool(ow)
                    out["failed"] += bad
        return out


def check_lines(checks: dict) -> Dict[str, dict]:
    """The numbers compared, each with its limit (exact comparisons: 0)."""
    return {k: {"value": checks[k], "limit": 0} for k in CHECKS}


def load_reader(name: str):
    path = bench_file("metrics", name, ".py")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def stop_workers() -> None:
    """Stop the compile pool's workers and the forkserver, and wait."""
    from repro.core import pool
    ex = getattr(pool, "_shared", None)
    if ex is not None:
        ex.shutdown(wait=True)
        pool._shared = None
    try:
        from multiprocessing import forkserver
        forkserver._forkserver._stop()
    except (ImportError, AttributeError, OSError):
        pass


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             prepare=None) -> int:
    """Run one cell; print its result line; return the exit code.

    ``require_chip=False`` and ``prepare`` (called with the ``Runner``
    before the window) are for tests and the control, never for the
    benchmark's own runs.
    """
    cell = load_cell(name)
    if trace:
        # without per-HLO trace events the TPU's trace keeps one event per
        # XLA module run; with them a scan step of the simulator is some 60
        # events, and a traced window of the Table-I pass would not fit on
        # disk.  Added before JAX starts the backend, to what the machine's
        # variable holds.
        os.environ["LIBTPU_INIT_ARGS"] = (
            os.environ.get("LIBTPU_INIT_ARGS", "")
            + " --xla_enable_hlo_trace=false").strip()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    chips = cell.workload.get("chips", 1)
    if require_chip and (dev.platform == "cpu" or len(devices) < chips):
        print(f"bench: {name} needs {chips} accelerator chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 3
    from repro.core import simcache

    from bench import work
    from bench.compilelog import CompileLog
    if require_chip:
        work.peaks(dev.device_kind)           # an unknown chip is an error
    log = CompileLog()
    try:
        runner = Runner(cell, seed)
        if prepare is not None:
            prepare(runner)
        runner.unit()                          # warm-up, on its own seeds
        gc.collect()
        setup_s = time.perf_counter() - t_start
        print(f"bench: {name} set-up {setup_s:.3f} s "
              f"(compile cache {cache_dir}; {log.since((0, 0, 0))})",
              file=sys.stderr)

        tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        runner.spans = runner.recorder.span = Spans(trace)
        runner.recorder.install()
        snap, misses = log.snapshot(), simcache.stats()["misses"]
        try:
            if trace:
                jax.profiler.start_trace(tmp, profiler_options=_profile_options())
            try:
                window_s = runner.window(seconds)
            finally:
                if trace:
                    jax.profiler.stop_trace()
        finally:
            runner.recorder.uninstall()
        inside = log.since(snap)
        inside["executables_built"] = simcache.stats()["misses"] - misses
        print(f"bench: window {window_s:.3f} s, {runner.units} unit(s), "
              f"{len(runner.calls)} call(s); inside it "
              + " ".join(f"{k}={v}" for k, v in inside.items()),
              file=sys.stderr)

        stats = dev.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        count = runner.count()
        summary = None
        if trace:
            from bench import tracefile
            summary = tracefile.summarize(tracefile.load(tmp))
            shutil.rmtree(tmp, ignore_errors=True)
        data = RunData(workload=name, device_kind=dev.device_kind,
                       window_s=window_s,
                       records=list(runner.recorder.records),
                       trace=summary, spans=dict(runner.spans.seconds))
        # the program's state goes before the reference runs
        runner.cks = None
        simcache.clear()
        jax.clear_caches()
        checks = runner.compare()
    finally:
        stop_workers()

    limits = check_lines(checks)
    correct = (checks["attempted"] > 0
               and all(v["value"] <= v["limit"] for v in limits.values()))
    metrics = {}
    if not trace:
        m = cell.metric
        metrics[m["name"]] = {"value": count / window_s, "unit": m["unit"]}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        for entry in cell.per_layer:
            value = load_reader(entry["name"])(data)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": checks["attempted"],
              "failed": checks["failed"], "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = limits
    for k, v in limits.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
