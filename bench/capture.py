"""What the timed entry's simulator launches and DFG oracle produced.

``Recorder`` wraps ``simulate_batch`` and ``simulate_multi`` of
``repro.core.simulator`` and ``reference_banks_batch`` of
``repro.core.verify`` while it is installed.  ``verify_batch`` and
``verify_stacked`` look those names up in their modules when they run, so
the images the recorder keeps are the very ones the entry compared.  A call
that ``simulate_multi`` hands on to ``simulate_batch`` (a group of one) is
recorded once.

Each wrapped call runs inside the span ``sim`` or ``oracle`` of the
benchmark's own host spans (``span``), which are ``bench.sim`` and
``bench.oracle`` in a traced run.  Both calls return host arrays, so the
device work each launches ends inside its span: that is how the trace's
device time is split between the simulator and the oracle, whatever XLA
names their programs.

Tests and the control put a ``replace`` (the whole launch) or a ``fault``
(applied to what a launch gave back) on the recorder; the benchmark's own
runs set neither.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Callable, List, Optional


@dataclass
class SimRecord:
    """One configuration's rows in one launch."""
    call: int              # index of the entry call that made the launch
    cfg: object            # the SimConfig simulated
    init: list             # [row] -> {bank: words}, as handed to the launch
    final: list            # [row] -> {bank: words}, as the launch gave back
    cycles: int            # real simulated cycles per row (all invocations)
    P: int
    RF: int
    LI: int
    bits: int

    @property
    def rows(self) -> int:
        return len(self.init)


@dataclass
class OracleRecord:
    """One DFG oracle pass: the kernel's rows in one call."""
    call: int
    dfg: object            # the DFG the oracle executed
    final: list            # [row] -> {bank: words}, as the oracle gave back


def _no_span(name: str, label: str = ""):
    return contextlib.nullcontext()


class Recorder:
    def __init__(self, simulator, verify):
        self.sim = simulator
        self.verify = verify
        self.records: List[SimRecord] = []
        self.oracle: List[OracleRecord] = []
        self.call = -1
        self.span = _no_span
        self.replace: Optional[Callable] = None   # (cfg, inits) -> finals
        self.fault: Optional[Callable] = None     # (cfg, inits, finals) -> finals
        self._depth = 0
        self._orig = None

    def install(self) -> None:
        self._orig = (self.sim.simulate_batch, self.sim.simulate_multi,
                      self.verify.reference_banks_batch)
        self.sim.simulate_batch = self._batch
        self.sim.simulate_multi = self._multi
        self.verify.reference_banks_batch = self._oracle

    def uninstall(self) -> None:
        if self._orig is not None:
            (self.sim.simulate_batch, self.sim.simulate_multi,
             self.verify.reference_banks_batch) = self._orig
            self._orig = None

    def clear(self) -> None:
        self.records.clear()
        self.oracle.clear()

    def find(self, call: int, cfg) -> Optional[SimRecord]:
        for r in self.records:
            if r.call == call and r.cfg is cfg:
                return r
        return None

    def find_oracle(self, call: int, dfgs) -> Optional[OracleRecord]:
        for r in self.oracle:
            if r.call == call and any(r.dfg is d for d in dfgs):
                return r
        return None

    @contextlib.contextmanager
    def _inside(self):
        self._depth += 1
        try:
            with self.span("sim"):
                yield
        finally:
            self._depth -= 1

    def _keep(self, cfg, inits, finals, n_iters, n_inv):
        if self.fault is not None:
            finals = self.fault(cfg, inits, finals)
        self.records.append(SimRecord(
            call=self.call, cfg=cfg, init=list(inits), final=list(finals),
            cycles=cfg.n_cycles(n_iters) * n_inv if n_inv else 0,
            P=cfg.P, RF=cfg.RF, LI=max(1, cfg.LI), bits=cfg.bits))
        return finals

    def _batch(self, cfg, banks_batch, invocations, n_iters):
        batch = self._orig[0]
        if self._depth:
            return batch(cfg, banks_batch, invocations, n_iters)
        banks_batch = list(banks_batch)
        if self.replace is not None:
            finals = self.replace(cfg, banks_batch)
        else:
            with self._inside():
                finals = batch(cfg, banks_batch, invocations, n_iters)
        return self._keep(cfg, banks_batch, finals, n_iters, len(invocations))

    def _multi(self, items, n_iters):
        multi = self._orig[1]
        items = [(cfg, list(bb), list(inv)) for cfg, bb, inv in items]
        if self.replace is not None:
            outs = [self.replace(cfg, bb) for cfg, bb, _ in items]
        else:
            with self._inside():
                outs = multi(items, n_iters)
        return [self._keep(cfg, bb, out, n_iters, len(inv))
                for (cfg, bb, inv), out in zip(items, outs)]

    def _oracle(self, dfg, init_banks, invocations, mapped_iters, bits):
        with self.span("oracle"):
            out = self._orig[2](dfg, init_banks, invocations, mapped_iters,
                                bits)
        rows = len(next(iter(out.values()))) if out else 0
        self.oracle.append(OracleRecord(
            call=self.call, dfg=dfg,
            final=[{b: v[i] for b, v in out.items()} for i in range(rows)]))
        return out
