"""The work a window did, computed from shapes, and the table of peaks.

The simulated fabric's per-cycle update has to touch, for every PE and every
memory image (row), at least:

* its configuration slot: an opcode byte, an immediate of the datapath's
  width, and one mux-select byte for each of its 3 operand ports, its RF
  register-file write ports and its 4 crossbar output ports;
* its state, read at the start of the cycle and written at its end: 4
  crossbar output registers, RF registers, the FU output register and the
  load pipeline register, each a datapath word, plus a load-valid byte;
* its LI live-in registers, read;
* one memory word read and one written through its memory port.

That is a lower bound on the bytes moved per row-cycle for any
implementation of the same cycle-accurate model, which is what makes it a
roofline: a faster scan, a Pallas kernel or a different batching still has
to move these bytes.  Only shapes enter (P, RF, LI, datapath bits), never
names from a trace or the program's own encoding.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def word_bytes(bits: int) -> int:
    return (bits + 7) // 8


def bytes_per_row_cycle(P: int, RF: int, LI: int, bits: int) -> int:
    """Bytes one simulated cycle of one memory image must touch (see above)."""
    w = word_bytes(bits)
    config = 1 + w + (3 + RF + 4)
    state = 2 * ((4 + RF + 2) * w + 1)
    liveins = LI * w
    memory = 2 * w
    return P * (config + state + liveins + memory)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind raises."""
    with open(PEAKS, encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def sim_bytes(records) -> float:
    """Bytes the simulated row-cycles of these launches had to touch: real
    cycles and real rows only, so bucket padding counts as no work."""
    return float(sum(bytes_per_row_cycle(r.P, r.RF, r.LI, r.bits)
                     * r.cycles * r.rows for r in records))


def sim_roofline_share(run):
    """Per cent of the chip's HBM bandwidth over the window that the
    simulated fabric needed; None when the window simulated nothing."""
    if not run.records or run.window_s <= 0:
        return None
    bw = peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * sim_bytes(run.records) / (bw * run.window_s)
