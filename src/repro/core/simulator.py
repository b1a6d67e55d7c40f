"""Cycle-accurate functional CGRA simulator in JAX (paper Fig. 3 piece 8).

Morpher simulates the generated Verilog with Verilator; here the same
contract is met by a jit-compiled loop over cycles that executes the
configuration bitstreams exactly as the RTL control memories would:

  * every cycle, every PE reads its slot-(t mod II) configuration,
  * operand muxes select from {4 inbound crossbar wires, register file,
    own FU output register, immediate, live-in register},
  * the FU executes (16-bit two's-complement datapath), LOADs have a
    2-cycle latency through a pipeline register, STOREs commit at end of
    cycle gated by the control module's iteration-validity window
    (prologue/epilogue predication),
  * crossbar output registers and RF writes update from the same
    start-of-cycle snapshot (fully synchronous design).

All PEs are vectorized.  The cycle loop has two bodies, with the same
cycle word for word:

  * the scan (``_sim_body``): the cycle loop is a ``lax.scan`` and the
    invocations (the host-driven outer loops) a second ``lax.scan``
    threading the memory image.  It runs on every backend but a TPU, for
    the multi-configuration planes of ``simulate_multi`` everywhere, and
    where the VMEM body's footprint would pass its budget;
  * the VMEM body (``_vmem_sim``, TPU only): one Pallas kernel per launch
    runs every invocation and every cycle in in-kernel loops, with the
    fabric state, the images, the slot planes and the live-ins resident in
    VMEM.  ``_body`` decides from the backend and the shapes alone, for
    the traced function and the launch counters alike.

This is the component that makes verification fast enough to run in CI
for every mapped kernel.

Every entry point launches through ``_launch``: one executable from the
process-wide cache (``repro.core.simcache``), under the
``morpher.sim.launch`` span and its counters.  The traced body has a
leading batch axis of memory images:

  * ``simulate`` — one memory image for exactly the schedule's cycles
    (``CompiledKernel.run``, the mutation probe, ISA cross-validation);
  * ``simulate_batch`` — many seeds / test vectors of the same compiled
    kernel in a single launch, with the batched image buffer donated and
    the batch and cycle count bucketed, so a verification fleet across
    many kernels and seeds triggers a handful of traces, not one per call
    (``verify_batch``, and so every verify);
  * ``simulate_multi`` — many *configurations* sharing a shape bucket
    (``stack_signature``) in a single XLA launch: the config planes gain a
    leading batch-row axis and ride alongside the memory images, so one
    executable scores dozens of candidate fabrics of a design-space
    search.  Per (config, image) row the computation is op-for-op the
    single-config body, so results stay bit-identical.

The scan body is hand-batched rather than ``vmap``-ed, and shaped around
what profiles as expensive on small CGRA configurations:

  * the batch axis rides the PE dimension of every dense op, where it
    amortizes per-op dispatch nearly for free;
  * the memory image is a flat ``[batch*words]`` vector and stores scatter
    only the (few) lanes whose slot holds a STORE opcode — XLA scatters
    cost per *index*, so the historical all-P-lanes masked scatter paid
    ~90% of its cost writing the scratch word;
  * the operand / register-file / crossbar mux banks resolve in one
    concatenated select chain over all ports instead of three chains.

Configuration planes are dtype-narrowed (``config_gen.narrowed_planes``)
before entering the traced body: the pre-tiled per-cycle streams shrink
~4x, which is also what lets the tiling byte-cap admit longer simulations.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import obs, simcache
from .config_gen import (KIND_FUOUT, KIND_IMM, KIND_IN_E, KIND_IN_N,
                         KIND_IN_S, KIND_IN_W, KIND_LIREG, KIND_NONE,
                         KIND_REG, OPC, OPC_LOAD, OPC_NONE, OPC_PASS,
                         OPC_STORE, SimConfig, narrowed_planes)
from .dfg import Op

# xo-port index a reader consults on its neighbour: OPP of (N,E,S,W)
_OPP_IDX = np.array([2, 3, 0, 1], dtype=np.int32)



def _dp_dtype(bits: int):
    """Datapath carrier dtype: a `bits`-wide two's-complement machine is
    simulated natively in int16 when the widths coincide (integer overflow
    in XLA HLO is defined as mod-2^n wraparound, which *is* the datapath's
    wrap semantics, so the explicit `_wrap` becomes the identity and every
    value/state/memory buffer halves); other widths keep int32 carriers
    with explicit wrapping."""
    return jnp.int16 if bits == 16 else jnp.int32


def _wrap(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    if x.dtype == jnp.int16 and bits == 16:
        return x  # int16 overflow already wraps mod 2^16
    half = 1 << (bits - 1)
    full = 1 << bits
    return ((x + half) & (full - 1)) - half


def _alu(opc: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray,
         bits: int) -> jnp.ndarray:
    sh = b & (bits - 1)
    res = jnp.zeros_like(a)
    res = jnp.where(opc == OPC_PASS, a, res)
    res = jnp.where(opc == OPC[Op.ADD], a + b, res)
    res = jnp.where(opc == OPC[Op.SUB], a - b, res)
    res = jnp.where(opc == OPC[Op.MUL], a * b, res)
    res = jnp.where(opc == OPC[Op.SHL], a << sh, res)
    res = jnp.where(opc == OPC[Op.SHR], a >> sh, res)
    res = jnp.where(opc == OPC[Op.AND], a & b, res)
    res = jnp.where(opc == OPC[Op.OR], a | b, res)
    res = jnp.where(opc == OPC[Op.XOR], a ^ b, res)
    res = jnp.where(opc == OPC[Op.CMPGE], (a >= b).astype(a.dtype), res)
    res = jnp.where(opc == OPC[Op.CMPEQ], (a == b).astype(a.dtype), res)
    res = jnp.where(opc == OPC[Op.CMPLT], (a < b).astype(a.dtype), res)
    res = jnp.where(opc == OPC[Op.SELECT], jnp.where(a != 0, b, c), res)
    return _wrap(res, bits)


# configuration planes indexed by the II slot; pre-tiled to cycle streams
# before the scan so the traced body does no `[t % II]` dynamic gathers.
# ``port_idx`` maps every mux port (operands + RF writes + crossbar
# writes, [II,P,3+RF+4]) to its gather index into the flat start-of-cycle
# state vector — the whole mux fabric resolves as one gather instead of a
# per-kind select chain; ``rf_mask``/``xo_mask`` flag which write ports
# are configured; ``store_lanes`` lists the (padded, -1-terminated) PE
# indices whose slot holds a STORE, so the memory scatter touches only
# lanes that can commit.
_SLOT_PLANES = ("op", "imm", "port_idx", "rf_mask", "xo_mask",
                "force_before", "force_val", "mem_off", "mem_words",
                "valid_start", "store_lanes")

# pre-tiling cap in *bytes of tiled stream*: beyond this the tiled config
# would dominate memory, so long simulations fall back to the per-cycle
# slot gather (identical numerics, O(II) config memory).  The budget is
# sized from the actual per-cycle footprint — every plane's inner dims
# (e.g. kind_all is [P,3+RF+4]) times its (narrowed) item size — not the
# bare n_cycles*P estimate, which undercounted the streams several-fold.
_TILE_BYTES_LIMIT = 64 << 20


def _tile_bytes_per_cycle(c: Dict[str, jnp.ndarray], II: int) -> int:
    """Bytes of pre-tiled stream one simulated cycle costs: the sum over
    slot planes of (elements per slot) x (narrowed item size).  Dividing
    the total element count by II covers both plane layouts — ``[II,...]``
    single-config and ``[B,II,...]`` config-batched (where every batch
    row's slot is streamed, so the per-cycle cost scales with B)."""
    return sum(int(np.prod(c[k].shape)) // II * c[k].dtype.itemsize
               for k in _SLOT_PLANES)


def _pretiled(c: Dict[str, jnp.ndarray], II: int, n_cycles: int) -> bool:
    """Whether the body pre-tiles the slot planes ``c`` into per-cycle
    streams for an ``n_cycles`` scan, or gathers the slot every cycle.
    Shapes and dtypes only, so host, device and traced planes agree."""
    return n_cycles * _tile_bytes_per_cycle(c, II) <= _TILE_BYTES_LIMIT


def _state_layout(P: int, RF: int, LI: int):
    """Section offsets of the flat per-cycle state vector the mux fabric
    gathers from: [ xo (P*4) | regs (P*RF) | fu (P) | imm (P) |
    li (P*LI) | zero (1) ] — the trailing cell is a constant 0 every
    unconfigured (KIND_NONE) port reads."""
    xo_off = 0
    reg_off = xo_off + P * 4
    fu_off = reg_off + P * RF
    imm_off = fu_off + P
    li_off = imm_off + P
    zero_off = li_off + P * LI
    return xo_off, reg_off, fu_off, imm_off, li_off, zero_off


def _port_gather_idx(kind: np.ndarray, idx: np.ndarray, cfg: SimConfig,
                     LI: int, rf_pad: int) -> np.ndarray:
    """Host-side compilation of one mux bank ([II,P,K] kind/idx planes)
    into flat state-vector gather indices — the per-kind select chain of
    the mux fabric becomes pure data, so the traced body resolves every
    port of every bank with a single gather.

    ``rf_pad >= cfg.RF`` is the register-file width of the *executable*'s
    state layout (``simulate_multi`` pads the group to one RF bucket so
    differently-provisioned fabrics share a trace); reads still clip to
    the config's own RF, so padded rows are never addressed."""
    P, RF = cfg.P, cfg.RF
    xo_off, reg_off, fu_off, imm_off, li_off, zero_off = \
        _state_layout(P, rf_pad, LI)
    II, _, K = kind.shape
    pe = np.arange(P)[None, :, None]
    nbr = np.asarray(cfg.nbr_idx)                          # [P,4]
    out = np.full(kind.shape, zero_off, dtype=np.int64)    # KIND_NONE -> 0
    for d, kind_in in enumerate((KIND_IN_N, KIND_IN_E, KIND_IN_S,
                                 KIND_IN_W)):
        # inbound wire: neighbour's opposite-facing crossbar port
        sel = kind == kind_in
        val = nbr[:, d][None, :, None] * 4 + _OPP_IDX[d] + xo_off
        out = np.where(sel, np.broadcast_to(val, kind.shape), out)
    out = np.where(kind == KIND_REG,
                   reg_off + pe * rf_pad + np.clip(idx, 0, RF - 1), out)
    out = np.where(kind == KIND_FUOUT, fu_off + pe, out)
    out = np.where(kind == KIND_IMM, imm_off + pe, out)
    out = np.where(kind == KIND_LIREG,
                   li_off + pe * LI + np.clip(idx, 0, LI - 1), out)
    return out.astype(np.int16 if zero_off <= np.iinfo(np.int16).max
                      else np.int32)


def _lane_table(op: np.ndarray, opcode: int) -> np.ndarray:
    """Per slot, the PE indices whose opcode is ``opcode``, padded with -1
    to the busiest slot's count (at least one column): ``[II, n]``."""
    op = np.asarray(op)
    lanes = [np.nonzero(row == opcode)[0] for row in op]
    n = max(1, max(len(l) for l in lanes))
    out = np.full((op.shape[0], n), -1,
                  dtype=np.int8 if op.shape[1] <= 127 else np.int16)
    for s, l in enumerate(lanes):
        out[s, :len(l)] = l
    return out


def _host_planes(cfg: SimConfig,
                 rf_pad: int = 0) -> Dict[str, np.ndarray]:
    """Host-side compilation of a SimConfig into the simulator's slot
    planes (numpy), cached on the SimConfig (keyed by the RF width the
    executable will use; 0 / cfg.RF is the plain single-config layout).

    Starting from the dtype-narrowed planes, the three mux banks are
    compiled into one ``port_idx`` gather plane over the flat state
    vector, write masks replace the RF/crossbar kind tests, and the
    per-slot store- and load-lane tables are derived from the opcode
    plane (see ``_SLOT_PLANES``; only the VMEM body reads the load
    lanes).  With ``rf_pad > cfg.RF`` the RF write-port bank
    pads to ``rf_pad`` ports with unconfigured (KIND_NONE, mask-off)
    lanes and the state layout stretches to match — the padded register
    rows are never written or read, which is what lets fabrics with
    different register-file provisioning stack into one executable
    bit-exactly.

    The cache means a SimConfig is frozen once simulated — and that is
    enforced: building the cache marks the numpy planes read-only, so a
    later in-place edit raises instead of silently diverging from the
    compiled copies.  Configs come out of ``generate_config``/
    ``from_json`` and are never mutated by the flow; anyone editing one by
    hand (tests injecting faults) must do so before the first run or
    delete ``_np_planes``/``_jnp_planes`` and restore
    ``.flags.writeable``.
    """
    R = rf_pad or cfg.RF
    assert R >= cfg.RF, "rf_pad must not shrink the register file"
    by_rf = getattr(cfg, "_np_planes", None)
    if by_rf is None:
        by_rf = cfg._np_planes = {}
    cached = by_rf.get(R)
    if cached is None:
        p = narrowed_planes(cfg)
        LI = max(1, cfg.LI)
        rf_kind = np.asarray(p["rf_kind"])
        rf_idx = np.asarray(p["rf_idx"])
        if R > cfg.RF:                   # pad write-port bank: dead lanes
            pad = ((0, 0), (0, 0), (0, R - cfg.RF))
            rf_kind = np.pad(rf_kind, pad, constant_values=KIND_NONE)
            rf_idx = np.pad(rf_idx, pad, constant_values=0)
        kind_all = np.concatenate(
            [p["src_kind"], rf_kind, p["xo_kind"]], axis=2)
        idx_all = np.concatenate(
            [p["src_idx"], rf_idx, p["xo_idx"]], axis=2)
        cached = {
            "op": np.asarray(p["op"]), "imm": np.asarray(p["imm"]),
            "port_idx": _port_gather_idx(kind_all, idx_all, cfg, LI, R),
            "rf_mask": rf_kind != KIND_NONE,
            "xo_mask": np.asarray(p["xo_kind"]) != KIND_NONE,
            "force_before": np.asarray(p["force_before"]),
            "force_val": np.asarray(p["force_val"]),
            "mem_off": np.asarray(p["mem_off"]),
            "mem_words": np.asarray(p["mem_words"]),
            "valid_start": np.asarray(p["valid_start"]),
            "store_lanes": _lane_table(cfg.op, OPC_STORE),
            "load_lanes": _lane_table(cfg.op, OPC_LOAD),
        }
        for k in SimConfig._ARRAY_DTYPES:
            arr = getattr(cfg, k)
            if isinstance(arr, np.ndarray):
                arr.flags.writeable = False
        by_rf[R] = cached
    return cached


def _as_jnp(cfg: SimConfig) -> Dict[str, jnp.ndarray]:
    """Device copies of ``_host_planes(cfg)``, cached on the SimConfig so
    repeated runs/verifies skip the host-side compilation and the
    transfer."""
    cached = getattr(cfg, "_jnp_planes", None)
    if cached is None:
        cached = {k: jnp.asarray(v) for k, v in _host_planes(cfg).items()}
        cfg._jnp_planes = cached
    return cached


def _sim_body(c: Dict[str, jnp.ndarray], mem0: jnp.ndarray,
              li_stack: jnp.ndarray, *, II: int, P: int, RF: int,
              bits: int, n_iters: int, n_cycles: int,
              cfg_batched: bool = False) -> jnp.ndarray:
    """A batch of memory images through all invocations in one launch.

    ``mem0``: [batch, words] initial images (batch=1 is the sequential
    path).  Per batch row the computation is op-for-op the classic
    single-image simulation, so results are bit-identical per element;
    batch and image size specialize from ``mem0``'s shape at trace time.
    Address and time-window sums happen in int32 (the narrowed config
    streams only carry the values).

    ``cfg_batched=True`` is the multi-architecture variant: every config
    plane carries a leading batch-row axis (``[B, II, ...]``, one config
    per memory image; ``li_stack`` becomes ``[n_inv, B, P, LI]``), so one
    launch simulates many *different* fabrics sharing the static shape
    tuple.  The branches below are trace-time only — with a broadcast
    config the batched trace degenerates to exactly the single-config
    graph per row, which is what keeps ``simulate_multi`` bit-identical
    to ``simulate_batch`` per element.
    """
    B, W = mem0.shape
    LI = li_stack.shape[-1]
    dt = _dp_dtype(bits)
    row_off = (jnp.arange(B) * W)[:, None]                # [B,1]
    scratch = row_off + (W - 1)                           # [B,1] per-row

    # pre-tile the per-slot configuration into per-cycle streams: the scan
    # consumes them as xs, so XLA sees static slot schedules instead of a
    # dynamic `cfg[t % II]` gather inside every traced cycle (the gather
    # defeats scan-level constant propagation and costs a fused lookup per
    # cycle per plane).  One gather per plane here, outside the loop.
    # Tiling is O(n_cycles) memory, so very long simulations (bounded by
    # _TILE_BYTES_LIMIT total tiled-stream bytes) keep the II-sized
    # planes and gather per cycle instead.
    pretile = _pretiled(c, II, n_cycles)
    t_arr = jnp.arange(n_cycles)
    if pretile:
        slots = jnp.arange(n_cycles) % II
        if cfg_batched:
            # [B,II,...] -> [n_cycles,B,...]: scan consumes cycle-major
            xs_cfg = {k: jnp.moveaxis(c[k][:, slots], 0, 1)
                      for k in _SLOT_PLANES}
        else:
            xs_cfg = {k: c[k][slots] for k in _SLOT_PLANES}
    else:
        xs_cfg = {}

    def one_invocation(mem: jnp.ndarray, li: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
        regs0 = jnp.zeros((B, P, RF), dtype=dt)
        xo0 = jnp.zeros((B, P, 4), dtype=dt)
        fu0 = jnp.zeros((B, P), dtype=dt)
        ldp0 = jnp.zeros((B, P), dtype=dt)
        fl0 = jnp.zeros((B, P), dtype=bool)
        if cfg_batched:
            li_flat = li.reshape(B, P * LI).astype(dt)
        else:
            li_flat = jnp.broadcast_to(li.reshape(-1).astype(dt),
                                       (B, P * LI))
        zero_cell = jnp.zeros((B, 1), dtype=dt)
        state_len = P * (4 + RF + 2 + LI) + 1
        state_row_off = (jnp.arange(B) * state_len)[:, None, None]  # [B,1,1]

        def cycle(carry, xs):
            regs, xo, fu, ldp, fl, mem = carry
            t, ct = xs
            if not pretile:
                slot = t % II
                ct = {k: (c[k][:, slot] if cfg_batched else c[k][slot])
                      for k in _SLOT_PLANES}
            opc = ct["op"]                                # [B,P] | [P]

            # the whole mux fabric (operand + RF-write + crossbar-write
            # ports) resolves as one flat 1D gather from the start-of-
            # cycle state snapshot (layout: _state_layout; indices
            # precompiled per slot by _port_gather_idx, offset per batch
            # row here — flat scalar gathers are what XLA CPU does fast)
            imm = ct["imm"].astype(dt)
            if not cfg_batched:
                imm = jnp.broadcast_to(imm[None], (B, P))
            state = jnp.concatenate(
                [xo.reshape(B, -1), regs.reshape(B, -1), fu,
                 imm, li_flat, zero_cell], axis=1)        # [B,SL]
            pidx = state_row_off + ct["port_idx"].astype(jnp.int32)
            v = jnp.take(state.reshape(-1), pidx)         # [B,P,3+RF+4]

            ops = v[:, :, :3]                             # [B,P,3]
            ops = jnp.where(t < ct["force_before"], ct["force_val"], ops)
            a, b, p3 = ops[:, :, 0], ops[:, :, 1], ops[:, :, 2]
            res = _alu(opc, a, b, p3, bits)

            # memory: flat global addresses = row offset + bank offset +
            # clipped bank-relative address; stores commit through only
            # the lanes whose slot holds a STORE (XLA scatters cost per
            # index), gated by the iteration-validity window — padded /
            # gated-off lanes write the scratch word's own value back
            mem_w = ct["mem_words"].astype(jnp.int32)
            gaddr = row_off + ct["mem_off"].astype(jnp.int32) + \
                jnp.clip(a, 0, mem_w - 1)                 # [B,P]
            loaded = jnp.take(mem, gaddr)
            is_load = opc == OPC_LOAD
            is_store = opc == OPC_STORE
            vstart = ct["valid_start"].astype(jnp.int32)
            window = is_store & (t >= vstart) & (t < vstart + n_iters * II)
            sl = ct["store_lanes"]                        # [B,S] | [S]
            if cfg_batched:
                slc = jnp.clip(sl, 0, P - 1).astype(jnp.int32)
                gate = (jnp.take_along_axis(window, slc, axis=1)
                        & (sl >= 0))                      # [B,S]
                st_src = jnp.take_along_axis(gaddr, slc, axis=1)
                st_val = jnp.take_along_axis(b, slc, axis=1)
            else:
                slc = jnp.clip(sl, 0, P - 1)
                gate = window[slc] & (sl >= 0)            # [S]
                st_src = gaddr[:, slc]
                st_val = b[:, slc]
            st_addr = jnp.where(gate, st_src, scratch)
            scr_val = jnp.take(mem, scratch)              # [B,1]
            mem = mem.at[st_addr].set(jnp.where(gate, st_val, scr_val))

            fu_next = jnp.where(fl, ldp,
                                jnp.where((opc != OPC_NONE) & ~is_load
                                          & ~is_store, res, fu))
            ldp_next = jnp.where(is_load, loaded, ldp)
            fl_next = jnp.broadcast_to(is_load, (B, P))

            # register-file and crossbar writes from the resolved ports
            regs_next = jnp.where(ct["rf_mask"], v[:, :, 3:3 + RF], regs)
            xo_next = jnp.where(ct["xo_mask"], v[:, :, 3 + RF:], xo)

            return (regs_next, xo_next, fu_next, ldp_next, fl_next, mem), 0

        carry = (regs0, xo0, fu0, ldp0, fl0, mem)
        carry, _ = jax.lax.scan(cycle, carry, (t_arr, xs_cfg))
        return carry[-1], 0

    mem, _ = jax.lax.scan(one_invocation, mem0.reshape(B * W), li_stack)
    return mem.reshape(B, W)


# ------------------------------------------------- VMEM-resident cycle body
# The TPU body: one Pallas kernel per launch runs every invocation and
# every cycle in in-kernel loops, with the fabric state, the memory images,
# the II-sized slot planes and the live-ins resident in VMEM throughout.
# Values ride int32 carriers wrapped to the datapath width (``_wrap``), and
# every array is laid out in 128-lane blocks:
#
#   * the carried state is ``X`` = [ xo (P*4) | regs (P*RF) ] and ``F`` =
#     the FU output registers, one lane per PE; the per-slot immediates and
#     the invocation's live-ins form a constant block ``C`` = [ imm (P) |
#     li (P*LI) ];
#   * the mux fabric resolves as a one-hot matmul ``[X | F] @ oh[slot] +
#     C @ ohc[slot]`` into [ write ports in ``X``'s layout | operands a, b,
#     c at lanes k*P + pe ].  A column has at most one nonzero term and the
#     values enter as 8-bit pieces that bf16 holds exactly, so the f32
#     accumulation is exact; an unconfigured port has no term and reads 0;
#   * each slot's one-hot planes stay in VMEM where they fit the budget; a
#     fabric whose planes do not (64 PEs from II 16 on) keeps each slot's
#     source rows instead and builds the cycle's planes in the kernel
#     (``_vmem_planes``);
#   * LOADs and STOREs touch only the slot's load / store lanes (SMEM
#     tables), by iota compares over a window of the image row that holds
#     the lane's bank (``_pass_words`` lanes from the bank's offset
#     aligned down to 128, kept inside the image); a gated-off store
#     writes nothing;
#   * step t of the cycle loop runs cycle t's mux and ALU beside cycle
#     t-1's memory work, which depends on nothing of cycle t: a LOAD's word
#     reaches an FU register only at the end of the next cycle, so the
#     image passes overlap the matmul instead of following it.
_VMEM_BUDGET = 48 << 20


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _pass_words(cfg: SimConfig) -> int:
    """Lanes one LOAD or STORE pass of the VMEM body covers for ``cfg``:
    the fewest whole 128-lane blocks that hold every bank the planes
    address (``mem_off``, ``mem_words``) from its offset aligned down to
    128 lanes, at most the image's lanes."""
    return min(_lanes(int(np.max(cfg.mem_off % 128 + cfg.mem_words))),
               _lanes(cfg.total_words))


def _vmem_layout(P: int, RF: int, LI: int) -> Tuple[int, int, int, int]:
    """Lane widths of the VMEM body's blocks: ``X`` (crossbar outputs and
    registers), per-PE vectors, the constant block ``C`` (immediates and
    live-ins) and the packed operand block."""
    return (_lanes(P * (4 + RF)), _lanes(P), _lanes(P * (1 + LI)),
            _lanes(3 * P))


def _vmem_bytes(B: int, W: int, P: int, RF: int, LI: int, II: int,
                n_inv: int, resident: bool = True) -> int:
    """VMEM the kernel body holds for these shapes: the image in and out
    plus two image-sized temporaries, the mux planes, the live-in rows, and
    the [II, 1, N] slot planes and scratch, whose single row the (8, 128)
    tiling pads to 8.  The mux planes are the one-hot planes of every slot
    (bf16) when ``resident``, else the source-row tables they are built
    from and one plane built in the kernel with its temporaries."""
    XW, PL, CW, OW = _vmem_layout(P, RF, LI)
    NW = XW + OW
    image = -(-B // 8) * 8 * _lanes(W) * 4
    rows = XW + PL + CW
    planes = (II * rows * NW * 2 if resident
              else rows * NW * 6 + II * 8 * 4 * 2 * NW)
    return (4 * image + planes + -(-n_inv // 8) * 8 * CW * 4
            + II * 8 * 4 * (XW + 2 * OW + PL + CW + NW))


def _vmem_planes(B: int, W: int, P: int, RF: int, LI: int, II: int,
                 n_inv: int) -> str:
    """How the VMEM body would hold the mux planes of these shapes:
    ``"resident"`` (every slot's one-hot plane in VMEM) where that fits
    ``_VMEM_BUDGET``, else ``"built"`` (each cycle's plane built in the
    kernel from its slot's source rows, for fabrics whose planes do not
    fit) where that fits, else ``""``."""
    for mode in ("resident", "built"):
        if _vmem_bytes(B, W, P, RF, LI, II, n_inv,
                       mode == "resident") <= _VMEM_BUDGET:
            return mode
    return ""


def _body(multi: bool, B: int, W: int, P: int, RF: int, LI: int, II: int,
          n_inv: int) -> str:
    """Which body a launch of these shapes runs: ``"vmem"`` (the Pallas
    kernel) on a TPU backend for single-configuration planes whose
    footprint fits ``_VMEM_BUDGET`` one way or the other
    (``_vmem_planes``), else ``"scan"``.  Shared by the traced function
    and the launch counters, so the two cannot disagree."""
    if (jax.default_backend() != "tpu" or multi
            or not _vmem_planes(B, W, P, RF, LI, II, n_inv)):
        return "scan"
    return "vmem"


def _mux_maps(P: int, RF: int, LI: int):
    """Static index maps from the scan's flat state layout
    (``_state_layout``) and port order to the VMEM body's blocks: the row
    of ``[X | F]`` and the row of ``C`` each state cell feeds (-1 where
    none; the scan's zero cell feeds neither), and the output column of
    each [P, 3+RF+4] mux port."""
    XW, PL, CW, OW = _vmem_layout(P, RF, LI)
    xo_off, reg_off, fu_off, imm_off, li_off, zero_off = \
        _state_layout(P, RF, LI)
    row = np.full(zero_off + 1, -1, np.int32)
    crow = np.full(zero_off + 1, -1, np.int32)
    row[:fu_off] = np.arange(fu_off)                 # xo, then registers
    row[fu_off:imm_off] = XW + np.arange(P)
    crow[imm_off:li_off] = np.arange(P)
    crow[li_off:zero_off] = P + np.arange(P * LI)
    pe = np.arange(P)[:, None]
    col = np.concatenate([
        XW + np.arange(3)[None, :] * P + pe,         # operands
        reg_off + pe * RF + np.arange(RF)[None, :],  # register writes
        xo_off + pe * 4 + np.arange(4)[None, :],     # crossbar writes
    ], axis=1)                                       # [P, 3+RF+4]
    return row, crow, col


def _mux_rows(port_idx: jnp.ndarray, to_row: np.ndarray, col: np.ndarray,
              cols: int) -> jnp.ndarray:
    """[II, cols] int32: the block row output column ``col[pe, k]`` reads,
    ``to_row[port_idx[slot, pe, k]]``, or -1 where it reads none."""
    II = port_idx.shape[0]
    src = jnp.take(jnp.asarray(to_row), port_idx.astype(jnp.int32)
                   .reshape(II, -1))
    return jnp.full((II, cols), -1, jnp.int32).at[:, col.reshape(-1)].set(
        src)


def _one_hot(by_col: jnp.ndarray, rows: int) -> jnp.ndarray:
    """[II, rows, cols] bf16: 1 where column c of slot s reads block row
    ``by_col[s, c]`` (``_mux_rows``)."""
    return (by_col[:, None, :] == jnp.arange(rows)[None, :, None]).astype(
        jnp.bfloat16)


def _mux(x: jnp.ndarray, oh: jnp.ndarray, bits: int) -> jnp.ndarray:
    """``x @ oh`` exactly for a one-hot ``oh`` and ``bits``-wide signed
    values: 8-bit pieces (the low ones unsigned), stacked along rows, each
    exact in bf16, recombined in int32."""
    n = -(-bits // 8)
    R = x.shape[0]
    pieces = [(x >> (8 * k)) & 255 for k in range(n - 1)] + \
        [x >> (8 * (n - 1))]
    xs = jnp.concatenate(pieces, axis=0).astype(jnp.float32).astype(
        jnp.bfloat16)
    y = jnp.dot(xs, oh, preferred_element_type=jnp.float32).astype(
        jnp.int32)
    out = y[(n - 1) * R:]
    for k in range(n - 2, -1, -1):
        out = out * 256 + y[k * R:(k + 1) * R]
    return out


def _vmem_sim(c: Dict[str, jnp.ndarray], mem0: jnp.ndarray,
              li_stack: jnp.ndarray, *, II: int, P: int, RF: int,
              bits: int, n_iters: int, n_cycles: int, pass_words: int,
              interpret: bool = False) -> jnp.ndarray:
    """``_sim_body`` of single-configuration planes as one Pallas kernel
    (see the section comment): the same cycle, word for word, with the
    loops inside the kernel.  Each LOAD and STORE passes over a window of
    ``pass_words`` lanes (``_pass_words``) that holds the PE's bank; a
    window as wide as the image starts at lane 0.  ``interpret=True`` runs
    it in the Pallas interpreter (the CPU tests)."""
    B, W = mem0.shape
    n_inv, _, LI = li_stack.shape
    XW, PL, CW, OW = _vmem_layout(P, RF, LI)
    NW, KR = XW + OW, XW + PL
    B8, Wp, G = -(-B // 8) * 8, _lanes(W), -(-n_inv // 8)
    PW = pass_words
    L = c["load_lanes"].shape[1]
    S = c["store_lanes"].shape[1]
    i32 = jnp.int32

    row, crow, col = _mux_maps(P, RF, LI)
    src = _mux_rows(c["port_idx"], row, col, NW)
    csrc = _mux_rows(c["port_idx"], crow, col, NW)
    resident = _vmem_planes(B, W, P, RF, LI, II, n_inv) == "resident"
    if resident:               # every slot's planes, built once per launch
        src, csrc = _one_hot(src, KR), _one_hot(csrc, CW)
    else:                      # each slot's source rows, [II, 1, NW]
        src, csrc = src[:, None, :], csrc[:, None, :]

    def plane(ref, s, rows):  # slot s's one-hot plane, [rows, NW]
        if resident:
            return ref[s]
        iota = jax.lax.broadcasted_iota(i32, (rows, NW), 0)
        return jnp.where(iota == ref[s], 1.0, 0.0).astype(jnp.bfloat16)

    def lanes(x, width):          # [II, n] -> [II, 1, width], zero-padded
        x = x.astype(i32)
        return jnp.pad(x, ((0, 0), (0, width - x.shape[1])))[:, None, :]

    wmask = lanes(jnp.concatenate(
        [c["xo_mask"].reshape(II, P * 4), c["rf_mask"].reshape(II, P * RF)],
        axis=1), XW)
    fb = lanes(jnp.swapaxes(c["force_before"], 1, 2).reshape(II, 3 * P), OW)
    fv = lanes(_wrap(jnp.swapaxes(c["force_val"], 1, 2).reshape(II, 3 * P)
                     .astype(i32), bits), OW)
    opc = lanes(c["op"], PL)
    imm = lanes(_wrap(c["imm"].astype(i32), bits), CW)
    li = _wrap(li_stack.reshape(n_inv, P * LI).astype(i32), bits)
    li = jnp.pad(li, ((0, G * 8 - n_inv), (P, CW - P - P * LI)))
    li = li.reshape(G, 8, CW)
    mem = jnp.pad(mem0.astype(i32), ((0, B8 - B), (0, Wp - W)))
    smem = [c[k].astype(i32) for k in ("load_lanes", "store_lanes",
                                       "mem_off", "mem_words",
                                       "valid_start")]
    window = n_iters * II

    def kernel(oh_ref, ohc_ref, imm_ref, li_ref, wm_ref, fb_ref, fv_ref,
               op_ref, mem_in, lds_ref, sts_ref, moff_ref, mw_ref, vs_ref,
               mem_ref, vc_ref):
        mem_ref[...] = mem_in[...]
        lane = jax.lax.broadcasted_iota(i32, (B8, PL), 1)
        word = jax.lax.broadcasted_iota(i32, (B8, PW), 1)
        sub = jax.lax.broadcasted_iota(i32, (8, CW), 0)

        def lane_of(x, q):        # [B8, PL] -> lane q as [B8, 1]
            return jnp.sum(jnp.where(lane == q, x, 0), axis=1,
                           keepdims=True)

        def address(a, s, q):     # clipped bank-relative word of lane q
            return moff_ref[s, q] + jnp.clip(lane_of(a, q), 0,
                                             mw_ref[s, q] - 1)

        def bank_pass(s, q):      # lane q's bank as PW lanes of the image
            start = pl.multiple_of(
                jnp.clip(moff_ref[s, q] // 128 * 128, 0, Wp - PW), 128)
            return pl.ds(start, PW), start

        def invocation(i, carry):
            li_row = jnp.sum(jnp.where(sub == i % 8, li_ref[i // 8], 0),
                             axis=0, keepdims=True)           # [1, CW]

            def constants(s, carry):
                cblk = jnp.broadcast_to(imm_ref[s] + li_row, (8, CW))
                vc_ref[s] = _mux(cblk, plane(ohc_ref, s, CW), bits)[0:1]
                return carry

            jax.lax.fori_loop(0, II, constants, 0)

            def store(t, a, b):
                # the STOREs of cycle t, each under its validity window
                s = t % II
                for k in range(S):
                    p = sts_ref[s, k]
                    q = jnp.maximum(p, 0)
                    vs = vs_ref[s, q]

                    @pl.when((p >= 0) & (t >= vs) & (t < vs + window))
                    def _store():
                        w, start = bank_pass(s, q)
                        mem_ref[:, w] = jnp.where(
                            word == address(a, s, q) - start, lane_of(b, q),
                            mem_ref[:, w])

            def cycle(t, st):
                # cycle t's mux and ALU beside cycle t-1's memory work:
                # cycle t-1's LOADs read the image after the stores of t-2
                # and fill the pipeline register the FU takes at the end
                # of cycle t; its STOREs end the step, before the next
                # step runs cycle t's LOADs
                x, fu, ldp, fl, a_prev, b_prev = st
                s = t % II
                v = _mux(jnp.concatenate([x, fu], axis=1),
                         plane(oh_ref, s, KR), bits) + vc_ref[s]  # [B8, NW]
                ops = jnp.where(t < fb_ref[s], fv_ref[s], v[:, XW:])
                a = ops[:, :PL]
                b = pltpu.roll(ops, OW - P, 1)[:, :PL]
                p3 = pltpu.roll(ops, OW - 2 * P, 1)[:, :PL]
                o = op_ref[s]                                 # [1, PL]
                res = _alu(o, a, b, p3, bits)

                s_prev = (t + II - 1) % II
                for k in range(L):
                    p = jnp.where(t >= 1, lds_ref[s_prev, k], -1)
                    q = jnp.maximum(p, 0)
                    w, start = bank_pass(s_prev, q)
                    val = jnp.sum(jnp.where(
                        word == address(a_prev, s_prev, q) - start,
                        mem_ref[:, w], 0), axis=1, keepdims=True)
                    ldp = jnp.where(lane == p, val, ldp)

                is_load = o == OPC_LOAD
                is_store = o == OPC_STORE
                fu_next = jnp.where(
                    fl != 0, ldp,
                    jnp.where((o != OPC_NONE) & ~is_load & ~is_store,
                              res, fu))
                fl_next = jnp.broadcast_to(is_load.astype(i32), (B8, PL))
                x_next = jnp.where(wm_ref[s] != 0, v[:, :XW], x)

                @pl.when(t >= 1)
                def _():
                    store(t - 1, a_prev, b_prev)

                return x_next, fu_next, ldp, fl_next, a, b

            zeros = jnp.zeros((B8, PL), i32)
            st = jax.lax.fori_loop(0, n_cycles, cycle,
                                   (jnp.zeros((B8, XW), i32), zeros, zeros,
                                    zeros, zeros, zeros))
            store(n_cycles - 1, st[4], st[5])
            return carry

        jax.lax.fori_loop(0, n_inv, invocation, 0)

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    limit = _vmem_bytes(B, W, P, RF, LI, II, n_inv, resident) + (16 << 20)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B8, Wp), i32),
        in_specs=[vmem] * 9 + [smem_spec] * 5,
        out_specs=vmem,
        scratch_shapes=[pltpu.VMEM((II, 1, NW), i32)],
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
        interpret=interpret,
        name="morpher_sim_cycles",
    )(src, csrc, imm, li, wmask, fb, fv, opc, mem, *smem)
    return out[:B, :W].astype(mem0.dtype)


def _build_batched(sig: simcache.SimSignature):
    """Compile-on-demand builder for one batched-simulator signature,
    jitted with the batched image buffer donated so per-seed images are
    updated in place.  Buffer donation is a device-memory optimization XLA
    only implements off-CPU, so it is skipped on the CPU backend (where it
    would just warn).

    The jitted functions are named, so their XLA modules are
    ``jit_morpher_sim`` (one configuration's planes) and
    ``jit_morpher_sim_multi`` (planes with a leading row axis) whatever
    the signature; a jitted ``functools.partial`` would be ``jit__unknown``.
    """
    static = dict(II=sig.II, P=sig.P, RF=sig.RF, bits=sig.bits,
                  n_iters=sig.n_iters, n_cycles=sig.n_cycles)

    def morpher_sim(c, mem0, li_stack):
        B, W = mem0.shape
        n_inv, _, LI = li_stack.shape
        if _body(False, B, W, sig.P, sig.RF, LI, sig.II, n_inv) == "vmem":
            return _vmem_sim(c, mem0, li_stack, pass_words=sig.pass_words,
                             **static)
        return _sim_body(c, mem0, li_stack, **static)

    def morpher_sim_multi(c, mem0, li_stack):
        return _sim_body(c, mem0, li_stack, cfg_batched=True, **static)

    donate = (1,) if jax.default_backend() != "cpu" else ()
    return jax.jit(morpher_sim_multi if sig.multi else morpher_sim,
                   donate_argnums=donate)


def _launch(sig: simcache.SimSignature, planes: Dict, mem: np.ndarray,
            li_stack: np.ndarray, real_rows: int,
            real_row_steps: int) -> np.ndarray:
    """One batched executable call through its host result, under the
    ``morpher.sim.launch`` span with the launch's counters as attrs:
    scan steps launched (bucketed cycles x invocations), rows (bucketed
    batch) and row-steps, the real rows and row-steps among them, whether
    which body ran (``_body``: the VMEM kernel or the scan), the fabric's
    PEs (``pes``), the VMEM the kernel holds for these shapes
    (``vmem_bytes``, ``_vmem_bytes`` as ``_vmem_planes`` would hold the
    planes), the image's lanes (``image_lanes``) and the lanes one LOAD or
    STORE pass covers (``pass_words``: the bank window on the kernel, the
    whole image on the scan), whether the scan took the pre-tiled streams
    (never on the kernel), and whether this launch built the executable."""
    n_inv = int(li_stack.shape[0])
    steps = sig.n_cycles * n_inv
    W = mem.shape[-1]
    shapes = (sig.batch, W, sig.P, sig.RF, li_stack.shape[-1], sig.II,
              n_inv)
    body = _body(sig.multi, *shapes)
    if body == "scan":          # the scan has no windows to key on
        sig = dataclasses.replace(sig, pass_words=0)
    with obs.span("morpher.sim.launch", multi=sig.multi, invocations=n_inv,
                  steps=steps, rows=sig.batch, real_rows=real_rows,
                  row_steps=steps * sig.batch,
                  real_row_steps=real_row_steps, body=body, pes=sig.P,
                  vmem_bytes=_vmem_bytes(
                      *shapes, _vmem_planes(*shapes) != "built"),
                  image_lanes=_lanes(W),
                  pass_words=sig.pass_words or _lanes(W),
                  pretiled=(body == "scan"
                            and _pretiled(planes, sig.II, sig.n_cycles)),
                  built=False) as attrs:
        def build():
            attrs["built"] = True
            return _build_batched(sig)

        fn = simcache.get(sig, build)
        return np.asarray(fn(planes, jnp.asarray(mem),
                             jnp.asarray(li_stack)))


def _banks_to_mem(cfg: SimConfig, banks: Dict[str, np.ndarray]) -> np.ndarray:
    mem = np.zeros(cfg.total_words,
                   dtype=np.int16 if cfg.bits == 16 else np.int32)
    for bid, off in cfg.bank_offsets.items():
        img = banks[f"bank{bid}"]
        mem[off:off + len(img)] = img
    return mem


def _mem_to_banks(cfg: SimConfig, mem: np.ndarray,
                  banks: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    return {f"bank{bid}": mem[off:off + len(banks[f"bank{bid}"])]
            for bid, off in cfg.bank_offsets.items()}


def _signature(cfg: SimConfig, n_iters: int, n_cycles: int,
               batch: int) -> simcache.SimSignature:
    """The executable signature of a single-configuration launch."""
    return simcache.SimSignature(
        II=cfg.II, P=cfg.P, RF=cfg.RF, bits=cfg.bits, n_iters=n_iters,
        n_cycles=n_cycles, batch=batch, pass_words=_pass_words(cfg))


def simulate(cfg: SimConfig, banks: Dict[str, np.ndarray],
             invocations, n_iters: int) -> Dict[str, np.ndarray]:
    """Run the mapped kernel for every invocation and return final banks.

    banks: {"bank<i>": int array} initial memory images.
    invocations: list of {livein name: value} dicts (host outer loops).

    One image through ``_launch`` and ``simcache``, like every launch, but
    for exactly ``cfg.n_cycles(n_iters)`` cycles rather than the bucketed
    count ``simulate_batch`` pads to.  Callers that hold a config to its
    real schedule length rely on that: ``check.mutate._probe_dead`` calls
    a mutant dead only if it is execution-equivalent over the real cycles,
    and a mutant whose stores move past the last real cycle would commit
    them in a bucketed run's padded cycles.
    """
    with obs.span("morpher.sim.planes"):
        mem = _banks_to_mem(cfg, banks)
        if not len(invocations):
            # nothing to run: the final image is the initial image
            return _mem_to_banks(cfg, mem, banks)
        li_stack = np.stack([cfg.livein_array(inv) for inv in invocations])
        n_cycles = cfg.n_cycles(n_iters)
        sig = _signature(cfg, n_iters, n_cycles, batch=1)
        planes = _as_jnp(cfg)
    out = _launch(sig, planes, mem[None, :], li_stack, real_rows=1,
                  real_row_steps=n_cycles * len(invocations))
    return _mem_to_banks(cfg, out[0], banks)


def simulate_batch(cfg: SimConfig, banks_batch: List[Dict[str, np.ndarray]],
                   invocations, n_iters: int) -> List[Dict[str, np.ndarray]]:
    """Run the same mapped kernel over a batch of initial memory images.

    All images share one configuration and invocation schedule (the batch
    axis is seeds / test vectors, not kernels), so the whole batch is one
    batched XLA launch: per-element results are bit-identical to
    ``simulate`` on that element.  The executable comes from the process-
    wide shape-bucketed cache (``repro.core.simcache``): batch is rounded
    up to a power of two (padded images are simulated and dropped) and the
    cycle count to its bucket boundary (padded cycles are store-gated
    no-ops), so sweeps across many kernels and seed counts retrace XLA a
    handful of times instead of once per call.
    """
    B = len(banks_batch)
    if B == 0:
        return []
    with obs.span("morpher.sim.planes"):
        mem = np.stack([_banks_to_mem(cfg, banks) for banks in banks_batch])
        if not len(invocations):
            return [_mem_to_banks(cfg, mem[i], banks_batch[i])
                    for i in range(B)]
        li_stack = np.stack([cfg.livein_array(inv) for inv in invocations])
        real_cycles = cfg.n_cycles(n_iters)
        sig = _signature(cfg, n_iters, simcache.bucket_cycles(real_cycles),
                         batch=simcache.bucket_batch(B))
        if sig.batch > B:  # pad to the bucket; padded rows masked out below
            mem = np.concatenate(
                [mem, np.repeat(mem[-1:], sig.batch - B, axis=0)])
        planes = _as_jnp(cfg)
    out = _launch(sig, planes, mem, li_stack, real_rows=B,
                  real_row_steps=real_cycles * len(invocations) * B)
    return [_mem_to_banks(cfg, out[i], banks_batch[i]) for i in range(B)]


# ------------------------------------------------- multi-architecture batch
def stack_signature(cfg: SimConfig, n_iters: int,
                    n_invocations: int) -> Tuple[int, ...]:
    """The shape bucket a (config, schedule) pair simulates in.

    Configs agreeing on this tuple can be stacked into one multi-arch
    executable (``simulate_multi``): every element is a *static* shape
    input of the traced body — per-arch values (opcode planes, neighbour
    tables, bank offsets, live-in values) ride the batch axis as data.
    The cycle count enters bucketed, so near-miss schedule depths stack
    too (padded cycles are store-gated no-ops); the register-file width
    enters bucketed (``simcache.bucket_rf``), so fabrics differing only
    in RF provisioning stack too — each config's planes pad to the
    bucket with dead write ports, and its own reads never index past its
    real RF.
    """
    return (cfg.II, cfg.P, simcache.bucket_rf(cfg.RF), cfg.bits,
            max(1, cfg.LI), n_iters, n_invocations,
            simcache.bucket_cycles(cfg.n_cycles(n_iters)))


def _stack_planes(per: List[Dict[str, np.ndarray]],
                  reps: List[int]) -> Dict[str, np.ndarray]:
    """Stack per-config host planes into ``[B, II, ...]`` rows, repeating
    each config for its memory-image count.  Lane tables pad to the
    group-wide lane count with -1 (dead lanes); value planes promote to
    the group's common dtype — both value-preserving, so stacked rows
    decode exactly as their single-config originals."""
    width = {k: max(p[k].shape[1] for p in per)
             for k in ("store_lanes", "load_lanes")}
    out: Dict[str, np.ndarray] = {}
    for k in per[0]:
        arrs = []
        for p, rep in zip(per, reps):
            a = p[k]
            if k in width and a.shape[1] < width[k]:
                a = np.concatenate(
                    [a, np.full((a.shape[0], width[k] - a.shape[1]), -1,
                                dtype=a.dtype)], axis=1)
            arrs.append(np.repeat(a[None], rep, axis=0))
        dtype = np.result_type(*(a.dtype for a in arrs))
        out[k] = np.concatenate([a.astype(dtype, copy=False)
                                 for a in arrs], axis=0)
    return out


# stacked-plane device cache: the multi-arch analogue of the per-config
# ``_jnp_planes`` memo.  A search cohort is re-simulated (warm executable)
# many times — rung after rung, benchmark repeats — and restacking +
# re-uploading ~10 config planes per call would otherwise dominate the
# launch it saves.  Keyed by config identities (the cached tuple holds
# strong refs, so an id can never be recycled while its key is live);
# bounded FIFO keeps one search's worth of groups.
_STACK_PLANES_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_STACK_PLANES_MAX = 32


def _stacked_jnp_planes(cfgs: Tuple[SimConfig, ...],
                        reps: Tuple[int, ...], pad: int,
                        rf_pad: int) -> Dict:
    key = (tuple(id(c) for c in cfgs), reps, pad, rf_pad)
    hit = _STACK_PLANES_CACHE.get(key)
    if hit is not None:
        _STACK_PLANES_CACHE.move_to_end(key)
        return hit[1]
    planes = _stack_planes([_host_planes(c, rf_pad) for c in cfgs],
                           list(reps))
    if pad:  # pad to the batch bucket by repeating the last config row
        planes = {k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                  for k, v in planes.items()}
    jp = {k: jnp.asarray(v) for k, v in planes.items()}
    _STACK_PLANES_CACHE[key] = (cfgs, jp)
    while len(_STACK_PLANES_CACHE) > _STACK_PLANES_MAX:
        _STACK_PLANES_CACHE.popitem(last=False)
    return jp


def simulate_multi(items: Sequence[Tuple[SimConfig,
                                         List[Dict[str, np.ndarray]],
                                         List[Dict[str, int]]]],
                   n_iters: int) -> List[List[Dict[str, np.ndarray]]]:
    """Simulate many *configurations* in one XLA launch.

    ``items``: a list of ``(cfg, banks_batch, invocations)`` triples — all
    sharing one :func:`stack_signature` — e.g. the same kernel compiled
    onto many candidate fabrics of a design-space search, each with its
    own seed batch.  Config planes are stacked along the batch axis next
    to the memory images, so the whole group is a single executable
    launch; per (config, image) element the result is bit-identical to
    ``simulate_batch`` on that config alone (pinned by
    ``tests/test_multiarch_sim.py``).

    Memory rows pad to the group's widest image (each config addresses
    only its own ``total_words``; the shared scratch word sits at the
    padded row end), the batch rounds up to its power-of-two bucket, and
    every config's register file pads to the group's RF bucket
    (``simcache.bucket_rf``) with dead write ports, so signatures — and
    executables — are shared with other groups of the same shapes and
    across RF provisioning variants.  Returns one list of final-banks
    dicts per item, in item order.
    """
    items = [(cfg, list(bb), list(inv)) for cfg, bb, inv in items]
    out: List[List[Dict[str, np.ndarray]]] = [[] for _ in items]
    live = [i for i, (_, bb, _inv) in enumerate(items) if bb]
    if not live:
        return out
    sigs = sorted({stack_signature(items[i][0], n_iters, len(items[i][2]))
                   for i in live})
    if len(sigs) != 1:
        raise ValueError(
            f"simulate_multi: items span {len(sigs)} shape buckets "
            f"{sigs}; stack only configs sharing one stack_signature")
    II, P, RF, bits, LI, _, n_inv, n_cycles = sigs[0]
    if n_inv == 0:
        # nothing to run: final images are the initial images
        for i in live:
            cfg, bb, _ = items[i]
            out[i] = [_mem_to_banks(cfg, _banks_to_mem(cfg, b), b)
                      for b in bb]
        return out
    if len(live) == 1:
        # a group of one is the plain batched path (shares its executable
        # with every non-stacked caller)
        i = live[0]
        cfg, bb, inv = items[i]
        out[i] = simulate_batch(cfg, bb, inv, n_iters)
        return out

    with obs.span("morpher.sim.planes"):
        reps = [len(items[i][1]) for i in live]
        B = sum(reps)
        W = max(items[i][0].total_words for i in live)
        mem = np.zeros((B, W), dtype=np.int16 if bits == 16 else np.int32)
        row = 0
        for i in live:
            cfg, bb, _ = items[i]
            for b in bb:
                mem[row, :cfg.total_words] = _banks_to_mem(cfg, b)
                row += 1
        li = np.concatenate(
            [np.repeat(np.stack([items[i][0].livein_array(inv)
                                 for inv in items[i][2]])[:, None],
                       rep, axis=1)
             for i, rep in zip(live, reps)], axis=1)   # [n_inv,B,P,LI]
        sig = simcache.SimSignature(
            II=II, P=P, RF=RF, bits=bits, n_iters=n_iters,
            n_cycles=n_cycles, batch=simcache.bucket_rows(B), LI=LI,
            multi=True)
        pad = sig.batch - B
        if pad:  # pad to the bucket by repeating the last row everywhere
            mem = np.concatenate([mem, np.repeat(mem[-1:], pad, axis=0)])
            li = np.concatenate([li, np.repeat(li[:, -1:], pad, axis=1)],
                                axis=1)
        planes = _stacked_jnp_planes(tuple(items[i][0] for i in live),
                                     tuple(reps), pad, RF)
    real_row_steps = sum(items[i][0].n_cycles(n_iters) * n_inv * rep
                         for i, rep in zip(live, reps))
    res = _launch(sig, planes, mem, li, real_rows=B,
                  real_row_steps=real_row_steps)
    row = 0
    for i in live:
        cfg, bb, _ = items[i]
        out[i] = []
        for b in bb:
            out[i].append(_mem_to_banks(cfg, res[row], b))
            row += 1
    return out
