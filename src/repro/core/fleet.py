"""Batched fleet execution engine: N simulated processes, one dispatch.

The scalar machine (:mod:`machine`) interprets one process with a
``lax.switch`` over op handlers inside a ``lax.while_loop`` — ideal for a
single lane, terrible under ``jax.vmap``: batching a 40-way switch executes
*every* handler for *every* lane each step, and each handler carries the
full 256 KiB memory image through a select.  Measured on CPU that is ~14x
slower per aggregate step than just looping the scalar engine.

This module instead implements the step **natively batched**
(:func:`fleet_step`): one fetch gather per decode field, register reads as
``take_along_axis``, all scalar-register/ALU/branch semantics as masked
selects, and — the part that makes it fast — memory traffic merged into at
most two word gathers + two word scatters per step plus a static 34-word
sigframe window, with the unbounded syscall-I/O fill/sum loops hidden
behind a *batch-uniform* ``lax.cond`` (the predicate is a reduction over
lanes, so XLA keeps it a real branch instead of flattening it).

Execution is **chunked**: an inner ``lax.scan`` of K steps per
``lax.while_loop`` iteration amortises the all-halted condition K-fold;
finished lanes are masked to no-ops (every write in :func:`fleet_step` is
gated on the lane being live), so per-lane results are bit-identical to the
scalar engine for any K — tested exhaustively in
``tests/test_fleet_parity.py``.

Decode tables are deduplicated: lanes reference a table stack
``[G, CODE_WORDS]`` through an ``img_ids`` indirection, so a census running
the same program under many iteration counts or mechanisms only ships each
distinct image once.  Entry points donate the state buffers
(``donate_argnums``) and can optionally lane-partition the fleet across
devices via :mod:`repro.parallel.sharding`.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import costmodel as cm
from . import layout as L
from . import opspec
from .isa import Op
from .machine import (COST_TABLE, HALT_BADMEM, HALT_EXIT, HALT_FUEL,
                      HALT_KILL, HALT_SEGV, HALT_TRAP, RUNNING,
                      SIGFRAME_WORDS, DecodedImage, MachineState,
                      _SIGFRAME_IDX)
from repro.emul import engine as emul_engine
from repro.emul import state as emul_state

I64 = jnp.int64
I32 = jnp.int32

_MAX_IO_WORDS = 4096  # mirrors machine._MAX_IO_WORDS
_COUNTER_IDX = (L.COUNTER - L.DATA_BASE) // 8

DEFAULT_CHUNK = 8


# ---------------------------------------------------------------------------
# syscall tracing + policy carry (the device side of repro.trace)
# ---------------------------------------------------------------------------
#
# The carry rides NEXT TO the MachineState through the chunked scan, so a
# traced fleet's machine states stay bit-identical to an untraced run (the
# repro.trace parity suite enforces this).  Appends happen inside the step
# under the svc mask as one masked scatter behind a batch-uniform cond —
# no host sync, no per-event dispatch.  Host-side construction, decoding
# and strace-style rendering live in repro.trace.recorder / .policy.

# Record layout: one ring row per executed svc.
REC_WORDS = 8
REC_STEP, REC_PC, REC_NR, REC_X0, REC_X1, REC_X2, REC_RET, REC_VERDICT = \
    range(REC_WORDS)

# Policy table slots: one per modelled syscall, plus the catch-all UNKNOWN
# slot every other number (the sys_enosys fall-through) resolves to.  The
# slot numbering, verdict codes and syscall rows all live in the op-spec
# table (repro.core.opspec.SYSCALLS) — re-exported here for the long list
# of existing importers.
TRACE_SYS = opspec.TRACE_SYS
SLOT_UNKNOWN = opspec.SLOT_UNKNOWN
N_POLICY_SLOTS = opspec.N_POLICY_SLOTS

# Per-slot actions (seccomp-style); also the recorded verdict codes, with
# UNKNOWN marking an ALLOWed syscall that fell through to -ENOSYS.
POL_ALLOW, POL_DENY = opspec.POL_ALLOW, opspec.POL_DENY
POL_EMULATE, POL_KILL = opspec.POL_EMULATE, opspec.POL_KILL
VERDICT_UNKNOWN = opspec.VERDICT_UNKNOWN
N_VERDICTS = opspec.N_VERDICTS

DEFAULT_TRACE_CAP = 64


class TraceState(NamedTuple):
    """Per-lane syscall trace ring + policy tables, carried on-device.

    ``buf`` is double-buffered: two ``CAP``-row halves per lane.  Lane
    ``b`` appends into half ``hot[b]`` at row ``(count[b] - base[b]) %
    CAP`` — ``base`` is the lifetime count at the last half-flip, so a
    never-flipped carry (``hot == base == 0``) behaves exactly like the
    classic single ring: a full half overwrites oldest-first and
    ``count`` keeps the lifetime total so the host decoder knows how
    many records were dropped.  The streaming pipeline
    (:func:`run_fleet_stream`, :mod:`repro.trace.stream`) instead flips
    halves at span boundaries — one cheap [B] meta update, no buffer
    copy — and harvests the cold half off-device while the hot half
    keeps filling, which is what makes zero-drop tracing possible at a
    fixed CAP.

    The ``*_count`` verdict counters are the scheduler's feed
    (:mod:`repro.sched`): cheap [B] adds bumped under the svc mask, so
    per-tenant budget accounting harvests one small array per field
    instead of decoding every ring.  ``count`` doubles as the per-lane
    executed-svc total (every svc appends exactly one record).
    ``hist`` is the analytics feed: per-lane policy-slot x verdict
    totals bumped by the same masked scatter-add as the record append,
    so syscall histograms never require decoding a ring at all.
    """

    buf: jnp.ndarray         # int64[B, 2, CAP, REC_WORDS]: hot/cold halves
    count: jnp.ndarray       # int64[B]: records ever produced per lane
    hot: jnp.ndarray         # int64[B]: the half currently appended to
    base: jnp.ndarray        # int64[B]: lifetime count at the last flip
    hist: jnp.ndarray        # int64[B, N_POLICY_SLOTS, N_VERDICTS]
    pol_action: jnp.ndarray  # int32[B, N_POLICY_SLOTS]
    pol_arg: jnp.ndarray     # int64[B, N_POLICY_SLOTS]: errno / constant
    deny_count: jnp.ndarray  # int64[B]: DENY verdicts per lane
    emul_count: jnp.ndarray  # int64[B]: EMULATE verdicts per lane
    kill_count: jnp.ndarray  # int64[B]: KILL verdicts per lane (0 or 1)


# ---------------------------------------------------------------------------
# stacking helpers
# ---------------------------------------------------------------------------

def stack_images(imgs: Sequence[DecodedImage]) -> DecodedImage:
    """Stack decode tables along a new leading axis -> [G, CODE_WORDS]."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *imgs)


class FleetImages(NamedTuple):
    """Fleet-side decode tables: the seven small fields of ``DecodedImage``
    packed into one int64 word per instruction, so a fetch is two gathers
    (packed + imm) instead of eight.  Field layout (low to high):
    op:6  rd:5  rn:5  rm:5  sh:6  cond:4  sf:1."""

    packed: jnp.ndarray  # int64[G, CODE_WORDS]
    imm: jnp.ndarray     # int64[G, CODE_WORDS]


def pack_images(imgs) -> FleetImages:
    """DecodedImage stack [G, CODE_WORDS] (or list of scalar images) ->
    :class:`FleetImages`."""
    if isinstance(imgs, FleetImages):
        return imgs
    if not isinstance(imgs, DecodedImage):
        imgs = stack_images(list(imgs))
    f = [x.astype(I64) for x in
         (imgs.op, imgs.rd, imgs.rn, imgs.rm, imgs.sh, imgs.cond, imgs.sf)]
    packed = (f[0] | (f[1] << 6) | (f[2] << 11) | (f[3] << 16)
              | (f[4] << 22) | (f[5] << 28) | (f[6] << 32))
    return FleetImages(packed=packed, imm=imgs.imm)


def stack_states(states: Sequence[MachineState]) -> MachineState:
    """Stack machine states along a new leading lane axis -> [B, ...]."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def unstack_state(states: MachineState, lane: int) -> MachineState:
    """Extract one lane of a batched state (host-side convenience)."""
    return jax.tree_util.tree_map(lambda x: x[lane], states)


def flat_planes(s: MachineState) -> MachineState:
    """The per-lane word planes (``mem``, ``k_ino_data``) as one flat
    ``[B * W]`` plane each, lane ``b``'s words at ``b * W``: the layout
    :func:`exec_lanes` addresses.  Drivers flatten once before their loop
    and restore with :func:`lane_planes` once after it."""
    return s._replace(mem=s.mem.reshape(-1),
                      k_ino_data=s.k_ino_data.reshape(-1))


def lane_planes(s: MachineState) -> MachineState:
    """Inverse of :func:`flat_planes`: back to ``[B, W]`` per plane."""
    B = s.pc.shape[0]
    return s._replace(mem=s.mem.reshape(B, L.MEM_WORDS),
                      k_ino_data=s.k_ino_data.reshape(
                          B, L.MAX_INODES * L.FILE_WORDS))


# ---------------------------------------------------------------------------
# the batched step
# ---------------------------------------------------------------------------

def _mem_ok_v(addr):
    return (addr >= L.DATA_BASE) & (addr < L.MEM_LIMIT) & ((addr & 7) == 0)


def _widx_v(addr):
    return jnp.clip((addr - L.DATA_BASE) >> 3, 0, L.MEM_WORDS - 1)


def _cond_holds_v(nzcv, cond):
    # One 16-word bitmask pick (opspec.COND_MASK) instead of materialising
    # 14 predicate trees: a tiny-constant gather exactly like COST_TABLE[op]
    # (NOT a [B, 16] take_along_axis, which CPU XLA wraps in parallel-task
    # calls — the reason the previous select-chain existed).  The mask LUT
    # is the op-spec table's single copy of the cond constants, shared by
    # the scalar, XLA and Pallas executors.
    return opspec.cond_holds(nzcv, cond)


def _fetch(img: FleetImages, ids: jnp.ndarray, pc0: jnp.ndarray):
    """Fetch + decode for every lane: two gathers (packed fields + imm),
    then bit-unpack.  Returns the per-lane field tuple ``(op, rd, rn, rm,
    sh, cond, sf, imm)`` that :func:`exec_lanes` consumes."""
    with jax.named_scope("fetch"):
        ok_fetch = (pc0 >= 0) & (pc0 < L.CODE_LIMIT) & ((pc0 & 3) == 0)
        idx = jnp.clip(pc0 >> 2, 0, L.CODE_WORDS - 1)
        w = img.packed[ids, idx]
        imm = img.imm[ids, idx]
        op = jnp.where(ok_fetch, (w & 63).astype(I32), I32(int(Op.NULLPAGE)))
        rd = ((w >> 6) & 31).astype(I32)
        rn = ((w >> 11) & 31).astype(I32)
        rm = ((w >> 16) & 31).astype(I32)
        sh = ((w >> 22) & 63).astype(I32)
        cond = ((w >> 28) & 15).astype(I32)
        sf = ((w >> 32) & 1).astype(I32)
    return op, rd, rn, rm, sh, cond, sf, imm


def exec_lanes(fields, s: MachineState, tr: Optional[TraceState],
               act: Optional[jnp.ndarray] = None,
               tbl: Optional["opspec.SpecTables"] = None):
    """Execute one decoded instruction per lane — the one executor body
    every engine shares, generated from the op-spec table
    (:mod:`repro.core.opspec`): per-op masks, ALU value rows, memory
    effects, halt transitions and the syscall branches are all derived
    from the spec columns, never hand-listed here.

    ``fields`` is :func:`_fetch`'s tuple (any decode source works: the
    packed fleet tables, or the scalar SoA tables in
    :func:`repro.core.machine.step`).  ``s`` holds its word planes flat
    (:func:`flat_planes`), and so does the returned state: every caller
    flattens once around its loop, so no step reshapes a plane.  ``act``
    overrides the live-lane mask — the scalar engine forces all-true to
    reproduce the legacy unconditional step; fleet drivers leave the
    default halted/fuel gate.

    ``tr is None`` keeps the graph unchanged from the untraced engine;
    with a trace carry the syscall ring + policy tables ride along and
    machine-state results stay bit-identical under all-ALLOW policy.

    ``tbl`` overrides the spec-column bundle (default: the module-level
    :data:`opspec.TABLES` constants) — the Pallas kernel passes the
    columns it received as operands, since kernels cannot capture array
    constants.

    Its parts run under ``jax.named_scope`` (``fetch`` for the op-class
    decode, ``regs``, ``mem``, ``alu``, ``syscall``, ``emul``,
    ``io_mover``, ``trace_ring``), so a device trace can charge each
    operation to one; scopes change op metadata only, never instruction
    names.
    """
    traced = tr is not None
    if tbl is None:
        tbl = opspec.TABLES
    op, rd, rn, rm, sh, cond, sf, imm = fields
    B = s.pc.shape[0]
    lanes = jnp.arange(B)
    regs0, sp0, pc0, nzcv0, mem_flat = s.regs, s.sp, s.pc, s.nzcv, s.mem

    if act is None:
        act = (s.halted == RUNNING) & (s.icount < s.fuel)
    sh64 = sh.astype(I64)

    # -- spec-column gathers: the per-lane op classes ------------------------
    # Tiny-constant gathers (like COST_TABLE[op]) followed by equality
    # masks; every mask below is one class compare, not a hand-written
    # per-op union, so a new opcode is a table row away.
    with jax.named_scope("fetch"):
        aluc = tbl.ALU[op]
        flagc = tbl.FLAGS[op]
        memc = tbl.MEM[op]
        pcc = tbl.PC[op]

        def c(tbl, v):
            return (tbl == v) & act

        m_svc = c(pcc, opspec.P_SVC)
        m_null = tbl.SEGV[op] & act
        m_hlt = tbl.EXIT[op] & act
        dlv = c(pcc, opspec.P_TRAP)
        ld_single = c(memc, opspec.M_LOAD)
        st_single = c(memc, opspec.M_STORE)
        ld_pair = c(memc, opspec.M_LOAD_P)
        st_pair = c(memc, opspec.M_STORE_P)
        byte_op = c(memc, opspec.M_LOAD_BYTE) | c(memc, opspec.M_STORE_BYTE)

    # -- register reads (reg 31 is XZR for _rr, SP for _rsp) -----------------
    with jax.named_scope("regs"):
        zero = jnp.zeros((B,), I64)
        ra = jnp.clip(imm, 0, 31).astype(I32)  # madd packs ra into imm
        ridx = jnp.stack([jnp.minimum(rn, 30), jnp.minimum(rm, 30),
                          jnp.minimum(rd, 30), jnp.minimum(ra, 30)],
                         axis=1).astype(I32)
        rvals = jnp.take_along_axis(regs0, ridx, axis=1)  # one gather, [B, 4]
        rn_raw, rm_raw, rd_raw, ra_raw = (rvals[:, 0], rvals[:, 1],
                                          rvals[:, 2], rvals[:, 3])
        rn_rr = jnp.where(rn == 31, zero, rn_raw)
        rn_rsp = jnp.where(rn == 31, sp0, rn_raw)
        rm_rr = jnp.where(rm == 31, zero, rm_raw)
        rd_rr = jnp.where(rd == 31, zero, rd_raw)
        ra_rr = jnp.where(ra == 31, zero, ra_raw)
        x0, x1, x2, x8 = regs0[:, 0], regs0[:, 1], regs0[:, 2], regs0[:, 8]

    # -- memory addressing: <=2 word gathers, <=2 word scatters per step -----
    with jax.named_scope("mem"):
        post_index = tbl.ADDR_POST[op] & act
        addr_a = jnp.where(post_index, rn_rsp, rn_rsp + imm)
        eff1 = jnp.where(byte_op, addr_a & ~jnp.int64(7), addr_a)
        ok1 = jnp.where(byte_op,
                        (addr_a >= L.DATA_BASE) & (addr_a < L.MEM_LIMIT),
                        _mem_ok_v(eff1))
        addr2 = addr_a + 8
        ok2 = _mem_ok_v(addr2)
        g1, g2 = _widx_v(eff1), _widx_v(addr2)
        # Flat 1-D addressing: rank-1 gathers/scatters take XLA's fast
        # in-place path on CPU.  The plane arrives flat: on CPU a
        # [B, MEM_WORDS] <-> [B*MEM_WORDS] view is a bitcast, but on a TPU
        # [B, W] is tiled T(8,128) and [B*W] T(1024), so each view is a full
        # relayout of the plane; callers flatten once per loop instead.
        lane_base = (lanes * L.MEM_WORDS).astype(I64)
        # The word reads live behind a (vacuously true while any lane runs)
        # batch-uniform cond.  Expressed as bare gathers, XLA's CPU pipeline
        # wraps them in parallel-task `call`s whose buffer use its copy
        # insertion cannot see through, and the whole [B, MEM_WORDS] carry gets
        # defensively copied every step (~10x slowdown at fleet width 40);
        # conditional branch reads keep the carry aliasable.
        v1, v2 = lax.cond(
            jnp.any(act),
            lambda: (mem_flat[lane_base + g1], mem_flat[lane_base + g2]),
            lambda: (jnp.zeros((B,), I64), jnp.zeros((B,), I64)))

        byte_shift = (addr_a & 7) * 8
        byte_val = (v1 >> byte_shift) & 0xFF
        strb_word = ((v1 & ~(jnp.int64(0xFF) << byte_shift))
                     | ((rd_rr & 0xFF) << byte_shift))

        ld1 = jnp.where(ok1, v1, zero)   # ldri/ldrpost/ldp/ldppost first word
        ld2 = jnp.where(ok2, v2, zero)   # ldp/ldppost second word

    # -- ALU / mov / load value for the primary register write --------------
    # One select row per ALU class column (opspec.ALU); class masks are
    # disjoint by construction, so row order cannot change results.
    with jax.named_scope("alu"):
        piece = imm << sh64
        movk_v = (rd_rr & ~(jnp.int64(0xFFFF) << sh64)) | piece
        mov_v = jnp.select([c(aluc, opspec.A_MOVZ), c(aluc, opspec.A_MOVN),
                            c(aluc, opspec.A_MOVK)],
                           [piece, ~piece, movk_v], zero)
        mov_v = jnp.where(sf == 1, mov_v, mov_v & jnp.int64(0xFFFFFFFF))

        slotA_val = jnp.select(
            [c(aluc, opspec.A_MOVZ) | c(aluc, opspec.A_MOVN)
             | c(aluc, opspec.A_MOVK),
             c(aluc, opspec.A_ADRP),
             c(aluc, opspec.A_ADR),
             c(aluc, opspec.A_ADD_I),
             c(aluc, opspec.A_SUB_I),
             c(aluc, opspec.A_ADD_R),
             c(aluc, opspec.A_SUB_R),
             c(aluc, opspec.A_ORR),
             c(aluc, opspec.A_AND),
             c(aluc, opspec.A_EOR),
             c(aluc, opspec.A_MADD),
             c(aluc, opspec.A_LSL),
             c(aluc, opspec.A_LOAD),
             c(aluc, opspec.A_LOAD_B),
             c(aluc, opspec.A_LINK)],
            [mov_v,
             (pc0 & ~jnp.int64(0xFFF)) + imm,
             pc0 + imm,
             rn_rsp + imm,
             rn_rsp - imm,
             rn_rr + rm_rr,
             rn_rr - rm_rr,
             rn_rr | rm_rr,
             rn_rr & rm_rr,
             rn_rr ^ rm_rr,
             rn_rr * rm_rr + ra_rr,
             rn_rr << sh64,
             ld1,
             byte_val,
             pc0 + 4],
            zero)
        slotA_en = (aluc != opspec.A_NONE) & act
        slotA_idx = jnp.where(tbl.WB_LR[op], I32(30), rd)
        slotA_sp = tbl.WB_SP[op] & act  # _wsp ops: rd == 31 targets SP

        # -- flags -----------------------------------------------------------
        f_imm = flagc == opspec.F_SUBS_I
        subs = (flagc != opspec.F_NONE) & act
        fa = jnp.where(f_imm, rn_rsp, rn_rr)
        fb = jnp.where(f_imm, imm, rm_rr)
        res = fa - fb
        flag_n = (res < 0).astype(I64) * 8
        flag_z = (res == 0).astype(I64) * 4
        flag_c = (fa.astype(jnp.uint64)
                  >= fb.astype(jnp.uint64)).astype(I64) * 2
        flag_v = (((fa ^ fb) & (fa ^ res)) < 0).astype(I64)
        nzcv = jnp.where(subs, flag_n + flag_z + flag_c + flag_v, nzcv0)

    # -- syscalls (scalar effects; the I/O word loop is under a cond below) --
    with jax.named_scope("syscall"):
        nr = x8
        in_pt = s.ptrace != 0
        en = s.k_enabled != 0  # per-lane guest-kernel gate (0 = legacy stubs)
        if traced:
            # Seccomp-style gate: resolve nr to a per-lane policy action, then
            # only ALLOW lanes reach the sys_* branches.  The lookup is a chain
            # of [B] selects over the 8 table columns rather than a gather —
            # take_along_axis here gets wrapped in CPU parallel-task calls
            # (the same pipeline issue as the word reads above) and costs ~10%
            # census throughput; the select chain fuses into the step for ~3%.
            any_svc = jnp.any(m_svc)
            action = tr.pol_action[:, SLOT_UNKNOWN]
            pol_arg = tr.pol_arg[:, SLOT_UNKNOWN]
            pol_slot = jnp.full((B,), SLOT_UNKNOWN, I64)
            emulable = jnp.zeros((B,), bool)
            for i, spec in enumerate(opspec.SYSCALLS):
                hit = nr == spec.nr
                action = jnp.where(hit, tr.pol_action[:, i], action)
                pol_arg = jnp.where(hit, tr.pol_arg[:, i], pol_arg)
                pol_slot = jnp.where(hit, jnp.int64(i), pol_slot)
                if spec.emul:
                    emulable = emulable | hit
            pol_deny = m_svc & (action == POL_DENY)
            pol_emul = m_svc & (action == POL_EMULATE)
            pol_kill = m_svc & (action == POL_KILL)
            # An EMULATE verdict on a guest-kernel-backed nr routes into the
            # emulation branch (real fd-table service); on anything else it
            # returns the policy constant, as it always did.  Both record the
            # POL_EMULATE verdict and feed emul_count.
            emul_route = pol_emul & emulable & en
            pol_emul_const = pol_emul & ~(emulable & en)
            svc_exec = m_svc & ((action == POL_ALLOW) | emul_route)
        else:
            svc_exec = m_svc

        # Per-kind syscall masks generated from the spec's syscall rows; a new
        # constant-returning syscall (K_CONST) is one table row, not a mask +
        # a select row + a scalar branch.  Guest-kernel kinds split on the
        # per-lane ``en`` gate: enabled lanes take the fd-table path
        # (repro.emul), disabled lanes reproduce the legacy semantics exactly
        # (openat/close keep their constant stubs, the rest fall through to
        # -ENOSYS).
        false_b = jnp.zeros((B,), bool)
        sys_read = sys_write = sys_getpid = sys_exit = sys_sigret = false_b
        sys_open = sys_close = sys_lseek = sys_dup = false_b
        sys_fstat = sys_pipe = sys_rand = sys_ioctl = false_b
        sys_const, known = false_b, false_b
        const_val = zero
        _EMUL_ONLY = {opspec.K_LSEEK: "lseek", opspec.K_DUP: "dup",
                      opspec.K_FSTAT: "fstat", opspec.K_PIPE2: "pipe",
                      opspec.K_GETRANDOM: "rand", opspec.K_IOCTL: "ioctl"}
        emul_only_masks = {"lseek": sys_lseek, "dup": sys_dup,
                           "fstat": sys_fstat, "pipe": sys_pipe,
                           "rand": sys_rand, "ioctl": sys_ioctl}
        for spec in opspec.SYSCALLS:
            hit = svc_exec & (nr == spec.nr)
            if spec.kind == opspec.K_IO_READ:
                sys_read = sys_read | hit
                known = known | hit
            elif spec.kind == opspec.K_IO_WRITE:
                sys_write = sys_write | hit
                known = known | hit
            elif spec.kind == opspec.K_GETPID:
                sys_getpid = sys_getpid | hit
                known = known | hit
            elif spec.kind == opspec.K_EXIT:
                sys_exit = sys_exit | hit
                known = known | hit
            elif spec.kind == opspec.K_SIGRETURN:
                sys_sigret = sys_sigret | hit
                known = known | hit
            elif spec.kind in (opspec.K_OPENAT, opspec.K_CLOSE):
                # enabled: real fd-table open/close; disabled: the historical
                # constant stub (openat -> 3, close -> 0)
                m = hit & en
                if spec.kind == opspec.K_OPENAT:
                    sys_open = sys_open | m
                else:
                    sys_close = sys_close | m
                sys_const = sys_const | (hit & ~en)
                const_val = jnp.where(hit & ~en, jnp.int64(spec.const),
                                      const_val)
                known = known | hit
            elif spec.kind in _EMUL_ONLY:
                name = _EMUL_ONLY[spec.kind]
                emul_only_masks[name] = emul_only_masks[name] | (hit & en)
                # disabled lanes: -ENOSYS, as before
                known = known | (hit & en)
            else:  # K_CONST
                sys_const = sys_const | hit
                const_val = jnp.where(hit, jnp.int64(spec.const), const_val)
                known = known | hit
        sys_lseek, sys_dup, sys_fstat = (emul_only_masks["lseek"],
                                         emul_only_masks["dup"],
                                         emul_only_masks["fstat"])
        sys_pipe, sys_rand, sys_ioctl = (emul_only_masks["pipe"],
                                         emul_only_masks["rand"],
                                         emul_only_masks["ioctl"])
        sys_enosys = svc_exec & ~known

        io_buf, io_n = x1, x2
        io_k = jnp.clip(io_n >> 3, 0, _MAX_IO_WORDS)
        io_ok = (_mem_ok_v(io_buf) & (io_buf + io_n <= L.MEM_LIMIT)
                 & (io_n >= 0) & ((io_n & 7) == 0))
        io_start = _widx_v(io_buf)

        # First path word for openat lanes — the one-word namespace key.
        # Read from the pre-store memory (like v1/v2 above) behind a
        # batch-uniform cond so the carry stays aliasable.
        path_w = lax.cond(
            jnp.any(sys_open),
            lambda: mem_flat[lane_base + _widx_v(x1)],
            lambda: jnp.zeros((B,), I64))

    # -- guest-kernel service (control plane) -------------------------------
    # The whole fd-table step hides behind one batch-uniform cond: steps
    # where no lane executes an emulated operation (and no enabled lane is
    # inside read/write, whose stream-vs-file routing the service decides)
    # pay a single jnp.any.  The neutral branch is bit-identical to the
    # service on such a batch.
    with jax.named_scope("emul"):
        emul_op = (sys_open | sys_close | sys_lseek | sys_dup | sys_fstat
                   | sys_pipe | sys_rand | sys_ioctl)
        any_kern = jnp.any(emul_op | ((sys_read | sys_write) & en))
        eff = lax.cond(
            any_kern,
            lambda: emul_engine.service(
                s, en=en, x0=x0, x1=x1, x2=x2, path_w=path_w,
                io_ok=io_ok, io_n=io_n,
                sys_open=sys_open, sys_close=sys_close, sys_lseek=sys_lseek,
                sys_dup=sys_dup, sys_fstat=sys_fstat, sys_pipe=sys_pipe,
                sys_rand=sys_rand, sys_ioctl=sys_ioctl,
                sys_read=sys_read, sys_write=sys_write),
            lambda: emul_engine.neutral(s, sys_read, sys_write))
        io_do = (eff.rd_stream | eff.wr_stream) & io_ok

    with jax.named_scope("syscall"):
        virt = in_pt & (s.virt_getpid != 0)
        svc_x0 = jnp.select(
            [eff.rd_stream | eff.wr_stream,
             eff.is_ret,
             sys_getpid,
             sys_const,
             sys_enosys],
            [jnp.where(io_ok, io_n, jnp.int64(-14)),
             eff.ret,
             jnp.where(virt, jnp.int64(L.VIRT_PID), s.pid),
             const_val,
             jnp.full((B,), -38, I64)],
            zero)
        svc_x0_en = svc_exec & ~(sys_exit | sys_sigret)
        if traced:
            # DENY returns -errno, non-routable EMULATE returns the policy
            # constant; both skip the kernel branch and fall through to pc+4.
            # Routed EMULATE lanes already hold their emulated return in
            # svc_x0 (eff.ret).
            svc_x0 = jnp.select([pol_deny, pol_emul_const],
                                [-pol_arg, pol_arg], svc_x0)
            svc_x0_en = svc_x0_en | pol_deny | pol_emul_const

        # -- signal delivery / sigreturn (static 34-word frame window) -------
        # ``dlv`` is the P_TRAP pc-class mask from the spec gathers above; the
        # signal number rides the SIGNO column (garbage on non-trap lanes, but
        # only consumed under can_sig).
        can_sig = dlv & (s.sig_handler != 0) & (s.in_signal == 0)
        trap_fail = dlv & ~can_sig
        signo = tbl.SIGNO[op]
        frame_out = jnp.concatenate(
            [regs0, sp0[:, None], pc0[:, None], nzcv0[:, None]], axis=1)
        frame_start = lane_base + _SIGFRAME_IDX

        # Sigreturn frame read, from the pre-store plane like the word
        # reads: a sigreturn lane performs no store/push/I-O in the same
        # step, so its row is the scalar engine's pre-handler read.  Rare op
        # => batch-uniform cond; every consumer is masked by sys_sigret.  A
        # read of the final plane, after every writer, instead makes a TPU's
        # copy insertion copy the whole plane before the store scatter
        # every step.
        frame_in = lax.cond(
            jnp.any(sys_sigret),
            lambda: mem_flat[frame_start[:, None]
                             + jnp.arange(SIGFRAME_WORDS, dtype=I64)],
            lambda: jnp.zeros((B, SIGFRAME_WORDS), I64))

    # -- memory writes -------------------------------------------------------
    # One merged scatter for both store slots.  Disabled / faulting writes
    # are parked at an out-of-bounds index and dropped (the scalar engine
    # writes the old value back — same result, no masking gather needed).
    # When a pair store clip-aliases (base in range, base+8 not), slot 2 is
    # dropped, exactly matching the scalar sequential-store semantics; when
    # both slots land, their indices are distinct by construction.
    with jax.named_scope("mem"):
        oob = jnp.int64(L.MEM_WORDS * B)
        # distinct OOB slots per entry
        park = oob + jnp.arange(2 * B, dtype=I64)
        st_byte = c(memc, opspec.M_STORE_BYTE)
        st1_en = (st_single | st_pair | st_byte) & ok1
        st2_en = st_pair & ok2
        st_idx = jnp.concatenate([jnp.where(st1_en, lane_base + g1, park[:B]),
                                  jnp.where(st2_en, lane_base + g2, park[B:])])
        st_val = jnp.concatenate([jnp.where(byte_op, strb_word, rd_rr), rm_rr])
        # indices are genuinely unique: live pair slots differ by construction,
        # parked slots each get their own out-of-bounds id (dropped)
        mem = mem_flat.at[st_idx].set(st_val, mode="drop",
                                      unique_indices=True)

        # Sigframe push: rare (only brk/illegal on a lane with a handler),
        # but lanes running one program trap on the same step.  A TPU
        # scatter costs the same per index whether it lands or is parked
        # (B * 34 indices: 16.6 ms on a v5e at 4,096 lanes, against 5.7 ms
        # for this loop with 1,024 lanes pushing), so the push walks only
        # the pushing lanes, one 34-word dynamic_update_slice each, in a
        # bare loop like the io mover's: zero trips without a push.
        n_push = jnp.sum(can_sig)
        push_order = lax.cond(
            n_push > 0,
            lambda: jnp.argsort(~can_sig, stable=True).astype(lanes.dtype),
            lambda: lanes)

        def push_frame(k, mm):
            b = push_order[k]
            return lax.dynamic_update_slice(mm, frame_out[b],
                                            (frame_start[b],))

        mem = lax.fori_loop(jnp.int64(0), n_push, push_frame, mem)

    # fstat statbuf / pipe2 fd-pair result words: <= 6 words fleet-wide,
    # parked out-of-bounds + dropped when masked, behind a batch-uniform
    # cond.
    with jax.named_scope("emul"):
        def emul_result_words(mm):
            return mm.at[eff.scat_idx].set(eff.scat_val, mode="drop",
                                           unique_indices=True)

        mem = lax.cond(jnp.any(eff.scat_do), emul_result_words,
                       lambda mm: mm, mem)

    # Syscall I/O fill/sum.  Typically only a lane or two is inside
    # read/write on any given step, so iterate over the io lanes (a bare
    # while_loop: zero iterations on no-io steps, no cond wrapper — nesting
    # the loop under a lax.cond makes XLA copy the whole memory defensively)
    # and stream each lane's payload through contiguous 512-word dynamic
    # slices of its own region.  Cost is proportional to the words actually
    # transferred, not fleet-width x window (a [B, W] masked scatter per
    # event throttled an 80-lane mixed census to 0.5x scalar).
    with jax.named_scope("io_mover"):
        W_IO = 512
        _woff = jnp.arange(W_IO, dtype=I64)

        def io_lane_body(carry):
            mf, sums, rem = carry
            b = jnp.argmax(rem)               # next io lane
            k_b = io_k[b]
            start_b = lane_base[b] + io_start[b]
            rd_b = sys_read[b]
            off_b = s.in_off[b]

            def win_body(c, inner):
                mf2, acc = inner
                base = start_b + c * W_IO     # dynamic_slice clamps at the end
                # conditional read (vacuously true: c < nwin inside the loop):
                # as at step level, a bare read whose value outlives the update
                # below would make XLA copy the whole flat memory every window;
                # branch-wrapped reads keep it aliasable
                cur = lax.cond(
                    c < nwin,
                    lambda: lax.dynamic_slice(mf2, (base,), (W_IO,)),
                    lambda: jnp.zeros((W_IO,), I64))
                pos = jnp.clip(base, 0, B * L.MEM_WORDS - W_IO) + _woff
                within = (pos >= start_b + c * W_IO) & (pos < start_b + k_b)
                fill = off_b + (pos - start_b) * 8
                new = jnp.where(within & rd_b, fill, cur)
                mf2 = lax.dynamic_update_slice(mf2, new, (base,))
                acc = acc + jnp.sum(jnp.where(within & ~rd_b, cur,
                                              jnp.int64(0)))
                return mf2, acc

            nwin = (k_b + W_IO - 1) // W_IO
            mf, acc = lax.fori_loop(jnp.int64(0), nwin, win_body,
                                    (mf, jnp.int64(0)))
            sums = sums.at[b].set(acc)
            rem = rem.at[b].set(False)
            return mf, sums, rem

        mem, io_sum, _ = lax.while_loop(
            lambda c: jnp.any(c[2]), io_lane_body, (mem, zero, io_do))

    # Guest-kernel bulk data (file/pipe/proc reads+writes, getrandom
    # fills) over the (memory, inode-data) flat planes: a loop over the
    # moving lanes only, behind a cond — no work when no lane moves words.
    with jax.named_scope("emul"):
        proc_flat = lax.cond(
            jnp.any(eff.src_is_proc),
            lambda: emul_engine.proc_rows(s).reshape(-1),
            lambda: jnp.zeros((B * L.PROC_WORDS,), I64))
        mem, k_ino_data = emul_engine.run_data_loop(
            mem, eff.kern.ino_data, proc_flat, eff)

    # -- register writes (slot order mirrors the scalar handler order) ------
    with jax.named_scope("regs"):
        col = jnp.arange(31)[None, :]

        def apply_slot(regs, en, idxv, val, sp, sp_ok):
            hit = en[:, None] & (idxv[:, None] == col)  # idx 31 never matches
            regs = jnp.where(hit, val[:, None], regs)
            sp = jnp.where(en & sp_ok & (idxv == 31), val, sp)
            return regs, sp

        regs, sp = apply_slot(regs0, slotA_en, slotA_idx, slotA_val, sp0,
                              slotA_sp)
        regs, sp = apply_slot(regs, ld_pair, rm, ld2, sp,
                              jnp.zeros((B,), bool))
        wb = tbl.WB_BASE[op] & act
        regs, sp = apply_slot(regs, wb, rn, rn_rsp + imm, sp,
                              jnp.ones((B,), bool))

        regs = regs.at[:, 0].set(jnp.where(svc_x0_en, svc_x0, regs[:, 0]))
        regs = regs.at[:, 0].set(jnp.where(can_sig, signo, regs[:, 0]))
        regs = regs.at[:, 1].set(jnp.where(can_sig,
                                           jnp.int64(L.SIGFRAME), regs[:, 1]))
        sp = jnp.where(can_sig, jnp.int64(L.SIGSTACK_TOP), sp)

        regs = jnp.where(sys_sigret[:, None], frame_in[:, :31], regs)
        sp = jnp.where(sys_sigret, frame_in[:, 31], sp)
        nzcv = jnp.where(sys_sigret, frame_in[:, 33], nzcv)

    # -- program counter -----------------------------------------------------
    br_target = pc0 + imm
    pc4 = pc0 + 4
    taken_bc = opspec.cond_holds(nzcv0, cond, tbl.COND_MASK)
    svc_pc = jnp.where(sys_exit, pc0,
                       jnp.where(sys_sigret, frame_in[:, 32] + 4, pc4))
    if traced:
        svc_pc = jnp.where(pol_kill, pc0, svc_pc)  # KILL parks like exit
    pc_new = jnp.select(
        [c(pcc, opspec.P_REL),
         c(pcc, opspec.P_IND),
         c(pcc, opspec.P_CBZ),
         c(pcc, opspec.P_CBNZ),
         c(pcc, opspec.P_BCOND),
         c(pcc, opspec.P_STAY),
         dlv,
         m_svc],
        [br_target,
         rn_rr,
         jnp.where(rd_rr == 0, br_target, pc4),
         jnp.where(rd_rr != 0, br_target, pc4),
         jnp.where(taken_bc, br_target, pc4),
         pc0,
         jnp.where(can_sig, s.sig_handler, pc0),
         svc_pc],
        pc4)
    pc = jnp.where(act, pc_new, pc0)

    # -- faults / halts ------------------------------------------------------
    bad_single = (ld_single | st_single) & ~ok1
    bad_pair = (ld_pair | st_pair) & ~(ok1 & ok2)
    bad_byte = byte_op & ~ok1
    mem_bad = bad_single | bad_pair | bad_byte

    halted = s.halted
    halted = jnp.where(m_null, jnp.int64(HALT_SEGV), halted)
    halted = jnp.where(mem_bad, jnp.int64(HALT_BADMEM), halted)
    halted = jnp.where(m_hlt | sys_exit, jnp.int64(HALT_EXIT), halted)
    halted = jnp.where(trap_fail, jnp.int64(HALT_TRAP), halted)
    exit_code = jnp.where(m_hlt | sys_exit, x0, s.exit_code)
    fault_pc = jnp.where(m_null | mem_bad | trap_fail, pc0, s.fault_pc)
    if traced:
        halted = jnp.where(pol_kill, jnp.int64(HALT_KILL), halted)
        fault_pc = jnp.where(pol_kill, pc0, fault_pc)

    # -- bookkeeping ---------------------------------------------------------
    cycles = s.cycles + jnp.where(act, tbl.COST_TABLE[op], zero)
    cycles = cycles + jnp.where(m_svc, jnp.int64(cm.KERNEL_CROSS), zero)
    cycles = cycles + jnp.where(m_svc & in_pt,
                                jnp.int64(2 * cm.PTRACE_STOP), zero)
    cycles = cycles + jnp.where(sys_read | sys_write,
                                io_n // cm.IO_BYTES_PER_CYCLE, zero)
    cycles = cycles + jnp.where(can_sig,
                                jnp.int64(cm.SIGNAL_DELIVERY), zero)
    icount = s.icount + jnp.where(act, jnp.int64(1), zero)
    hook_count = s.hook_count + jnp.where(m_svc & in_pt, jnp.int64(1), zero)
    # Stream effects follow the service routing: on legacy lanes
    # rd_stream/wr_stream equal the raw masks, so these reduce to the
    # historical expressions; on enabled lanes only FD_RSTREAM reads /
    # FD_WSINK writes touch the modelled stream counters.
    in_off = s.in_off + jnp.where(eff.rd_stream & io_ok, io_n, zero)
    out_count = s.out_count + jnp.where(eff.wr_stream & io_ok, io_n, zero)
    out_sum = s.out_sum + jnp.where(eff.wr_stream & io_ok, io_sum, zero)
    in_signal = jnp.where(can_sig, jnp.int64(1),
                          jnp.where(sys_sigret, jnp.int64(0), s.in_signal))
    enosys_count = s.enosys_count + jnp.where(sys_enosys, jnp.int64(1), zero)
    emul_served = s.emul_served + jnp.where(eff.served, jnp.int64(1), zero)

    # -- trace record append (traced path only) ------------------------------
    with jax.named_scope("trace_ring"):
        if traced:
            cap = tr.buf.shape[2]

            # Svc steps are rare (one in tens of steps), so the whole record
            # computation + 8-word row scatter + histogram bump hide behind the
            # same batch-uniform cond as the policy lookup (like the sigframe
            # push); parked out-of-bounds indices drop the non-svc lanes.
            def append(operand):
                buf, hist = operand
                ret = jnp.select(
                    [pol_deny, pol_emul_const, pol_kill, sys_exit, sys_sigret],
                    [-pol_arg, pol_arg, zero, x0, frame_in[:, 0]],
                    svc_x0)  # routed EMULATE lanes: svc_x0 == the emulated ret
                verdict = jnp.select(
                    [pol_deny, pol_emul, pol_kill, sys_enosys],
                    [jnp.full((B,), POL_DENY, I64),
                     jnp.full((B,), POL_EMULATE, I64),
                     jnp.full((B,), POL_KILL, I64),
                     jnp.full((B,), VERDICT_UNKNOWN, I64)],
                    zero)  # POL_ALLOW
                flat = buf.reshape(B * 2 * cap, REC_WORDS)
                pos = (lanes * (2 * cap)).astype(I64) + tr.hot * cap \
                    + (tr.count - tr.base) % cap
                idx = jnp.where(m_svc, pos,
                                jnp.int64(B * 2 * cap) + lanes.astype(I64))
                rows = jnp.stack([s.icount, pc0, nr, x0, x1, x2, ret, verdict],
                                 axis=1)
                buf = flat.at[idx].set(rows, mode="drop",
                                       unique_indices=True).reshape(B, 2, cap,
                                                                    REC_WORDS)
                hflat = hist.reshape(B * N_POLICY_SLOTS * N_VERDICTS)
                hpos = lanes.astype(I64) * (N_POLICY_SLOTS * N_VERDICTS) \
                    + pol_slot * N_VERDICTS + verdict
                hidx = jnp.where(m_svc, hpos,
                                 jnp.int64(B * N_POLICY_SLOTS * N_VERDICTS)
                                 + lanes.astype(I64))
                hist = hflat.at[hidx].add(jnp.int64(1), mode="drop",
                                          unique_indices=True).reshape(
                                              B, N_POLICY_SLOTS, N_VERDICTS)
                return buf, hist

            buf, hist = lax.cond(any_svc, append, lambda op: op,
                                 (tr.buf, tr.hist))
            one = jnp.int64(1)
            tr = tr._replace(
                buf=buf, hist=hist,
                count=tr.count + jnp.where(m_svc, one, zero),
                # the scheduler's budget feed: plain masked adds, cheap enough
                # to live outside the any_svc cond
                deny_count=tr.deny_count + jnp.where(pol_deny, one, zero),
                emul_count=tr.emul_count + jnp.where(pol_emul, one, zero),
                kill_count=tr.kill_count + jnp.where(pol_kill, one, zero))

    kern = eff.kern
    return s._replace(
        regs=regs, sp=sp, pc=pc, nzcv=nzcv, mem=mem, cycles=cycles,
        icount=icount, halted=halted, exit_code=exit_code, fault_pc=fault_pc,
        in_signal=in_signal, hook_count=hook_count, in_off=in_off,
        out_count=out_count, out_sum=out_sum, enosys_count=enosys_count,
        emul_served=emul_served,
        k_rng=kern.rng, k_fd_ofd=kern.fd_ofd, k_ofd_kind=kern.ofd_kind,
        k_ofd_ino=kern.ofd_ino, k_ofd_off=kern.ofd_off,
        k_ofd_flags=kern.ofd_flags, k_ofd_ref=kern.ofd_ref,
        k_ino_kind=kern.ino_kind, k_ino_name=kern.ino_name,
        k_ino_size=kern.ino_size, k_ino_data=k_ino_data), tr


def _step_core(img: FleetImages, ids: jnp.ndarray, s: MachineState,
               tr: Optional[TraceState],
               tbl: Optional["opspec.SpecTables"] = None):
    """One masked step for every lane: fetch/decode, then the shared
    spec-generated executor body (``tbl`` as in :func:`exec_lanes`).
    Takes and returns the word planes flat (:func:`flat_planes`)."""
    return exec_lanes(_fetch(img, ids, s.pc), s, tr, tbl=tbl)


def fleet_step(img: FleetImages, ids: jnp.ndarray,
               s: MachineState) -> MachineState:
    """One masked step for every lane.  ``img`` leaves are [G, CODE_WORDS],
    ``ids`` is the per-lane image index [B], state leaves are [B, ...].

    Bit-identical per lane to :func:`machine.step` applied to live lanes and
    the identity on halted/out-of-fuel lanes.
    """
    return lane_planes(_step_core(img, ids, flat_planes(s), None)[0])


def fleet_step_traced(img: FleetImages, ids: jnp.ndarray, s: MachineState,
                      tr: TraceState):
    """:func:`fleet_step` plus the syscall ring/policy carry: appends one
    record per executed svc and applies the per-lane policy tables.  Under
    the default all-ALLOW policy the returned machine state is bit-identical
    to the untraced step's (enforced by the repro.trace parity suite)."""
    s, tr = _step_core(img, ids, flat_planes(s), tr)
    return lane_planes(s), tr


# ---------------------------------------------------------------------------
# the fleet driver: chunked while_loop
# ---------------------------------------------------------------------------

def _alive(s: MachineState):
    return (s.halted == RUNNING) & (s.icount < s.fuel)


def _patch_fuel(s: MachineState) -> MachineState:
    return s._replace(halted=jnp.where(
        (s.halted == RUNNING) & (s.icount >= s.fuel),
        jnp.int64(HALT_FUEL), s.halted))


# Every driver carries the word planes flat through its whole loop
# (flat_planes before, lane_planes after): one reshape each way per
# dispatch, none per step.

def _run_fleet(img: FleetImages, ids: jnp.ndarray, s: MachineState,
               chunk: int) -> MachineState:
    def scan_body(carry, _):
        return _step_core(img, ids, carry, None)[0], None

    def body(ss):
        ss, _ = lax.scan(scan_body, ss, None, length=chunk)
        return ss

    s = lax.while_loop(lambda ss: jnp.any(_alive(ss)), body, flat_planes(s))
    return _patch_fuel(lane_planes(s))


def _run_fleet_traced(img: FleetImages, ids: jnp.ndarray, s: MachineState,
                      tr: TraceState, chunk: int):
    def scan_body(carry, _):
        ss, tt = carry
        return _step_core(img, ids, ss, tt), None

    def body(c):
        c, _ = lax.scan(scan_body, c, None, length=chunk)
        return c

    s, tr = lax.while_loop(lambda c: jnp.any(_alive(c[0])), body,
                           (flat_planes(s), tr))
    return _patch_fuel(lane_planes(s)), tr


@functools.lru_cache(maxsize=None)
def _jitted_run(chunk: int):
    return jax.jit(functools.partial(_run_fleet, chunk=chunk),
                   donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _jitted_run_traced(chunk: int):
    return jax.jit(functools.partial(_run_fleet_traced, chunk=chunk),
                   donate_argnums=(2, 3))


# ---------------------------------------------------------------------------
# engine selection: the XLA chunk-scan vs the Pallas megastep kernel
# ---------------------------------------------------------------------------
#
# Both engines run the same spec-generated executor body (exec_lanes), so
# results are bit-identical by construction — the choice is purely how the
# chunk loop is dispatched: "xla" scans _step_core with the full carry
# re-materialised per step; "pallas" fuses the whole chunk into one
# kernels.megastep dispatch with the carry resident in refs (interpret
# mode on CPU, where it lowers back to the same XLA ops).  The kernel does
# not lower for a TPU: its carry operands are int64, and Mosaic refuses
# their lane blocks, so a TPU backend refuses the engine up front.

ENGINES = ("xla", "pallas")


def _check_engine(engine: str, *, shard: bool = False) -> str:
    if engine not in ENGINES:
        raise ValueError(
            f"unknown fleet engine {engine!r}: expected one of {ENGINES}")
    if engine == "pallas" and jax.default_backend() == "tpu":
        raise ValueError(
            "engine='pallas' does not lower for a TPU: the megastep kernel's "
            "carry operands are int64, and Mosaic refuses their lane blocks "
            "('The Pallas TPU lowering currently requires that rank 1 block "
            "shapes ... is a multiple of the tiling size (128 = 128 * "
            "(32 // 32))'; a 128-lane block then fails the 64-bit tiling "
            "128 * (32 // 64) = 0 with 'integer modulo by zero').  Use "
            "engine='xla'; the 32-bit kernel rewrite is ROADMAP A2")
    if engine == "pallas" and shard:
        raise ValueError(
            "engine='pallas' does not compose with shard=True "
            "(the megastep kernel is single-device); use engine='xla' "
            "for sharded fleets")
    return engine


def _engine_run(engine: str, chunk: int, traced: bool):
    """The run-to-halt driver for ``engine`` — identical call shape,
    donation and HALT_FUEL contract either way."""
    if engine == "pallas":
        from repro.kernels.megastep import ops as mops  # lazy: kernel layer
        return (mops.jitted_run_traced(chunk) if traced
                else mops.jitted_run(chunk))
    return _jitted_run_traced(chunk) if traced else _jitted_run(chunk)


def _engine_span(engine: str, chunk: int, span: int, traced: bool):
    """The bounded-span driver for ``engine`` (no HALT_FUEL patch)."""
    if engine == "pallas":
        from repro.kernels.megastep import ops as mops  # lazy: kernel layer
        return (mops.jitted_span_traced(chunk, span) if traced
                else mops.jitted_span(chunk, span))
    return (_jitted_span_traced(chunk, span) if traced
            else _jitted_span(chunk, span))


# ---------------------------------------------------------------------------
# bounded-step generations (continuous-batching building block)
# ---------------------------------------------------------------------------

def _run_fleet_span(img: FleetImages, ids: jnp.ndarray, s: MachineState,
                    chunk: int, span: int) -> MachineState:
    """At most ``span`` chunks of ``chunk`` masked steps — early exit when
    every lane halts.  Unlike :func:`_run_fleet` this does NOT patch
    ``HALT_FUEL``: lanes that ran out of fuel stay ``RUNNING`` (masked), so
    a fleet can keep stepping across generations and the server patches the
    halt code only when it harvests the lane."""
    def scan_body(carry, _):
        return _step_core(img, ids, carry, None)[0], None

    def body(c):
        ss, k = c
        ss, _ = lax.scan(scan_body, ss, None, length=chunk)
        return ss, k + 1

    def cond(c):
        ss, k = c
        return jnp.any(_alive(ss)) & (k < span)

    s, _ = lax.while_loop(cond, body, (flat_planes(s), jnp.int32(0)))
    return lane_planes(s)


def _run_fleet_span_traced(img: FleetImages, ids: jnp.ndarray,
                           s: MachineState, tr: TraceState,
                           chunk: int, span: int):
    def scan_body(carry, _):
        ss, tt = carry
        return _step_core(img, ids, ss, tt), None

    def body(c):
        (ss, tt), k = c
        (ss, tt), _ = lax.scan(scan_body, (ss, tt), None, length=chunk)
        return (ss, tt), k + 1

    def cond(c):
        (ss, _), k = c
        return jnp.any(_alive(ss)) & (k < span)

    (s, tr), _ = lax.while_loop(cond, body,
                                ((flat_planes(s), tr), jnp.int32(0)))
    return lane_planes(s), tr


@functools.lru_cache(maxsize=None)
def _jitted_span(chunk: int, span: int):
    return jax.jit(functools.partial(_run_fleet_span, chunk=chunk, span=span),
                   donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _jitted_span_traced(chunk: int, span: int):
    return jax.jit(functools.partial(_run_fleet_span_traced, chunk=chunk,
                                     span=span),
                   donate_argnums=(2, 3))


def run_fleet_span(imgs: FleetImages, states: MachineState, img_ids,
                   *, steps: int, chunk: int = DEFAULT_CHUNK,
                   trace: Optional[TraceState] = None,
                   engine: str = "xla"):
    """One bounded generation: up to ``steps`` masked steps (rounded up to a
    whole number of ``chunk``-sized scans) in ONE device dispatch.

    Halted / out-of-fuel lanes are frozen (bit-identical no-ops), so driving
    a lane through any sequence of generations gives exactly the state the
    unbounded :func:`run_fleet` would.  State buffers are donated; the
    caller must drop its reference and keep the returned state.

    With ``trace`` (a :class:`TraceState`, also donated) every executed svc
    appends a ring record and the per-lane policy tables gate the syscall
    branches; returns ``(states, trace)`` instead of just ``states``.

    ``engine`` picks the chunk dispatcher — ``"xla"`` (the scan) or
    ``"pallas"`` (the fused megastep kernel); results are bit-identical.
    """
    _check_engine(engine)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    span = -(-steps // chunk)
    imgs = pack_images(imgs)
    img_ids = jnp.asarray(img_ids, I32)
    run_span = _engine_span(engine, int(chunk), int(span), trace is not None)
    if trace is None:
        return run_span(imgs, img_ids, states)
    return run_span(imgs, img_ids, states, trace)


def finish_halt_codes(halted: np.ndarray, icount: np.ndarray,
                      fuel: np.ndarray) -> np.ndarray:
    """Host-side HALT_FUEL patch for harvested lanes (what ``_run_fleet``
    does on-device at the end of an unbounded run)."""
    return np.where((halted == RUNNING) & (icount >= fuel),
                    np.int64(HALT_FUEL), halted)


def _admit_lanes(s: MachineState, idx: jnp.ndarray, regs: jnp.ndarray,
                 pc: jnp.ndarray, fuel: jnp.ndarray, sig_handler: jnp.ndarray,
                 ptrace: jnp.ndarray, virt_getpid: jnp.ndarray,
                 k_enabled: jnp.ndarray) -> MachineState:
    """Scatter fresh per-lane initial states into slots ``idx`` in place.

    ``idx`` is padded with out-of-range entries (>= B) for unused admission
    slots — those scatter with ``mode="drop"``.  A row admitted here is
    bit-identical to ``runtime.initial_state``: zero memory/flags/counters,
    ``sp = STACK_TOP``, ``pid = PID``, the given entry/fuel/mechanism
    registers, and a fresh preopened guest-kernel state.
    """
    k = idx.shape[0]
    zeros = jnp.zeros((k,), I64)
    kern = emul_state.fresh_kern(k)

    def put(leaf, val):
        return leaf.at[idx].set(val, mode="drop")

    return s._replace(
        regs=put(s.regs, regs),
        sp=put(s.sp, jnp.full((k,), L.STACK_TOP, I64)),
        pc=put(s.pc, pc),
        nzcv=put(s.nzcv, zeros),
        mem=put(s.mem, jnp.zeros((k, L.MEM_WORDS), I64)),
        cycles=put(s.cycles, zeros),
        icount=put(s.icount, zeros),
        fuel=put(s.fuel, fuel),
        halted=put(s.halted, zeros),
        exit_code=put(s.exit_code, zeros),
        fault_pc=put(s.fault_pc, zeros),
        sig_handler=put(s.sig_handler, sig_handler),
        in_signal=put(s.in_signal, zeros),
        ptrace=put(s.ptrace, ptrace),
        virt_getpid=put(s.virt_getpid, virt_getpid),
        hook_count=put(s.hook_count, zeros),
        pid=put(s.pid, jnp.full((k,), L.PID, I64)),
        in_off=put(s.in_off, zeros),
        out_count=put(s.out_count, zeros),
        out_sum=put(s.out_sum, zeros),
        enosys_count=put(s.enosys_count, zeros),
        emul_served=put(s.emul_served, zeros),
        # fresh guest kernel: preopened fds 0..3, empty fs, the admitted
        # lane's own enable gate (from its HookConfig via initial_state)
        **{f: put(getattr(s, f),
                  kern[f] if f != "k_enabled" else k_enabled)
           for f in emul_state.KERN_FIELDS},
    )


_jitted_admit = jax.jit(_admit_lanes, donate_argnums=(0,))


def _admit_lanes_traced(s: MachineState, tr: TraceState, idx: jnp.ndarray,
                        regs, pc, fuel, sig_handler, ptrace, virt_getpid,
                        k_enabled, pol_action, pol_arg):
    """The traced admission: reset each admitted lane's ring (row + count)
    and install its per-request policy tables, same donated-scatter shape as
    the machine-state admission."""
    k = idx.shape[0]
    cap = tr.buf.shape[2]
    zk = jnp.zeros((k,), I64)
    tr = tr._replace(
        buf=tr.buf.at[idx].set(jnp.zeros((k, 2, cap, REC_WORDS), I64),
                               mode="drop"),
        count=tr.count.at[idx].set(zk, mode="drop"),
        hot=tr.hot.at[idx].set(zk, mode="drop"),
        base=tr.base.at[idx].set(zk, mode="drop"),
        hist=tr.hist.at[idx].set(
            jnp.zeros((k, N_POLICY_SLOTS, N_VERDICTS), I64), mode="drop"),
        pol_action=tr.pol_action.at[idx].set(pol_action, mode="drop"),
        pol_arg=tr.pol_arg.at[idx].set(pol_arg, mode="drop"),
        deny_count=tr.deny_count.at[idx].set(zk, mode="drop"),
        emul_count=tr.emul_count.at[idx].set(zk, mode="drop"),
        kill_count=tr.kill_count.at[idx].set(zk, mode="drop"),
    )
    return _admit_lanes(s, idx, regs, pc, fuel, sig_handler, ptrace,
                        virt_getpid, k_enabled), tr


_jitted_admit_traced = jax.jit(_admit_lanes_traced, donate_argnums=(0, 1))


def admit_lanes(states: MachineState, slots: Sequence[int],
                lane_states: Sequence[MachineState], *,
                trace: Optional[TraceState] = None,
                policies: Optional[Sequence] = None):
    """Admit fresh scalar initial states into lanes ``slots`` of a batched
    state, in place (donated scatter; one dispatch for the whole batch of
    admissions, one compilation per admission-batch width).

    ``lane_states`` must be *initial* states (``runtime.initial_state``):
    only their entry pc / fuel / mechanism flags / seeded registers are
    carried — everything else is reset exactly as ``initial_state`` does,
    which avoids shipping each lane's 256 KiB zero memory image.

    With ``trace`` the ring rows of the admitted lanes are recycled (count
    reset, records zeroed) and ``policies`` — one ``(action_row, arg_row)``
    pair per slot, e.g. from :func:`repro.trace.policy.compile_policy`, or
    ``None`` entries for all-ALLOW — is scattered into the policy tables;
    returns ``(states, trace)``.
    """
    assert len(slots) == len(lane_states) and len(slots) > 0
    idx = jnp.asarray(np.asarray(slots, np.int64))
    regs = jnp.stack([ls.regs for ls in lane_states])
    pack = lambda f: jnp.stack([getattr(ls, f) for ls in lane_states])
    if trace is None:
        assert policies is None, "policies require a trace carry"
        return _jitted_admit(states, idx, regs, pack("pc"), pack("fuel"),
                             pack("sig_handler"), pack("ptrace"),
                             pack("virt_getpid"), pack("k_enabled"))
    if policies is None:
        policies = [None] * len(slots)
    assert len(policies) == len(slots)
    pa = np.full((len(slots), N_POLICY_SLOTS), POL_ALLOW, np.int32)
    pg = np.zeros((len(slots), N_POLICY_SLOTS), np.int64)
    for i, pol in enumerate(policies):
        if pol is not None:
            pa[i], pg[i] = pol
    return _jitted_admit_traced(states, trace, idx, regs, pack("pc"),
                                pack("fuel"), pack("sig_handler"),
                                pack("ptrace"), pack("virt_getpid"),
                                pack("k_enabled"),
                                jnp.asarray(pa), jnp.asarray(pg))


def _set_image_row(packed, imm, row, new_packed, new_imm):
    return packed.at[row].set(new_packed), imm.at[row].set(new_imm)


_jitted_set_image_row = jax.jit(_set_image_row, donate_argnums=(0, 1))


def set_image_row(imgs: FleetImages, row: int,
                  new: DecodedImage) -> FleetImages:
    """Write one decode table into row ``row`` of a packed image stack, in
    place (both table buffers are donated) — incremental image admission
    without touching the other rows or triggering any recompilation (the
    stack shape is unchanged)."""
    one = pack_images(stack_images([new]))
    packed, imm = _jitted_set_image_row(
        imgs.packed, imgs.imm, jnp.int32(row), one.packed[0], one.imm[0])
    return FleetImages(packed=packed, imm=imm)


def _update_policy_rows(tr: TraceState, idx: jnp.ndarray,
                        pol_action: jnp.ndarray,
                        pol_arg: jnp.ndarray) -> TraceState:
    return tr._replace(
        pol_action=tr.pol_action.at[idx].set(pol_action, mode="drop"),
        pol_arg=tr.pol_arg.at[idx].set(pol_arg, mode="drop"))


_jitted_update_policy_rows = jax.jit(_update_policy_rows, donate_argnums=(0,))


def update_policy_rows(trace: TraceState, lanes: Sequence[int],
                       rows: Sequence) -> TraceState:
    """Swap the policy-table rows of *running* lanes in place, between
    spans — one donated masked scatter over the two policy leaves (rings,
    counters and machine states are untouched, so every other lane is
    bit-identical afterwards).  This is how an operator tightens a
    tenant's policy mid-flight without evicting its lanes
    (:meth:`repro.serve.fleet_server.FleetServer.update_policy`).

    ``lanes`` are physical lane indices (out-of-range entries drop, so
    callers may pad for a compile-once width); ``rows`` is one compiled
    ``(action_row, arg_row)`` pair per lane — ``None`` entries fall back
    to all-ALLOW.
    """
    assert len(lanes) == len(rows) and len(lanes) > 0
    pa = np.full((len(lanes), N_POLICY_SLOTS), POL_ALLOW, np.int32)
    pg = np.zeros((len(lanes), N_POLICY_SLOTS), np.int64)
    for i, r in enumerate(rows):
        if r is not None:
            pa[i], pg[i] = r
    return _jitted_update_policy_rows(
        trace, jnp.asarray(np.asarray(lanes, np.int64)),
        jnp.asarray(pa), jnp.asarray(pg))


def _restore_lanes(s: MachineState, idx: jnp.ndarray,
                   lanes: MachineState) -> MachineState:
    put = lambda leaf, val: leaf.at[idx].set(val, mode="drop")
    return jax.tree_util.tree_map(put, s, lanes)


_jitted_restore = jax.jit(_restore_lanes, donate_argnums=(0,))


def _restore_lanes_traced(s: MachineState, tr: TraceState, idx: jnp.ndarray,
                          lanes: MachineState, lane_tr: TraceState):
    put = lambda leaf, val: leaf.at[idx].set(val, mode="drop")
    return (jax.tree_util.tree_map(put, s, lanes),
            jax.tree_util.tree_map(put, tr, lane_tr))


_jitted_restore_traced = jax.jit(_restore_lanes_traced, donate_argnums=(0, 1))


def restore_lanes(states: MachineState, slots: Sequence[int],
                  lane_states: Sequence[MachineState], *,
                  trace: Optional[TraceState] = None,
                  lane_traces: Optional[Sequence[TraceState]] = None):
    """Scatter *checkpointed* lanes back into slots ``slots``, in place.

    The re-admission half of scheduler preemption
    (:mod:`repro.sched.scheduler`): unlike :func:`admit_lanes`, which
    rebuilds an initial state, the WHOLE per-lane tree is shipped — the
    [MEM_WORDS] memory image, registers, counters, and (when traced) the
    ring + policy tables + verdict counters — so a preempted lane resumes
    exactly where its checkpoint (one :func:`unstack_state` at harvest
    time) left off and its final published state stays bit-identical to an
    uninterrupted run.  ``slots`` entries >= B drop (padding), matching
    the admission scatter's compile-once convention.
    """
    assert len(slots) == len(lane_states) and len(slots) > 0
    idx = jnp.asarray(np.asarray(slots, np.int64))
    stacked = stack_states(lane_states)
    if trace is None:
        assert lane_traces is None, "lane_traces require a trace carry"
        return _jitted_restore(states, idx, stacked)
    assert lane_traces is not None and len(lane_traces) == len(slots)
    stacked_tr = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                        *lane_traces)
    return _jitted_restore_traced(states, trace, idx, stacked, stacked_tr)


def unstack_trace(trace: TraceState, lane: int) -> TraceState:
    """Extract one lane of a trace carry (the checkpoint counterpart of
    :func:`unstack_state`)."""
    return jax.tree_util.tree_map(lambda x: x[lane], trace)


def run_fleet(imgs, states, img_ids=None, *, chunk: int = DEFAULT_CHUNK,
              shard: bool = False, trace: Optional[TraceState] = None,
              engine: str = "xla"):
    """Run every lane to halt (or out of fuel) in one device dispatch.

    ``imgs``: a ``DecodedImage`` with leaves [G, CODE_WORDS] (or a list of
    scalar images, which is stacked).  ``states``: a ``MachineState`` with
    leaves [B, ...] (or a list of scalar states).  ``img_ids`` maps lanes to
    image rows; defaults to the identity (then G must equal B).

    ``chunk`` is the inner ``lax.scan`` length: loop-condition evaluation
    happens once per ``chunk`` steps.  Results are invariant to ``chunk``
    (only dispatch count changes).  ``shard=True`` lane-partitions the fleet
    across available devices, whose count must divide the lane count.

    With ``trace`` (a :class:`TraceState`, donated like the states) the run
    records every executed svc into the per-lane rings and applies the
    per-lane policy tables; returns ``(states, trace)``.  Machine states
    under the default all-ALLOW policy are bit-identical to an untraced run.

    ``engine="pallas"`` dispatches each chunk as one fused megastep kernel
    (:mod:`repro.kernels.megastep`) instead of the XLA scan; results are
    bit-identical (shared spec-generated executor body).  Pallas does not
    compose with ``shard=True``.
    """
    _check_engine(engine, shard=shard)
    imgs = pack_images(imgs)
    if not isinstance(states, MachineState):  # list/tuple of scalar states
        states = stack_states(states)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    n_lanes = int(states.pc.shape[0])
    if img_ids is None:
        if int(imgs.packed.shape[0]) != n_lanes:
            raise ValueError("img_ids required when #images != #lanes")
        img_ids = jnp.arange(n_lanes, dtype=I32)
    else:
        img_ids = jnp.asarray(img_ids, I32)

    if shard:
        from repro.parallel.sharding import shard_fleet
        if trace is None:
            imgs, img_ids, states = shard_fleet(imgs, img_ids, states)
        else:
            imgs, img_ids, states, trace = shard_fleet(
                imgs, img_ids, states, trace=trace)

    if trace is None:
        out = _engine_run(engine, int(chunk), False)(imgs, img_ids, states)
        return jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    out, tr = _engine_run(engine, int(chunk), True)(imgs, img_ids, states,
                                                    trace)
    out = jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)
    tr = jax.tree_util.tree_map(lambda x: x.block_until_ready(), tr)
    return out, tr


# ---------------------------------------------------------------------------
# streaming trace harvest: half-flips + overlapped cold-half readback
# ---------------------------------------------------------------------------
#
# The fixed ring drops oldest-first once a lane logs more than CAP records
# between harvests — on the 400-lane census that is ~47% of all records
# (BENCH_trace/v1).  The streaming pipeline bounds the un-harvested window
# instead: at span boundaries the driver flips every lane's hot half (one
# [B] meta update, the 2xCAP buffer itself is never copied on-device) and
# gathers the now-cold half into a fresh device buffer whose device->host
# copy overlaps the next span's dispatch.  As long as a span runs at most
# CAP steps per lane (worst case one svc per step), a half can never wrap
# between flips, so every record reaches the host: zero drops at fixed
# device memory.  Host-side decoding / ordering / sinks live in
# repro.trace.stream.

def _flip_halves(buf, hot, count):
    B = hot.shape[0]
    cold = buf[jnp.arange(B), hot]
    # count + 0: the new base must be a FRESH buffer — several entry points
    # donate the whole trace carry, and donating one shared buffer through
    # two leaves (base aliasing count) is an XLA error.
    return cold, jnp.int64(1) - hot, count + jnp.int64(0)


_jitted_flip_halves = jax.jit(_flip_halves)


def flip_trace(trace: TraceState):
    """Flip every lane's hot half and gather the cold half for harvest.

    Returns ``(trace', cold, counts, bases)``: the updated carry (``hot``
    toggled, ``base`` advanced to the current lifetime count; ``buf``
    untouched — stale cold rows are simply overwritten on the next pass),
    the cold halves as a device array ``int64[B, CAP, REC_WORDS]`` whose
    host conversion the caller should defer until after dispatching the
    next span (that is the overlap), and host copies of the pre-flip
    ``count`` / ``base`` — lane ``b``'s cold half holds records with
    lifetime sequence numbers ``[bases[b], counts[b])`` (oldest-first from
    row 0 when it did not wrap).
    """
    counts = np.asarray(trace.count)
    bases = np.asarray(trace.base)
    cold, new_hot, new_base = _jitted_flip_halves(trace.buf, trace.hot,
                                                  trace.count)
    return trace._replace(hot=new_hot, base=new_base), cold, counts, bases


def stream_interval(cap: int, chunk: int) -> int:
    """The widest flip interval (in steps) that still guarantees zero
    drops when chunk boundaries permit it: the largest multiple of
    ``chunk`` that is <= ``cap`` (worst case one record per step fills
    exactly one half between flips).  When ``chunk > cap`` a flip cannot
    land inside a chunk, so the interval degrades to one chunk — drops
    are then *possible* for svc-every-step lanes and are detected and
    counted by the sink, never silent."""
    if chunk >= cap:
        return int(chunk)
    return (cap // chunk) * chunk


def run_fleet_stream(imgs, states, img_ids=None, *,
                     chunk: int = DEFAULT_CHUNK,
                     trace: TraceState,
                     stream,
                     interval: Optional[int] = None,
                     keys: Optional[Sequence] = None,
                     engine: str = "xla"):
    """:func:`run_fleet` with streaming trace harvest: run every lane to
    halt in bounded spans, flipping ring halves at each span boundary and
    pushing the cold halves into ``stream`` (a
    :class:`repro.trace.stream.TraceStream`).  Machine states are
    bit-identical to the untraced/plain-traced run; the stream receives
    every record (zero drops) whenever ``interval <= cap``
    (:func:`stream_interval`, the default).

    The cold-half device->host copy of span *k* is converted on the host
    while span *k+1* executes on the device, so streaming costs one small
    gather + meta update per span, not a synchronous drain.

    ``keys`` names each lane in the stream (default: the lane index).
    Returns ``(states, trace)``; harvested records live in ``stream``.
    ``engine`` as in :func:`run_fleet` (bit-identical either way).
    """
    _check_engine(engine)
    imgs = pack_images(imgs)
    if not isinstance(states, MachineState):
        states = stack_states(states)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    n_lanes = int(states.pc.shape[0])
    if img_ids is None:
        if int(imgs.packed.shape[0]) != n_lanes:
            raise ValueError("img_ids required when #images != #lanes")
        img_ids = jnp.arange(n_lanes, dtype=I32)
    else:
        img_ids = jnp.asarray(img_ids, I32)
    cap = int(trace.buf.shape[2])
    interval = stream_interval(cap, chunk) if interval is None else \
        int(interval)
    if interval < 1:
        raise ValueError(f"interval must be >= 1, got {interval}")
    span = -(-interval // chunk)
    run_span = _engine_span(engine, int(chunk), int(span), True)
    if keys is None:
        keys = list(range(n_lanes))

    cur_s, cur_t = states, trace
    pending = None
    while True:
        cur_s, cur_t = run_span(imgs, img_ids, cur_s, cur_t)
        if pending is not None:
            # decode the PREVIOUS span's cold halves while the device runs
            # this span — np.asarray here only waits on the old gather
            stream.push_block(*pending)
            pending = None
        halted = np.asarray(cur_s.halted)
        icount = np.asarray(cur_s.icount)
        fuel = np.asarray(cur_s.fuel)
        alive = (halted == RUNNING) & (icount < fuel)
        cur_t, cold, counts, bases = flip_trace(cur_t)
        pending = (keys, cold, counts, bases)
        if not alive.any():
            break
    stream.push_block(*pending)
    cur_s = cur_s._replace(
        halted=jnp.asarray(finish_halt_codes(halted, icount, fuel)))
    return cur_s, cur_t


# ---------------------------------------------------------------------------
# live-lane compaction: bucketed re-dispatch over a precompiled ladder
# ---------------------------------------------------------------------------
#
# A fixed-width fleet burns full step compute on halted lanes: the census
# runs every lane to the longest lane's step count, so a tail-heavy grid
# spends most of its dispatched lane-steps masked to no-ops.  Because every
# lane's trajectory is independent of which other lanes share the batch
# (each write in _step_core is gated on the lane itself), the fleet can be
# *compacted* at chunk boundaries — still-live lanes gathered into a dense
# prefix by one donated permutation — and re-dispatched at a narrower
# power-of-two bucket width from a precompiled ladder, without changing any
# lane's results.  The inverse permutation is tracked host-side so the
# assembled output is bit-identical and lane-ordered versus run_fleet.

DEFAULT_MIN_BUCKET = 8


def compact_ladder(n_lanes: int, min_bucket: int = DEFAULT_MIN_BUCKET, *,
                   divisor: int = 1) -> List[int]:
    """Descending bucket widths: the full fleet width, then every power of
    two below it down to ``min_bucket``.  Each rung is one compiled
    executable; the ladder is the whole set a compacted run can visit, so
    XLA never compiles mid-run once the ladder is warm
    (:func:`precompile_ladder`).

    ``divisor`` builds per-shard ladders: rungs that are not divisible are
    dropped, so a lane-partitioned fleet keeps an equal per-device slice at
    every rung (see :func:`repro.parallel.sharding.shard_fleet`).
    """
    if n_lanes < 1:
        raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
    min_bucket = max(1, int(min_bucket), int(divisor))
    rungs = [int(n_lanes)]
    w = (1 << max(0, int(n_lanes) - 1).bit_length()) >> 1
    while w >= min_bucket:
        if w < n_lanes and w % divisor == 0:
            rungs.append(w)
        w >>= 1
    return rungs


def choose_bucket(ladder: Sequence[int], n_live: int, *,
                  cur: Optional[int] = None,
                  hysteresis: float = 0.0) -> int:
    """The occupancy-chosen rung: the smallest ladder width that holds
    ``n_live`` lanes.  With ``hysteresis`` h, a *shrink* below ``cur`` is
    only taken when the live count also clears ``rung * (1 - h)`` — a
    margin that keeps a pool from oscillating between rungs when lanes
    halt and admissions re-expand near a boundary."""
    asc = sorted({int(w) for w in ladder})
    need = max(1, int(n_live))
    target = next((w for w in asc if w >= need), asc[-1])
    if cur is not None and hysteresis > 0.0:
        while target < int(cur) and need > target * (1.0 - hysteresis):
            target = next((w for w in asc if w > target), int(cur))
    return target


def make_halted_states(n: int) -> MachineState:
    """A batched all-halted fleet state: every lane parked on ``HALT_EXIT``
    with zero fuel, so any run/span entry point returns without stepping.
    The ladder-precompile dummy and the grow-padding of a compacted pool."""
    z = lambda: jnp.zeros((n,), I64)   # fresh buffer per field: several
    # entry points donate the whole state, and donating one shared buffer
    # through two leaves is an XLA error
    return MachineState(
        regs=jnp.zeros((n, 31), I64),
        sp=jnp.full((n,), L.STACK_TOP, I64),
        pc=z(), nzcv=z(), mem=jnp.zeros((n, L.MEM_WORDS), I64),
        cycles=z(), icount=z(), fuel=z(),
        halted=jnp.full((n,), HALT_EXIT, I64),
        exit_code=z(), fault_pc=z(), sig_handler=z(), in_signal=z(),
        ptrace=z(), virt_getpid=z(), hook_count=z(),
        pid=jnp.full((n,), L.PID, I64),
        in_off=z(), out_count=z(), out_sum=z(), enosys_count=z(),
        emul_served=z(),
        **emul_state.fresh_kern(n))  # fresh buffers, same donation rule


def make_empty_trace(n: int, cap: int) -> TraceState:
    """An all-ALLOW, empty-ring trace carry (the device-only counterpart of
    ``repro.trace.recorder.make_trace_state`` for padding/precompile)."""
    return TraceState(
        buf=jnp.zeros((n, 2, cap, REC_WORDS), I64),
        count=jnp.zeros((n,), I64),
        hot=jnp.zeros((n,), I64),
        base=jnp.zeros((n,), I64),
        hist=jnp.zeros((n, N_POLICY_SLOTS, N_VERDICTS), I64),
        pol_action=jnp.full((n, N_POLICY_SLOTS), POL_ALLOW, I32),
        pol_arg=jnp.zeros((n, N_POLICY_SLOTS), I64),
        deny_count=jnp.zeros((n,), I64),
        emul_count=jnp.zeros((n,), I64),
        kill_count=jnp.zeros((n,), I64))


def _permute_split(tree, keep_idx, drop_idx):
    """One gather-permutation over every lane-leading leaf: the kept lanes
    as a dense prefix tree, the dropped lanes as a suffix tree.

    Not donated: a gather's output can never alias its operand, so donation
    would only emit unusable-buffer warnings — the source fleet is instead
    freed by the caller dropping its reference right after the call (the
    practical equivalent for the [B, MEM_WORDS] carry)."""
    take = lambda i: (lambda x: jnp.take(x, i, axis=0))
    return (jax.tree_util.tree_map(take(keep_idx), tree),
            jax.tree_util.tree_map(take(drop_idx), tree))


_jitted_permute_split = jax.jit(_permute_split)


def _concat_lanes(tree, pad_tree):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b]), tree, pad_tree)


_jitted_concat_lanes = jax.jit(_concat_lanes)


def permute_split(tree, keep_idx, drop_idx):
    """Public entry for the compaction permutation (one jitted
    gather-permutation over every lane-leading leaf of ``tree``): returns
    ``(kept, dropped)`` trees.  What :func:`run_fleet_compact` and the
    serving pool's shrink path run at every rung transition."""
    return _jitted_permute_split(tree, jnp.asarray(keep_idx),
                                 jnp.asarray(drop_idx))


def concat_lanes(tree, pad_tree):
    """Public entry for the grow transition: append ``pad_tree``'s lanes
    (e.g. :func:`make_halted_states`) after ``tree``'s along the lane
    axis, jitted.  The serving pool's re-expansion path."""
    return _jitted_concat_lanes(tree, pad_tree)


def precompile_ladder(imgs, ladder: Sequence[int], *,
                      chunk: int = DEFAULT_CHUNK,
                      interval: Optional[int] = None,
                      trace_cap: Optional[int] = None,
                      shard: bool = False,
                      engine: str = "xla") -> None:
    """Compile every executable a compacted run can hit, ahead of the run:

    * one dispatch per rung on an all-halted dummy fleet of that width —
      the span executable (the while_loop condition fails immediately, so
      the cost is the compile alone);
    * the rung-transition graphs: the gather-permutation split for every
      descending (shrink) pair and the pad-concatenation for every
      ascending (grow) pair a serving pool can take.

    A compacted run over the same (chunk, interval, trace) configuration
    then never pays a step-path XLA compile mid-run; only a serving
    pool's per-rung admission scatters still compile lazily on first use.
    ``engine`` warms that engine's span drivers (:func:`run_fleet_span`'s
    dispatch table), so a pallas-engined pool precompiles its kernels too.
    """
    _check_engine(engine, shard=shard)
    imgs = pack_images(imgs)
    interval = chunk * 8 if interval is None else interval
    span = -(-interval // chunk)
    ladder = sorted({int(w) for w in ladder}, reverse=True)
    shard_fn = None
    if shard:
        from repro.parallel.sharding import shard_fleet
        shard_fn = shard_fleet

    def dummy(w):
        s = make_halted_states(w)
        ids = jnp.zeros((w,), I32)
        tr = None if trace_cap is None else make_empty_trace(w, trace_cap)
        if shard_fn is not None:
            parts = shard_fn(imgs, ids, s, trace=tr)
            ids, s = parts[1], parts[2]
            if tr is not None:
                tr = parts[3]
        return ids, s, tr

    for w in ladder:
        ids, s, tr = dummy(w)
        run_span = _engine_span(engine, int(chunk), int(span), tr is not None)
        if tr is None:
            run_span(imgs, ids, s)
        else:
            run_span(imgs, ids, s, tr)

    for i, wfrom in enumerate(ladder):
        for wto in ladder[i + 1:]:
            # shrink: indices arrive as int64 np.argsort output at run time
            keep = jnp.asarray(np.arange(wto, dtype=np.int64))
            drop = jnp.asarray(np.arange(wto, wfrom, dtype=np.int64))
            _, s, tr = dummy(wfrom)
            _jitted_permute_split(s if tr is None else (s, tr), keep, drop)
            # grow: a wto-wide (possibly sharded) pool padded back to wfrom
            # with fresh all-halted lanes, exactly as FleetServer._grow_to
            _, s, tr = dummy(wto)
            pad_s = make_halted_states(wfrom - wto)
            if tr is None:
                _jitted_concat_lanes(s, pad_s)
            else:
                pad_t = make_empty_trace(wfrom - wto, trace_cap)
                _jitted_concat_lanes((s, tr), (pad_s, pad_t))


def _assemble_lanes(n_lanes: int, segments):
    """Inverse-permutation assembly: scatter finished segments (original
    lane ids + state slices) back into original lane order, one host buffer
    per leaf."""
    treedef = jax.tree_util.tree_structure(segments[0][1])
    bufs = None
    for idx, tree in segments:
        leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]
        if bufs is None:
            bufs = [np.empty((n_lanes,) + lf.shape[1:], lf.dtype)
                    for lf in leaves]
        for buf, lf in zip(bufs, leaves):
            buf[idx] = lf
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(b) for b in bufs])


def run_fleet_compact(imgs, states, img_ids=None, *,
                      chunk: int = DEFAULT_CHUNK,
                      min_bucket: int = DEFAULT_MIN_BUCKET,
                      hysteresis: float = 0.0,
                      interval: Optional[int] = None,
                      shard: bool = False,
                      trace: Optional[TraceState] = None,
                      stats: Optional[dict] = None,
                      engine: str = "xla"):
    """:func:`run_fleet` with live-lane compaction: results (states, and the
    trace carry when passed) are **bit-identical and lane-ordered** to the
    fixed-width run, but halted lanes stop costing step compute.

    The fleet runs in bounded spans of ``interval`` masked steps (default
    ``8 * chunk``).  After each span the live count is read back; when it
    falls below the next rung of the bucket ladder (power-of-two widths
    down to ``min_bucket``, ``hysteresis`` guarding borderline shrinks),
    live lanes are compacted into a dense prefix by one donated
    gather-permutation over every carry leaf — the ``[B, MEM_WORDS]``
    memory image, registers, trace rings and counters — and the run
    re-dispatches at the narrower width.  Every rung is a precompiled
    executable (:func:`precompile_ladder`), so no XLA compilation happens
    mid-run once the ladder is warm.

    ``stats`` (a dict, filled in place) reports the occupancy ledger:
    dispatched vs useful lane-steps, the ladder, and each compaction.
    ``shard=True`` lane-partitions every rung across local devices; the
    ladder then only holds device-divisible rungs (per-shard ladders).
    ``engine`` as in :func:`run_fleet` (bit-identical; pallas does not
    compose with shard).
    """
    _check_engine(engine, shard=shard)
    imgs = pack_images(imgs)
    if not isinstance(states, MachineState):
        states = stack_states(states)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    n_lanes = int(states.pc.shape[0])
    if img_ids is None:
        if int(imgs.packed.shape[0]) != n_lanes:
            raise ValueError("img_ids required when #images != #lanes")
        ids_np = np.arange(n_lanes, dtype=np.int32)
    else:
        ids_np = np.asarray(img_ids, np.int32)
    interval = chunk * 8 if interval is None else int(interval)
    if interval < 1:
        raise ValueError(f"interval must be >= 1, got {interval}")
    span = -(-interval // chunk)

    divisor = 1
    shard_fn = None
    if shard:
        from repro.parallel.sharding import fleet_divisor, shard_fleet
        divisor = fleet_divisor(n_lanes)   # per-shard ladder rungs
        if divisor > 1:
            shard_fn = shard_fleet

    ladder = compact_ladder(n_lanes, min_bucket, divisor=divisor)
    traced = trace is not None

    order = np.arange(n_lanes)          # physical slot -> original lane
    cur_s, cur_t = states, trace
    W = n_lanes
    ids_w = jnp.asarray(ids_np, I32)
    if shard_fn is not None:
        parts = shard_fn(imgs, ids_w, cur_s, trace=cur_t)
        imgs, ids_w, cur_s = parts[0], parts[1], parts[2]
        if traced:
            cur_t = parts[3]

    segments = []                        # (original lane ids, slice trees)
    prev_icount = np.asarray(cur_s.icount).copy()
    dispatched = 0
    useful = 0
    compactions = []
    dispatches = 0
    run_span = _engine_span(engine, int(chunk), int(span), traced)

    while True:
        if traced:
            cur_s, cur_t = run_span(imgs, ids_w, cur_s, cur_t)
        else:
            cur_s = run_span(imgs, ids_w, cur_s)
        dispatches += 1
        halted = np.asarray(cur_s.halted)
        icount = np.asarray(cur_s.icount)
        fuel = np.asarray(cur_s.fuel)
        delta = icount - prev_icount
        # chunks actually scanned: the while_loop exits at the first chunk
        # boundary with no live lane, so the longest per-lane delta rounds
        # up to the dispatched chunk count
        chunks_run = int(-(-int(delta.max()) // chunk)) if delta.max() else 0
        dispatched += W * chunks_run * chunk
        useful += int(delta.sum())
        alive = (halted == RUNNING) & (icount < fuel)
        n_live = int(alive.sum())
        if n_live == 0:
            break
        target = choose_bucket(ladder, n_live, cur=W, hysteresis=hysteresis)
        if target < W:
            perm = np.argsort(~alive, kind="stable")   # live lanes first
            keep = jnp.asarray(perm[:target])
            drop = jnp.asarray(perm[target:])
            if traced:
                (ks, kt), (ds, dt) = _jitted_permute_split(
                    (cur_s, cur_t), keep, drop)
                segments.append((order[perm[target:]], (ds, dt)))
                cur_s, cur_t = ks, kt
            else:
                ks, ds = _jitted_permute_split(cur_s, keep, drop)
                segments.append((order[perm[target:]], ds))
                cur_s = ks
            compactions.append({"from": W, "to": target, "live": n_live})
            order = order[perm[:target]]
            W = target
            ids_w = jnp.asarray(ids_np[order], I32)
            prev_icount = icount[perm[:target]]
            if shard_fn is not None:
                parts = shard_fn(imgs, ids_w, cur_s, trace=cur_t)
                imgs, ids_w, cur_s = parts[0], parts[1], parts[2]
                if traced:
                    cur_t = parts[3]
        else:
            prev_icount = icount

    segments.append((order, (cur_s, cur_t) if traced else cur_s))
    if traced:
        out_s, out_t = _assemble_lanes(n_lanes, segments)
    else:
        out_s = _assemble_lanes(n_lanes, segments)
    out_s = out_s._replace(halted=jnp.asarray(finish_halt_codes(
        np.asarray(out_s.halted), np.asarray(out_s.icount),
        np.asarray(out_s.fuel))))

    if stats is not None:
        stats.update({
            "ladder": ladder,
            "interval": interval,
            "dispatches": dispatches,
            "compactions": compactions,
            "final_bucket": W,
            "dispatched_lane_steps": dispatched,
            "useful_steps": useful,
            "occupancy": round(useful / dispatched, 4) if dispatched else 1.0,
            "wasted_lane_steps": dispatched - useful,
        })
    return (out_s, out_t) if traced else out_s


# ---------------------------------------------------------------------------
# bulk host-side readback
# ---------------------------------------------------------------------------

def fleet_counters(states: MachineState) -> np.ndarray:
    """Per-lane hook-invocation totals in one device transfer per array
    (COUNTER word + ptrace-side hook_count), not one sync per lane."""
    counter = np.asarray(states.mem[:, _COUNTER_IDX])
    return counter + np.asarray(states.hook_count)


def fleet_summary(states: MachineState) -> List[dict]:
    """Host-side per-lane result rows with a single device->host transfer
    per field (the scalar path syncs once per scalar per lane)."""
    fields = {
        "halted": np.asarray(states.halted),
        "exit_code": np.asarray(states.exit_code),
        "cycles": np.asarray(states.cycles),
        "icount": np.asarray(states.icount),
        "out_count": np.asarray(states.out_count),
        "out_sum": np.asarray(states.out_sum),
        "enosys_count": np.asarray(states.enosys_count),
        "emul_served": np.asarray(states.emul_served),
    }
    hooks = fleet_counters(states)
    n = fields["halted"].shape[0]
    return [dict({k: int(v[i]) for k, v in fields.items()},
                 hooks=int(hooks[i])) for i in range(n)]


# ---------------------------------------------------------------------------
# durable-serving helpers (the device side of repro.serve.durability)
# ---------------------------------------------------------------------------
#
# A fleet snapshot is the WHOLE carry — MachineState tree, optional
# TraceState tree — moved to host as a flat {key: np.ndarray} dict plus a
# full-coverage digest.  The digest intentionally does NOT reuse
# checkpoint.manager._tree_hash: that one prefix-hashes the first 64KB of
# each leaf (fine for torn-file detection on big training arrays), while
# the chaos harness must catch a single flipped bit anywhere in a
# [B, MEM_WORDS] memory image, so every byte participates here.  crc32 is
# plenty: this is corruption *detection* inside one trust domain, not an
# authenticated hash.

def _carry_bytes(leaf) -> memoryview:
    a = np.ascontiguousarray(np.asarray(leaf))
    return memoryview(a).cast("B")


def carry_digest(states: MachineState,
                 trace: Optional[TraceState] = None) -> int:
    """Full-coverage crc32 over every byte of a fleet carry (machine state
    tree + optional trace tree), shape/dtype-framed so a reshaped-but-
    equal-bytes carry does not collide.  The per-snapshot integrity check
    of :mod:`repro.serve.durability` and the detector for chaos-injected
    lane-carry bit-flips."""
    crc = 0
    for tree in (states,) if trace is None else (states, trace):
        for key, leaf in zip(tree._fields, tree):
            frame = f"{key}:{np.asarray(leaf).shape}:{np.asarray(leaf).dtype};"
            crc = zlib.crc32(frame.encode(), crc)
            crc = zlib.crc32(_carry_bytes(leaf), crc)
    return crc


def lane_digests(states: MachineState,
                 trace: Optional[TraceState] = None) -> List[int]:
    """Per-lane crc32s of a fleet carry — ``carry_digest`` restricted to
    lane ``b`` of every leaf.  Lets rollback attribute a corrupted carry
    to the specific lanes (and so tenants) whose bytes diverged."""
    n = int(np.asarray(states.halted).shape[0])
    host = [np.ascontiguousarray(np.asarray(leaf)) for leaf in
            (list(states) + (list(trace) if trace is not None else []))]
    out = []
    for b in range(n):
        crc = 0
        for a in host:
            crc = zlib.crc32(memoryview(np.ascontiguousarray(a[b])).cast("B"),
                             crc)
        out.append(crc)
    return out


# Big mostly-zero planes stored as nonzero (idx, val) pairs in snapshots.
_SPARSE_CARRY = ("mem", "k_ino_data")


def pack_carry(states: MachineState, trace: Optional[TraceState] = None,
               *, prefix: str = "") -> Dict[str, np.ndarray]:
    """Flatten a fleet carry into snapshot arrays: ``state/<field>`` and
    ``trace/<field>`` host arrays, with the mostly-zero big planes — the
    [B, MEM_WORDS] memory leaf and the [B, MAX_INODES*FILE_WORDS] inode
    data plane — stored sparsely (``state/<f>@idx`` flat nonzero indices
    + ``state/<f>@val`` values) — a 400-lane pool's dense memory plane is
    100MB/snapshot, which would sink the <10% durability-overhead budget
    on its own.  :func:`unpack_carry` reverses both encodings."""
    out: Dict[str, np.ndarray] = {}
    for f in _SPARSE_CARRY:
        dense = np.asarray(getattr(states, f))
        idx = np.flatnonzero(dense.reshape(-1))
        out[f"{prefix}state/{f}@idx"] = idx
        out[f"{prefix}state/{f}@val"] = dense.reshape(-1)[idx]
        out[f"{prefix}state/{f}@shape"] = np.asarray(dense.shape, np.int64)
    for key, leaf in zip(states._fields, states):
        if key not in _SPARSE_CARRY:
            out[f"{prefix}state/{key}"] = np.asarray(leaf)
    if trace is not None:
        for key, leaf in zip(trace._fields, trace):
            out[f"{prefix}trace/{key}"] = np.asarray(leaf)
    return out


def unpack_carry(arrays, *, prefix: str = ""
                 ) -> Tuple[MachineState, Optional[TraceState]]:
    """Rebuild ``(MachineState, TraceState | None)`` host trees from
    :func:`pack_carry` snapshot arrays."""
    fields = {}
    for f in _SPARSE_CARRY:
        shape = tuple(int(x) for x in arrays[f"{prefix}state/{f}@shape"])
        dense = np.zeros(int(np.prod(shape)), I64)
        dense[np.asarray(arrays[f"{prefix}state/{f}@idx"])] = \
            np.asarray(arrays[f"{prefix}state/{f}@val"])
        fields[f] = dense.reshape(shape)
    for key in MachineState._fields:
        if key not in _SPARSE_CARRY:
            fields[key] = np.asarray(arrays[f"{prefix}state/{key}"])
    states = MachineState(**fields)
    if f"{prefix}trace/count" not in arrays:
        return states, None
    trace = TraceState(**{key: np.asarray(arrays[f"{prefix}trace/{key}"])
                          for key in TraceState._fields})
    return states, trace


def unpack_images(imgs: FleetImages) -> DecodedImage:
    """Invert :func:`pack_images`: packed int64 words back to the eight
    SoA decode tables, vectorised (no per-word Python loop — recovery
    rehydrates images from the content-addressed store without paying
    ``machine.decode_image``'s 65536-iteration host decode)."""
    p = np.asarray(imgs.packed)
    f32 = lambda shift, mask: ((p >> shift) & mask).astype(np.int32)
    return DecodedImage(
        op=f32(0, 0x3F), rd=f32(6, 0x1F), rn=f32(11, 0x1F),
        rm=f32(16, 0x1F), sh=f32(22, 0x3F), cond=f32(28, 0xF),
        sf=f32(32, 0x1), imm=np.asarray(imgs.imm))


def flip_bit(states: MachineState, lane: int, word: int,
             bit: int) -> MachineState:
    """Flip one bit of one lane's memory plane — the chaos harness's
    injected carry corruption (what :func:`carry_digest` must catch)."""
    mem = np.asarray(states.mem).copy()
    mem[lane, word] ^= np.int64(1) << np.int64(bit)
    return states._replace(mem=jnp.asarray(mem))
