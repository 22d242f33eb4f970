"""Legacy-vs-generated bit-exactness sweep for the op-spec executors.

The hand-written per-op handlers in ``core/machine.py`` were retired in
favour of one spec-driven executor body (``fleet.exec_lanes``, generated
from ``core/opspec``).  This module is the one-time regression net that
gated the deletion: a standalone Python-int oracle transcribed from the
legacy handlers, swept over every opcode x flag state x edge operand and
compared bit-for-bit against the generated executor (batched) and the
generated scalar ``machine.step``.

The oracle deliberately re-implements the *old* semantics from scratch
(two's-complement int64 in plain Python) so it shares no code with the
spec table it checks.
"""
import numpy as np
import pytest

import repro.core.fleet as F
import repro.core.machine as M
import repro.core.opspec as opspec
from repro.core import costmodel as cm
from repro.core import layout as L
from repro.core.isa import Op
from repro.emul import state as emul_state

import jax
import jax.numpy as jnp

_M64 = (1 << 64) - 1


def s64(x):
    """Two's-complement wrap to signed 64-bit (what every jnp.int64 op does)."""
    x &= _M64
    return x - (1 << 64) if x >= (1 << 63) else x


def u64(x):
    return x & _M64


# ---------------------------------------------------------------------------
# the legacy scalar step, transcribed to plain Python ints
# ---------------------------------------------------------------------------

_LEGACY_COSTS = np.ones(int(Op.N_OPS), np.int64) * cm.COST_ALU
for _o in (Op.LDRI, Op.STRI, Op.LDRPOST, Op.STRPRE, Op.STP, Op.LDP,
           Op.STPPRE, Op.LDPPOST, Op.LDRB, Op.STRB):
    _LEGACY_COSTS[int(_o)] = cm.COST_MEM
for _o in (Op.B, Op.BCOND, Op.CBZ, Op.CBNZ):
    _LEGACY_COSTS[int(_o)] = cm.COST_BRANCH
for _o in (Op.BL, Op.RET):
    _LEGACY_COSTS[int(_o)] = cm.COST_CALL
for _o in (Op.BR, Op.BLR):
    _LEGACY_COSTS[int(_o)] = cm.COST_INDIRECT

_SIGFRAME_IDX = (L.SIGFRAME - L.DATA_BASE) // 8


class Lane:
    """Mutable scalar machine state for the oracle."""

    def __init__(self, case):
        self.regs = [0] * 31
        for i, v in case.get("regs", {}).items():
            self.regs[i] = s64(v)
        self.sp = s64(case.get("sp", L.STACK_TOP))
        self.pc = s64(case.get("pc", 0x2000))
        self.nzcv = s64(case.get("nzcv", 0))
        self.mem = np.zeros(L.MEM_WORDS, np.int64)
        for i, v in case.get("mem", {}).items():
            self.mem[i] = s64(v)
        self.cycles = 0
        self.icount = 0
        self.halted = 0
        self.exit_code = 0
        self.fault_pc = 0
        self.sig_handler = s64(case.get("sig_handler", 0))
        self.in_signal = s64(case.get("in_signal", 0))
        self.ptrace = s64(case.get("ptrace", 0))
        self.virt_getpid = s64(case.get("virt_getpid", 0))
        self.hook_count = 0
        self.pid = L.PID
        self.in_off = s64(case.get("in_off", 0))
        self.out_count = 0
        self.out_sum = 0
        self.enosys_count = 0
        self.emul_served = 0  # sweep runs with the guest kernel disabled


def _rr(st, i):
    return 0 if i == 31 else st.regs[min(i, 30)]


def _rsp(st, i):
    return st.sp if i == 31 else st.regs[min(i, 30)]


def _wr(st, i, v):
    if i != 31:
        st.regs[i] = s64(v)


def _wsp(st, i, v):
    if i == 31:
        st.sp = s64(v)
    else:
        st.regs[i] = s64(v)


def _mem_ok(a):
    return L.DATA_BASE <= a < L.MEM_LIMIT and a % 8 == 0


def _widx(a):
    return max(0, min(s64(a - L.DATA_BASE) >> 3, L.MEM_WORDS - 1))


def _load(st, a):
    ok = _mem_ok(a)
    v = int(st.mem[_widx(a)])
    return (v if ok else 0), ok


def _store(st, a, v):
    if _mem_ok(a):
        st.mem[_widx(a)] = s64(v)
        return True
    return False


def _badmem(st, ok):
    if not ok:
        st.halted = 5  # HALT_BADMEM
        st.fault_pc = st.pc


def _adv(st):
    st.pc = s64(st.pc + 4)


def _set_flags_sub(st, a, b):
    res = s64(a - b)
    n = 8 if res < 0 else 0
    z = 4 if res == 0 else 0
    c = 2 if u64(a) >= u64(b) else 0
    v = 1 if s64((a ^ b) & (a ^ res)) < 0 else 0
    st.nzcv = n + z + c + v


def legacy_cond_holds(nzcv, cond):
    n = (nzcv & 8) != 0
    z = (nzcv & 4) != 0
    c = (nzcv & 2) != 0
    v = (nzcv & 1) != 0
    preds = (z, not z, c, not c, n, not n, v, not v,
             c and not z, not (c and not z), n == v, n != v,
             (not z) and n == v, not ((not z) and n == v), True, True)
    return preds[max(0, min(cond, 15))]


def _deliver_signal(st, signo):
    can = st.sig_handler != 0 and st.in_signal == 0
    if can:
        frame = st.regs + [st.sp, st.pc, st.nzcv]
        st.mem[_SIGFRAME_IDX:_SIGFRAME_IDX + 34] = frame
        st.regs[0] = signo
        st.regs[1] = L.SIGFRAME
        st.sp = L.SIGSTACK_TOP
        st.pc = st.sig_handler
        st.in_signal = 1
        st.cycles += cm.SIGNAL_DELIVERY
    else:
        st.halted = 3  # HALT_TRAP
        st.fault_pc = st.pc


def _do_svc(st):
    nr = st.regs[8]
    st.cycles += cm.KERNEL_CROSS
    if st.ptrace != 0:
        st.cycles += 2 * cm.PTRACE_STOP
        st.hook_count += 1
    if nr in (L.SYS_READ, L.SYS_WRITE):
        buf, n = st.regs[1], st.regs[2]
        k = max(0, min(n >> 3, 4096))
        ok = (_mem_ok(buf) and s64(buf + n) <= L.MEM_LIMIT
              and n >= 0 and (n & 7) == 0)
        start = _widx(buf)
        if nr == L.SYS_READ:
            if ok:
                for j in range(k):
                    st.mem[start + j] = s64(st.in_off + j * 8)
                st.in_off = s64(st.in_off + n)
        else:
            if ok:
                tot = 0
                for j in range(k):
                    tot = s64(tot + int(st.mem[start + j]))
                st.out_count = s64(st.out_count + n)
                st.out_sum = s64(st.out_sum + tot)
        st.cycles += n // cm.IO_BYTES_PER_CYCLE
        _wr(st, 0, n if ok else -14)
        _adv(st)
    elif nr == L.SYS_GETPID:
        virt = st.ptrace != 0 and st.virt_getpid != 0
        _wr(st, 0, L.VIRT_PID if virt else st.pid)
        _adv(st)
    elif nr == L.SYS_EXIT:
        st.halted = 1  # HALT_EXIT
        st.exit_code = st.regs[0]
    elif nr == L.SYS_RT_SIGRETURN:
        frame = [int(x) for x in st.mem[_SIGFRAME_IDX:_SIGFRAME_IDX + 34]]
        st.regs = frame[:31]
        st.sp = frame[31]
        st.pc = s64(frame[32] + 4)
        st.nzcv = frame[33]
        st.in_signal = 0
    elif nr == L.SYS_OPENAT:
        _wr(st, 0, 3)
        _adv(st)
    elif nr == L.SYS_CLOSE:
        _wr(st, 0, 0)
        _adv(st)
    else:
        st.enosys_count += 1
        _wr(st, 0, -38)
        _adv(st)


def oracle_step(case, st):
    """One legacy (unconditional) step of ``case``'s instruction on ``st``."""
    op = Op(case["op"])
    rd, rn, rm = case.get("rd", 0), case.get("rn", 0), case.get("rm", 0)
    sh, cond, sf = case.get("sh", 0), case.get("cond", 0), case.get("sf", 1)
    imm = s64(case.get("imm", 0))
    st.cycles += int(_LEGACY_COSTS[int(op)])
    st.icount += 1

    if op == Op.ILLEGAL:
        _deliver_signal(st, L.SIGILL)
    elif op == Op.NULLPAGE:
        st.halted = 2  # HALT_SEGV
        st.fault_pc = st.pc
    elif op in (Op.MOVZ, Op.MOVN, Op.MOVK):
        piece = s64(imm << sh)
        if op == Op.MOVZ:
            v = piece
        elif op == Op.MOVN:
            v = s64(~piece)
        else:
            v = s64((_rr(st, rd) & s64(~s64(0xFFFF << sh))) | piece)
        if sf != 1:
            v &= 0xFFFFFFFF
        _wr(st, rd, v)
        _adv(st)
    elif op == Op.ADRP:
        _wr(st, rd, s64((st.pc & ~0xFFF) + imm))
        _adv(st)
    elif op == Op.ADR:
        _wr(st, rd, s64(st.pc + imm))
        _adv(st)
    elif op == Op.ADDI:
        _wsp(st, rd, s64(_rsp(st, rn) + imm))
        _adv(st)
    elif op == Op.SUBI:
        _wsp(st, rd, s64(_rsp(st, rn) - imm))
        _adv(st)
    elif op == Op.SUBSI:
        a = _rsp(st, rn)
        _set_flags_sub(st, a, imm)
        _wr(st, rd, s64(a - imm))
        _adv(st)
    elif op in (Op.ADDR, Op.SUBR, Op.SUBSR, Op.ORRR, Op.ANDR, Op.EORR):
        a, b = _rr(st, rn), _rr(st, rm)
        if op == Op.SUBSR:
            _set_flags_sub(st, a, b)
        v = {Op.ADDR: a + b, Op.SUBR: a - b, Op.SUBSR: a - b,
             Op.ORRR: a | b, Op.ANDR: a & b, Op.EORR: a ^ b}[op]
        _wr(st, rd, s64(v))
        _adv(st)
    elif op == Op.MADD:
        ra = imm  # ra rides in imm, in [0, 31] by decode
        _wr(st, rd, s64(_rr(st, rn) * _rr(st, rm) + _rr(st, ra)))
        _adv(st)
    elif op == Op.LDRI:
        v, ok = _load(st, s64(_rsp(st, rn) + imm))
        _wr(st, rd, v)
        _badmem(st, ok)
        _adv(st)
    elif op == Op.STRI:
        ok = _store(st, s64(_rsp(st, rn) + imm), _rr(st, rd))
        _badmem(st, ok)
        _adv(st)
    elif op == Op.LDRPOST:
        base = _rsp(st, rn)
        v, ok = _load(st, base)
        _wr(st, rd, v)
        _wsp(st, rn, s64(base + imm))
        _badmem(st, ok)
        _adv(st)
    elif op == Op.STRPRE:
        addr = s64(_rsp(st, rn) + imm)
        ok = _store(st, addr, _rr(st, rd))
        _wsp(st, rn, addr)
        _badmem(st, ok)
        _adv(st)
    elif op in (Op.STP, Op.STPPRE):
        base = s64(_rsp(st, rn) + imm)
        ok1 = _store(st, base, _rr(st, rd))
        ok2 = _store(st, s64(base + 8), _rr(st, rm))
        if op == Op.STPPRE:
            _wsp(st, rn, base)
        _badmem(st, ok1 and ok2)
        _adv(st)
    elif op == Op.LDP:
        base = s64(_rsp(st, rn) + imm)
        v1, ok1 = _load(st, base)
        v2, ok2 = _load(st, s64(base + 8))
        _wr(st, rd, v1)
        _wr(st, rm, v2)
        _badmem(st, ok1 and ok2)
        _adv(st)
    elif op == Op.LDPPOST:
        base = _rsp(st, rn)
        v1, ok1 = _load(st, base)
        v2, ok2 = _load(st, s64(base + 8))
        _wr(st, rd, v1)
        _wr(st, rm, v2)
        _wsp(st, rn, s64(base + imm))
        _badmem(st, ok1 and ok2)
        _adv(st)
    elif op == Op.B:
        st.pc = s64(st.pc + imm)
    elif op == Op.BL:
        _wr(st, 30, s64(st.pc + 4))
        st.pc = s64(st.pc + imm)
    elif op in (Op.BR, Op.RET):
        st.pc = _rr(st, rn)
    elif op == Op.BLR:
        tgt = _rr(st, rn)
        _wr(st, 30, s64(st.pc + 4))
        st.pc = tgt
    elif op == Op.CBZ:
        st.pc = s64(st.pc + (imm if _rr(st, rd) == 0 else 4))
    elif op == Op.CBNZ:
        st.pc = s64(st.pc + (imm if _rr(st, rd) != 0 else 4))
    elif op == Op.BCOND:
        taken = legacy_cond_holds(st.nzcv, cond)
        st.pc = s64(st.pc + (imm if taken else 4))
    elif op == Op.SVC:
        _do_svc(st)
    elif op == Op.BRK:
        _deliver_signal(st, L.SIGTRAP)
    elif op == Op.NOP:
        _adv(st)
    elif op == Op.LDRB:
        addr = s64(_rsp(st, rn) + imm)
        ok = L.DATA_BASE <= addr < L.MEM_LIMIT
        word = int(st.mem[_widx(addr & ~7)])
        byte = (word >> ((addr & 7) * 8)) & 0xFF  # written even when !ok
        _wr(st, rd, byte)
        _badmem(st, ok)
        _adv(st)
    elif op == Op.STRB:
        addr = s64(_rsp(st, rn) + imm)
        ok = L.DATA_BASE <= addr < L.MEM_LIMIT
        idx = _widx(addr & ~7)
        shift = (addr & 7) * 8
        word = int(st.mem[idx])
        if ok:
            st.mem[idx] = s64((word & s64(~s64(0xFF << shift)))
                              | ((_rr(st, rd) & 0xFF) << shift))
        _badmem(st, ok)
        _adv(st)
    elif op == Op.HLT:
        st.halted = 1  # HALT_EXIT
        st.exit_code = st.regs[0]
    elif op == Op.LSLI:
        _wr(st, rd, s64(_rr(st, rn) << sh))
        _adv(st)
    else:  # pragma: no cover
        raise AssertionError(f"unhandled op {op}")
    return st


# ---------------------------------------------------------------------------
# case generation: every op x flag state x edge operand
# ---------------------------------------------------------------------------

EDGE = (0, 1, -1, (1 << 63) - 1, -(1 << 63), 0x0123456789ABCDEF, 8)
ADDRS = (L.DATA_BASE, L.DATA_BASE + 8, L.MEM_LIMIT - 8, L.MEM_LIMIT - 16,
         L.DATA_BASE - 8, L.MEM_LIMIT, L.DATA_BASE + 4, -(1 << 63),
         (1 << 63) - 8)


def _mem_seed(addr, val=0x5151515151515151):
    """Seed the target word (by the clipped legacy index) so loads see data."""
    return {_widx(s64(addr) & ~7): val}


def gen_cases():
    cases = []

    def add(op, **kw):
        kw["op"] = int(op)
        cases.append(kw)

    # halting / trivial ops, with and without a handler
    for sig, insig in ((0, 0), (0x3000, 0), (0x3000, 1), (0, 1)):
        for op in (Op.ILLEGAL, Op.BRK):
            add(op, sig_handler=sig, in_signal=insig, nzcv=0b1010,
                regs={0: 77, 7: -3, 30: 1234}, sp=L.STACK_TOP - 64)
    add(Op.NULLPAGE, pc=0x0)
    add(Op.NOP)
    for x0 in EDGE:
        add(Op.HLT, regs={0: x0})

    # moves: imm x hw shift x sf, movk over a seeded destination
    for op in (Op.MOVZ, Op.MOVN, Op.MOVK):
        for imm in (0, 1, 0xFFFF, 0x8000):
            for sh in (0, 16, 32, 48):
                for sf in (0, 1):
                    add(op, rd=5, sh=sh, sf=sf, imm=imm,
                        regs={5: -0x0123456789ABCDEF})
    add(Op.MOVZ, rd=31, imm=0xFFFF)  # XZR write is a no-op

    # pc-relative
    for imm in (0, 0x1000, -0x1000, 4):
        add(Op.ADRP, rd=2, imm=imm, pc=0x2ABC & ~3)
        add(Op.ADR, rd=2, imm=imm, pc=0x2ABC & ~3)

    # imm ALU (incl. SP read/write via reg 31) and flag edges
    for op in (Op.ADDI, Op.SUBI, Op.SUBSI):
        for a in EDGE:
            for imm in (0, 1, 0xFFF):
                add(op, rd=3, rn=4, imm=imm, regs={4: a}, nzcv=0b0110)
        add(op, rd=31, rn=31, imm=8, sp=L.STACK_TOP - 32)
        add(op, rd=3, rn=31, imm=16, sp=0x41000)

    # reg-reg ALU over the full edge grid (flag states ride on SUBSR)
    for op in (Op.ADDR, Op.SUBR, Op.SUBSR, Op.ORRR, Op.ANDR, Op.EORR):
        for a in EDGE:
            for b in EDGE:
                add(op, rd=6, rn=7, rm=8, regs={7: a, 8: b}, nzcv=0b1111)
        add(op, rd=6, rn=31, rm=8, regs={8: 5})   # XZR operand
        add(op, rd=31, rn=7, rm=8, regs={7: 1, 8: 2})

    add(Op.MADD, rd=9, rn=10, rm=11, imm=12,
        regs={10: 7, 11: -3, 12: 1000})
    add(Op.MADD, rd=9, rn=10, rm=11, imm=31, regs={10: 5, 11: 5})  # ra=XZR
    add(Op.MADD, rd=9, rn=10, rm=11, imm=12,
        regs={10: (1 << 62), 11: 8, 12: -1})  # wrapping product

    # loads/stores: every addressing edge (good / OOB / misaligned / wrap)
    for op in (Op.LDRI, Op.STRI, Op.LDRPOST, Op.STRPRE, Op.STP, Op.LDP,
               Op.STPPRE, Op.LDPPOST):
        post = op in (Op.LDRPOST, Op.LDPPOST)
        for base in ADDRS:
            for imm in (0, 8, -8):
                eff = base if post else s64(base + imm)
                add(op, rd=12, rn=13, rm=14, imm=imm,
                    regs={12: 0x1111, 13: base, 14: 0x2222},
                    mem={**_mem_seed(eff), **_mem_seed(s64(eff + 8), 0x6262)})
    # pair aliasing / writeback corner cases
    add(Op.LDP, rd=15, rm=15, rn=13, imm=0, regs={13: L.DATA_BASE + 16},
        mem={2: 0xAA, 3: 0xBB})
    add(Op.LDPPOST, rd=13, rm=14, rn=13, imm=16,
        regs={13: L.DATA_BASE + 16}, mem={2: 0xAA, 3: 0xBB})
    add(Op.LDPPOST, rd=12, rm=13, rn=13, imm=16,
        regs={13: L.DATA_BASE + 16}, mem={2: 0xAA, 3: 0xBB})
    add(Op.LDRPOST, rd=13, rn=13, imm=8, regs={13: L.DATA_BASE + 24},
        mem={3: 0xCC})
    add(Op.STP, rd=12, rm=14, rn=31, imm=0, sp=L.MEM_LIMIT - 8,
        regs={12: 0x77, 14: 0x88})  # slot 1 lands, slot 2 faults

    # byte ops: every in-word offset plus the OOB edges
    for off in range(8):
        addr = L.DATA_BASE + 40 + off
        add(Op.LDRB, rd=16, rn=17, imm=0, regs={17: addr},
            mem=_mem_seed(addr, -0x0123456789ABCDEF))
        add(Op.STRB, rd=16, rn=17, imm=0,
            regs={16: 0x1A5, 17: addr}, mem=_mem_seed(addr, -1))
    for base in (L.DATA_BASE - 1, L.MEM_LIMIT, L.MEM_LIMIT - 1):
        add(Op.LDRB, rd=16, rn=17, imm=0, regs={17: base})
        add(Op.STRB, rd=16, rn=17, imm=0, regs={16: 0xFF, 17: base})

    # branches
    for imm in (8, -8, 0):
        add(Op.B, imm=imm)
        add(Op.BL, imm=imm, regs={30: 7})
    for tgt in (0x2000, 0, -4, (1 << 63) - 4):
        for op in (Op.BR, Op.BLR, Op.RET):
            add(op, rn=19, regs={19: tgt, 30: 9})
    for v in (0, 1, -1):
        add(Op.CBZ, rd=20, imm=16, regs={20: v})
        add(Op.CBNZ, rd=20, imm=16, regs={20: v})
    # B.cond: the full cond x flag-state product
    for cond in range(16):
        for nzcv in range(16):
            add(Op.BCOND, cond=cond, imm=-16, nzcv=nzcv)

    add(Op.LSLI, rd=21, rn=22, sh=0, regs={22: -1})
    for sh in (1, 31, 63):
        for a in EDGE:
            add(Op.LSLI, rd=21, rn=22, sh=sh, regs={22: a})

    # syscalls: every table row + unknown numbers, ptrace on and off
    for pt in (0, 1):
        for nr in list(opspec.TRACE_SYS) + [0, 1, 999, -1]:
            if nr in (L.SYS_READ, L.SYS_WRITE):
                continue  # the I/O grid below
            add(Op.SVC, regs={8: nr, 0: 55}, ptrace=pt, virt_getpid=0)
    for virt in (0, 1):
        for pt in (0, 1):
            add(Op.SVC, regs={8: L.SYS_GETPID}, ptrace=pt, virt_getpid=virt)
    # read/write: ok, bad pointer, misaligned, negative/odd length, huge
    io_grid = ((L.DATA_BASE + 64, 64), (L.DATA_BASE + 64, 0),
               (L.DATA_BASE + 63, 64), (L.DATA_BASE + 64, 63),
               (L.DATA_BASE + 64, -8), (L.MEM_LIMIT - 8, 16),
               (L.DATA_BASE - 8, 64), (L.DATA_BASE + 64, 1 << 40))
    for nr in (L.SYS_READ, L.SYS_WRITE):
        for buf, n in io_grid:
            mem = {_widx(L.DATA_BASE + 64) + j: 0x100 + j for j in range(8)}
            add(Op.SVC, regs={8: nr, 1: buf, 2: n}, mem=mem,
                in_off=0x999, ptrace=0)
            add(Op.SVC, regs={8: nr, 1: buf, 2: n}, mem=mem,
                in_off=0x999, ptrace=1)
    # sigreturn restores an arbitrary frame (incl. garbage nzcv)
    frame = {_SIGFRAME_IDX + i: 0x4000 + 17 * i for i in range(34)}
    frame[_SIGFRAME_IDX + 33] = s64(0xDEADBEEF00F3)  # nzcv garbage
    add(Op.SVC, regs={8: L.SYS_RT_SIGRETURN}, mem=frame, in_signal=1)
    add(Op.SVC, regs={8: L.SYS_RT_SIGRETURN}, mem=frame, in_signal=1,
        ptrace=1)

    return cases


# ---------------------------------------------------------------------------
# batched comparison through the generated executor
# ---------------------------------------------------------------------------

_BATCH = 128
_NOP_CASE = {"op": int(Op.NOP)}


def _batch_inputs(batch):
    B = len(batch)
    f = {k: np.zeros(B, np.int32)
         for k in ("op", "rd", "rn", "rm", "sh", "cond")}
    f["sf"] = np.ones(B, np.int32)
    imm = np.zeros(B, np.int64)
    lanes = [Lane(c) for c in batch]
    for b, c in enumerate(batch):
        for k in ("op", "rd", "rn", "rm", "sh", "cond"):
            f[k][b] = c.get(k, 0)
        f["sf"][b] = c.get("sf", 1)
        imm[b] = s64(c.get("imm", 0))
    st = M.MachineState(
        regs=jnp.asarray(np.stack([np.asarray(l.regs, np.int64)
                                   for l in lanes])),
        sp=jnp.asarray(np.asarray([l.sp for l in lanes], np.int64)),
        pc=jnp.asarray(np.asarray([l.pc for l in lanes], np.int64)),
        nzcv=jnp.asarray(np.asarray([l.nzcv for l in lanes], np.int64)),
        mem=jnp.asarray(np.stack([l.mem for l in lanes])),
        cycles=jnp.zeros(B, jnp.int64), icount=jnp.zeros(B, jnp.int64),
        fuel=jnp.full(B, 10**9, jnp.int64), halted=jnp.zeros(B, jnp.int64),
        exit_code=jnp.zeros(B, jnp.int64), fault_pc=jnp.zeros(B, jnp.int64),
        sig_handler=jnp.asarray(np.asarray([l.sig_handler for l in lanes],
                                           np.int64)),
        in_signal=jnp.asarray(np.asarray([l.in_signal for l in lanes],
                                         np.int64)),
        ptrace=jnp.asarray(np.asarray([l.ptrace for l in lanes], np.int64)),
        virt_getpid=jnp.asarray(np.asarray([l.virt_getpid for l in lanes],
                                           np.int64)),
        hook_count=jnp.zeros(B, jnp.int64),
        pid=jnp.full(B, L.PID, jnp.int64),
        in_off=jnp.asarray(np.asarray([l.in_off for l in lanes], np.int64)),
        out_count=jnp.zeros(B, jnp.int64), out_sum=jnp.zeros(B, jnp.int64),
        enosys_count=jnp.zeros(B, jnp.int64),
        emul_served=jnp.zeros(B, jnp.int64),
        # guest kernel disabled: the oracle transcribes the legacy
        # pre-emulation semantics (openat -> 3, close -> 0, new -> -ENOSYS)
        **emul_state.fresh_kern(B, enabled=False))
    fields = tuple(jnp.asarray(f[k]) for k in
                   ("op", "rd", "rn", "rm", "sh", "cond", "sf")) \
        + (jnp.asarray(imm),)
    return fields, st, lanes


@jax.jit
def _exec_batch(fields, st):
    out, _ = F.exec_lanes(fields, F.flat_planes(st), None,
                          act=jnp.ones(st.pc.shape, bool))
    return F.lane_planes(out)


_CHECK_FIELDS = ("regs", "sp", "pc", "nzcv", "mem", "cycles", "icount",
                 "halted", "exit_code", "fault_pc", "sig_handler",
                 "in_signal", "ptrace", "virt_getpid", "hook_count", "pid",
                 "in_off", "out_count", "out_sum", "enosys_count",
                 "emul_served")


def _assert_lane(case_i, case, got, want: Lane):
    exp = {"regs": np.asarray(want.regs, np.int64), "mem": want.mem}
    for k in _CHECK_FIELDS:
        if k in exp:
            e = exp[k]
        else:
            e = np.int64(getattr(want, k))
        g = np.asarray(getattr(got, k))
        assert np.array_equal(g, e), (
            f"case {case_i} op={Op(case['op']).name} field {k}: "
            f"generated={g!r} legacy={e!r} (case={case})")


def test_generated_executor_matches_legacy_oracle():
    """The committed sweep: every op x flag state x edge operand, generated
    executor vs the transcribed legacy handlers, all state bits compared."""
    cases = gen_cases()
    for lo in range(0, len(cases), _BATCH):
        batch = cases[lo:lo + _BATCH]
        batch = batch + [_NOP_CASE] * (_BATCH - len(batch))
        fields, st, lanes = _batch_inputs(batch)
        out = jax.tree_util.tree_map(np.asarray, _exec_batch(fields, st))
        for b, (case, lane) in enumerate(zip(batch, lanes)):
            got = jax.tree_util.tree_map(lambda x: x[b], out)
            oracle_step(case, lane)
            _assert_lane(lo + b, case, got, lane)


def test_scalar_step_matches_legacy_oracle():
    """Spot-check the generated scalar ``machine.step`` (one representative
    case per opcode) through the real fetch path."""
    per_op = {}
    for case in gen_cases():
        per_op.setdefault(case["op"], case)
    assert len(per_op) == int(Op.N_OPS)

    jstep = jax.jit(M.step)
    for case in per_op.values():
        lane = Lane(case)
        pc = lane.pc
        img_np = {k: np.zeros(L.CODE_WORDS, np.int32)
                  for k in ("op", "rd", "rn", "rm", "sh", "cond")}
        img_np["sf"] = np.ones(L.CODE_WORDS, np.int32)
        imm = np.zeros(L.CODE_WORDS, np.int64)
        w = pc >> 2
        for k in ("op", "rd", "rn", "rm", "sh", "cond"):
            img_np[k][w] = case.get(k, 0)
        img_np["sf"][w] = case.get("sf", 1)
        imm[w] = s64(case.get("imm", 0))
        img = M.DecodedImage(*(jnp.asarray(img_np[k]) for k in
                               ("op", "rd", "rn", "rm", "sh", "cond", "sf")),
                             imm=jnp.asarray(imm))
        st = M.make_state(pc, fuel=10**9)._replace(
            regs=jnp.asarray(np.asarray(lane.regs, np.int64)),
            sp=jnp.int64(lane.sp), nzcv=jnp.int64(lane.nzcv),
            mem=jnp.asarray(lane.mem),
            sig_handler=jnp.int64(lane.sig_handler),
            in_signal=jnp.int64(lane.in_signal),
            ptrace=jnp.int64(lane.ptrace),
            virt_getpid=jnp.int64(lane.virt_getpid),
            in_off=jnp.int64(lane.in_off),
            k_enabled=jnp.int64(0))  # legacy semantics for the oracle
        got = jstep(img, st)
        oracle_step(case, lane)
        _assert_lane(-1, case, got, lane)


# ---------------------------------------------------------------------------
# table-level checks
# ---------------------------------------------------------------------------

def test_cost_table_matches_legacy():
    assert np.array_equal(opspec.COST_TABLE_NP, _LEGACY_COSTS)
    assert np.array_equal(np.asarray(M.COST_TABLE), _LEGACY_COSTS)


def test_cond_mask_matches_legacy_predicates():
    """COND_MASK agrees with the Arm predicate trees for every cond, at
    every 4-bit flag state and at arbitrary (sigreturn-restored) int64
    nzcv values."""
    conds = np.arange(16)
    for nzcv in list(range(16)) + [s64(0xDEADBEEF00F3), -1, (1 << 63) - 1,
                                   -(1 << 63), 1 << 40]:
        got = np.asarray(opspec.cond_holds(jnp.int64(nzcv),
                                           jnp.asarray(conds)))
        want = np.asarray([legacy_cond_holds(nzcv, int(c)) for c in conds])
        assert np.array_equal(got, want), f"nzcv={nzcv}"


def test_specs_cover_every_op():
    assert set(opspec.SPECS) == {Op(i) for i in range(int(Op.N_OPS))}
    assert opspec.TRACE_SYS == (L.SYS_READ, L.SYS_WRITE, L.SYS_GETPID,
                                L.SYS_EXIT, L.SYS_RT_SIGRETURN,
                                L.SYS_OPENAT, L.SYS_CLOSE, L.SYS_LSEEK,
                                L.SYS_DUP, L.SYS_FSTAT, L.SYS_PIPE2,
                                L.SYS_GETRANDOM, L.SYS_IOCTL)
    assert opspec.slot_of(L.SYS_READ) == 0
    assert opspec.slot_of(L.SYS_IOCTL) == len(opspec.SYSCALLS) - 1
    assert opspec.slot_of(12345) == opspec.SLOT_UNKNOWN
    # the guest-kernel rows are flagged for EMULATE routing
    emul_nrs = {s.nr for s in opspec.SYSCALLS if s.emul}
    assert emul_nrs == {L.SYS_READ, L.SYS_WRITE, L.SYS_OPENAT, L.SYS_CLOSE,
                       L.SYS_LSEEK, L.SYS_DUP, L.SYS_FSTAT, L.SYS_PIPE2,
                       L.SYS_GETRANDOM, L.SYS_IOCTL}
