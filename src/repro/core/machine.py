"""A pre-decoded AArch64 machine in JAX.

Programs (application text + libraries + every trampoline level) are decoded
once, host-side, into structure-of-arrays field tables covering the whole
executable region ``[0, CODE_LIMIT)``.  The machine ``step`` is *generated*
from the op-spec table (:mod:`repro.core.opspec`): it lifts the lane to a
width-1 batch and runs the same spec-driven executor body as the fleet and
Pallas engines (:func:`repro.core.fleet.exec_lanes`) — there is no separate
hand-written scalar interpreter to keep in sync.  ``run`` is a
``lax.while_loop``.  Table and memory shapes are fixed by the layout, so
*one* XLA compilation serves every program, every rewrite variant and every
interception mechanism in the test suite and benchmarks.

The machine also embeds the modelled kernel: syscall dispatch on ``x8``
(Linux arm64 numbers), signal delivery for ``brk``/illegal instructions, the
``rt_sigreturn`` path, and an optional ptrace mode.  OS-boundary costs come
from :mod:`repro.core.costmodel` via the spec table's cost column.
"""
from __future__ import annotations

from typing import NamedTuple

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
from jax import lax

from . import layout as L
from . import opspec
from .isa import Op, decode

I64 = jnp.int64
I32 = jnp.int32

# halted codes
RUNNING = 0
HALT_EXIT = 1
HALT_SEGV = 2
HALT_TRAP = 3  # brk/illegal with no handler registered
HALT_FUEL = 4
HALT_BADMEM = 5
HALT_KILL = 6  # terminated by a seccomp-style KILL policy (fleet/serve only)

SIGFRAME_WORDS = 34  # x0..x30, sp, pc, nzcv
_SIGFRAME_IDX = (L.SIGFRAME - L.DATA_BASE) // 8


class DecodedImage(NamedTuple):
    """SoA decode tables over [0, CODE_LIMIT)."""

    op: jnp.ndarray   # int32[CODE_WORDS]
    rd: jnp.ndarray
    rn: jnp.ndarray
    rm: jnp.ndarray
    sh: jnp.ndarray
    cond: jnp.ndarray
    sf: jnp.ndarray
    imm: jnp.ndarray  # int64[CODE_WORDS]


class MachineState(NamedTuple):
    regs: jnp.ndarray  # int64[31]
    sp: jnp.ndarray
    pc: jnp.ndarray
    nzcv: jnp.ndarray  # int64 bitfield N=8 Z=4 C=2 V=1
    mem: jnp.ndarray   # int64[MEM_WORDS]
    cycles: jnp.ndarray
    icount: jnp.ndarray
    fuel: jnp.ndarray
    halted: jnp.ndarray
    exit_code: jnp.ndarray
    fault_pc: jnp.ndarray
    sig_handler: jnp.ndarray  # 0 = none
    in_signal: jnp.ndarray
    ptrace: jnp.ndarray
    virt_getpid: jnp.ndarray
    hook_count: jnp.ndarray   # tracer-side hook invocations (ptrace mode)
    pid: jnp.ndarray
    in_off: jnp.ndarray       # modelled input-stream position (read)
    out_count: jnp.ndarray    # modelled output effects (write)
    out_sum: jnp.ndarray
    enosys_count: jnp.ndarray  # syscalls that fell through to -ENOSYS
    emul_served: jnp.ndarray   # syscalls serviced by the guest kernel
    # -- guest-kernel emulation carry (repro.emul) -------------------------
    # Flat ``k_``-prefixed leaves rather than a nested pytree: every fleet
    # mechanism (admission, compaction, checkpoints, sharding, snapshots,
    # megastep refs) iterates MachineState._fields generically, so flat
    # leaves ride all of them for free.  repro.emul.state.KernelState is
    # the typed view.
    k_enabled: jnp.ndarray    # per-lane emulation gate (0 = legacy stubs)
    k_rng: jnp.ndarray        # getrandom counter state
    k_fd_ofd: jnp.ndarray     # int64[MAX_FDS]: open-file-description id, -1 free
    k_ofd_kind: jnp.ndarray   # int64[MAX_FDS]: emul.state.FD_* kind
    k_ofd_ino: jnp.ndarray    # int64[MAX_FDS]: backing inode id
    k_ofd_off: jnp.ndarray    # int64[MAX_FDS]: file offset in bytes
    k_ofd_flags: jnp.ndarray  # int64[MAX_FDS]: open(2) flags (O_APPEND...)
    k_ofd_ref: jnp.ndarray    # int64[MAX_FDS]: fd refcount (dup sharing)
    k_ino_kind: jnp.ndarray   # int64[MAX_INODES]: emul.state.INO_* kind
    k_ino_name: jnp.ndarray   # int64[MAX_INODES]: first 8 path bytes
    k_ino_size: jnp.ndarray   # int64[MAX_INODES]: size / pipe write pos, bytes
    k_ino_data: jnp.ndarray   # int64[MAX_INODES * FILE_WORDS] data words


def decode_image(code_words: np.ndarray) -> DecodedImage:
    """Host-side linear decode of the full executable region."""
    assert code_words.shape == (L.CODE_WORDS,)
    op = np.full(L.CODE_WORDS, int(Op.ILLEGAL), np.int32)
    rd = np.zeros(L.CODE_WORDS, np.int32)
    rn = np.zeros(L.CODE_WORDS, np.int32)
    rm = np.zeros(L.CODE_WORDS, np.int32)
    sh = np.zeros(L.CODE_WORDS, np.int32)
    cond = np.zeros(L.CODE_WORDS, np.int32)
    sf = np.ones(L.CODE_WORDS, np.int32)
    imm = np.zeros(L.CODE_WORDS, np.int64)
    for i in range(L.CODE_WORDS):
        w = int(code_words[i])
        if i < L.NULL_END // 4:
            op[i] = int(Op.NULLPAGE)  # the unmapped null page
            continue
        if w == 0:
            continue  # stays ILLEGAL (also the paper's "illegal instruction")
        d = decode(w)
        op[i], rd[i], rn[i], rm[i] = int(d.op), d.rd, d.rn, d.rm
        sh[i], cond[i], sf[i], imm[i] = d.sh, d.cond, d.sf, d.imm
    return DecodedImage(*(jnp.asarray(a) for a in (op, rd, rn, rm, sh, cond, sf, imm)))


# Per-op base cycle costs, indexed by Op value — the spec table's cost
# column (kept under the historical name for the many importers).
COST_TABLE = opspec.COST_TABLE


def make_state(entry_pc: int, fuel: int = 2_000_000) -> MachineState:
    # deferred: emul.state imports only layout, but keep core importable
    # without pulling the emul package at module-load time
    from repro.emul import state as emul_state

    z = jnp.int64(0)
    return MachineState(
        regs=jnp.zeros(31, jnp.int64),
        sp=jnp.int64(L.STACK_TOP),
        pc=jnp.int64(entry_pc),
        nzcv=z,
        mem=jnp.zeros(L.MEM_WORDS, jnp.int64),
        cycles=z, icount=z, fuel=jnp.int64(fuel),
        halted=z, exit_code=z, fault_pc=z,
        sig_handler=z, in_signal=z, ptrace=z, virt_getpid=z,
        hook_count=z, pid=jnp.int64(L.PID), in_off=z, out_count=z, out_sum=z,
        enosys_count=z, emul_served=z,
        **emul_state.fresh_kern_scalar(),
    )


# ---------------------------------------------------------------------------
# the generated scalar step
# ---------------------------------------------------------------------------

def _lift(x):
    return x[None]


def step(img: DecodedImage, s: MachineState) -> MachineState:
    """One instruction, unconditionally (``_run``'s while-cond is the only
    halt gate, as it always was).

    Generated from the op-spec table: the lane is lifted to a width-1
    batch and executed by the same spec-driven body as the fleet and
    Pallas engines (:func:`repro.core.fleet.exec_lanes`), with the
    live-lane mask forced all-true to match the legacy unconditional
    scalar semantics.  ``tests/test_opspec.py`` carries the
    legacy-vs-generated bit-exactness sweep that retired the hand-written
    per-op handlers.
    """
    from . import fleet as F  # deferred: fleet imports this module at load

    ok_fetch = (s.pc >= 0) & (s.pc < L.CODE_LIMIT) & ((s.pc & 3) == 0)
    idx = jnp.clip(s.pc >> 2, 0, L.CODE_WORDS - 1)
    op = jnp.where(ok_fetch, img.op[idx], jnp.int32(int(Op.NULLPAGE)))
    fields = tuple(_lift(a) for a in
                   (op, img.rd[idx], img.rn[idx], img.rm[idx], img.sh[idx],
                    img.cond[idx], img.sf[idx], img.imm[idx]))
    sb = F.flat_planes(jax.tree_util.tree_map(_lift, s))
    out, _ = F.exec_lanes(fields, sb, None, act=jnp.ones((1,), bool))
    return jax.tree_util.tree_map(lambda x: x[0], F.lane_planes(out))


def _run(img: DecodedImage, s: MachineState) -> MachineState:
    def cond(s):
        return (s.halted == RUNNING) & (s.icount < s.fuel)

    s = lax.while_loop(cond, lambda s: step(img, s), s)
    return s._replace(halted=jnp.where(
        (s.halted == RUNNING) & (s.icount >= s.fuel), jnp.int64(HALT_FUEL), s.halted))


# The scalar entry point deliberately does NOT donate: callers (tests,
# completeness re-exec) reuse their input state across runs, and
# ``make_state`` aliases one zero scalar across many fields — donation would
# invalidate both.  The fleet entry points (fleet.run_fleet) donate instead:
# stacked lane states are freshly materialised, single-consumer buffers.
run = jax.jit(_run)


def run_image(img: DecodedImage, state: MachineState) -> MachineState:
    """Run to halt (or out of fuel) and block until done."""
    out = run(img, state)
    return jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)


# -- host-side convenience ----------------------------------------------------

def mem_read(state: MachineState, addr: int) -> int:
    assert addr % 8 == 0 and L.DATA_BASE <= addr < L.MEM_LIMIT
    return int(state.mem[(addr - L.DATA_BASE) // 8])


def mem_read_block(state: MachineState, addr: int, nwords: int) -> np.ndarray:
    """Read ``nwords`` consecutive words in ONE device->host transfer.

    ``mem_read`` in a loop forces a device sync per word; census and
    benchmark code reading counters/buffers should use this instead.
    """
    assert addr % 8 == 0 and L.DATA_BASE <= addr < L.MEM_LIMIT
    i0 = (addr - L.DATA_BASE) // 8
    assert nwords >= 0 and i0 + nwords <= L.MEM_WORDS
    return np.asarray(state.mem[i0:i0 + nwords])


def mem_write(state: MachineState, addr: int, value: int) -> MachineState:
    assert addr % 8 == 0 and L.DATA_BASE <= addr < L.MEM_LIMIT
    return state._replace(mem=state.mem.at[(addr - L.DATA_BASE) // 8].set(jnp.int64(value)))
