"""Fleet/scalar parity: the batched engine must be BIT-identical, per lane,
to the scalar interpreter — for every mechanism, across workloads, and for
any chunk size (chunking changes dispatch count, never results)."""
import numpy as np
import pytest

from repro.core import (Mechanism, prepare, programs, run_fleet_prepared,
                        run_prepared, unstack_state)
from repro.core import isa
from repro.core import layout as L
from repro.core.image import APP_BASE
from repro.core.isa import Asm
from repro.emul.state import STAT_WORDS

FUEL = 300_000

MECHS = [Mechanism.NONE, Mechanism.LD_PRELOAD, Mechanism.ASC,
         Mechanism.SIGNAL, Mechanism.PTRACE]

# >= 3 workloads, chosen to cover every interpreter path: trampolines (ASC),
# signal delivery + sigreturn (SIGNAL / R3 sites), ptrace stops, syscall
# I/O fill & sum loops, byte ops, pair loads/stores, indirect jumps.
PROGS = {
    "getpid": lambda: programs.getpid_loop(20),
    "read": lambda: programs.read_loop(4, 256),
    "mixed": lambda: programs.mixed_ops(3, 128),
    "io_bw": lambda: programs.io_bandwidth(3, 4096),
    "retry": lambda: programs.retry_loop(2),
    "caller_x8": lambda: programs.caller_x8(3),
}


def _grid():
    pps, keys = [], []
    for mech in MECHS:
        for name, builder in PROGS.items():
            for virt in ([True, False] if mech is not Mechanism.NONE
                         else [False]):
                pps.append(prepare(builder(), mech, virtualize=virt))
                keys.append((mech.value, name, virt))
    return pps, keys


@pytest.fixture(scope="module")
def grid():
    pps, keys = _grid()
    refs = [run_prepared(pp, fuel=FUEL) for pp in pps]
    return pps, keys, refs


def _assert_lane_equal(ref, lane, key):
    for field in ref._fields:
        a = np.asarray(getattr(ref, field))
        b = np.asarray(getattr(lane, field))
        assert np.array_equal(a, b), (
            f"lane {key}: field {field!r} diverged "
            f"(scalar {a if a.ndim == 0 else 'array'}, "
            f"fleet {b if b.ndim == 0 else 'array'})")


def test_fleet_matches_scalar_bit_exact(grid):
    """Every mechanism x workload x virtualize lane: full-state equality,
    including the entire memory image, cycles, icount and hook effects."""
    pps, keys, refs = grid
    out = run_fleet_prepared(pps, fuel=FUEL, chunk=8)
    for i, (key, ref) in enumerate(zip(keys, refs)):
        _assert_lane_equal(ref, unstack_state(out, i), key)


@pytest.mark.parametrize("chunk", [1, 64])
def test_chunk_size_never_changes_results(grid, chunk):
    """K in {1, 8, 64}: identical lane results (8 covered above); only the
    number of loop-condition evaluations may differ."""
    pps, keys, refs = grid
    out = run_fleet_prepared(pps, fuel=FUEL, chunk=chunk)
    for i, (key, ref) in enumerate(zip(keys, refs)):
        _assert_lane_equal(ref, unstack_state(out, i), key)


def test_fleet_fuel_exhaustion_matches_scalar():
    """A lane that runs out of fuel mid-flight halts with HALT_FUEL at the
    exact same icount/cycles as the scalar engine."""
    from repro.core import HALT_FUEL
    pp = prepare(programs.getpid_loop(1000), Mechanism.ASC, virtualize=True)
    ref = run_prepared(pp, fuel=500)
    out = run_fleet_prepared([pp, pp], fuel=500, chunk=8)
    assert int(ref.halted) == HALT_FUEL
    for lane in range(2):
        _assert_lane_equal(ref, unstack_state(out, lane), f"fuel-lane{lane}")


def test_param_workloads_share_one_image_and_match_scalar():
    """Parameterised workloads (count in x19, seeded via reg overrides):
    all lanes share one decode table, and each lane is bit-identical to the
    scalar engine run with the same override."""
    from repro.core import pack_fleet
    pp = prepare(programs.getpid_loop_param(), Mechanism.ASC, virtualize=True)
    counts = [5, 9, 13]
    regs = [{19: n} for n in counts]
    imgs, ids, _ = pack_fleet([pp] * 3, regs=regs)
    assert imgs.packed.shape[0] == 1  # one image serves every lane
    out = run_fleet_prepared([pp] * 3, fuel=FUEL, regs=regs)
    for i, n in enumerate(counts):
        ref = run_prepared(pp, fuel=FUEL, regs={19: n})
        _assert_lane_equal(ref, unstack_state(out, i), f"param-getpid-{n}")
    # the parameter actually takes effect: hook counts differ per lane
    from repro.core import fleet
    assert fleet.fleet_counters(out).tolist() == [n + 1 for n in counts]


def test_image_dedup_shares_tables():
    """pack_fleet ships one decode table per distinct image."""
    from repro.core import pack_fleet
    pp1 = prepare(programs.getpid_loop(10), Mechanism.ASC, virtualize=True)
    pp2 = prepare(programs.getpid_loop(10), Mechanism.ASC, virtualize=True)
    pp3 = prepare(programs.getpid_loop(20), Mechanism.ASC, virtualize=True)
    imgs, ids, states = pack_fleet([pp1, pp2, pp3])
    assert imgs.packed.shape[0] == 2  # pp1/pp2 share, pp3 differs
    assert list(ids) == [0, 0, 1]
    assert states.pc.shape[0] == 3


# -- lane edges of the flat word plane ----------------------------------------
#
# The fleet addresses every lane's memory as one flat [B * MEM_WORDS] plane
# (lane b's words start at b * MEM_WORDS).  Each case drives one writer of
# that plane at a lane's first and last words, with per-lane values (x19),
# so an address that is one row off lands in a neighbour lane and shows as
# a field mismatch against the scalar engine.

EDGE_LANES = 3
_LAST = L.MEM_LIMIT - 8


def _imm(a, rd, value):
    a.emit(*isa.mov_imm48(rd, value))


def _edge_stores():
    """str/strb/stp at the first and last words; the final pair's second
    word clips at MEM_LIMIT (its first word lands, the lane faults)."""
    a = Asm(APP_BASE)
    a.label("main")
    a.emit(isa.addi(20, 19, 1))
    _imm(a, 21, L.DATA_BASE)
    a.emit(isa.str_imm(19, 21))
    _imm(a, 22, L.MEM_LIMIT - 16)
    a.emit(isa.stp(19, 20, 22))
    a.emit(isa.ldp(24, 25, 22))
    _imm(a, 23, L.MEM_LIMIT - 1)
    a.emit(isa.strb(20, 23))
    _imm(a, 23, _LAST)
    a.emit(isa.stp(20, 19, 23))      # second word past MEM_LIMIT
    programs._exit0(a)
    return a


def _edge_results():
    """A pipe2 fd pair into the first two words, fstat of its read end into
    the last STAT_WORDS words, then x19 pipe2 pairs into the last two
    words, so the final fds differ per lane."""
    a = Asm(APP_BASE)
    a.label("main")
    _imm(a, 0, L.DATA_BASE)
    a.emit(isa.movz(1, 0))
    programs._raw(a, L.SYS_PIPE2)
    _imm(a, 21, L.DATA_BASE)
    a.emit(isa.ldr_imm(0, 21))       # the read end just written
    _imm(a, 1, L.MEM_LIMIT - STAT_WORDS * 8)
    programs._raw(a, L.SYS_FSTAT)
    a.label("loop")
    _imm(a, 0, L.MEM_LIMIT - 16)
    a.emit(isa.movz(1, 0))
    programs._raw(a, L.SYS_PIPE2)
    a.emit(isa.subsi(19, 19, 1))
    a.b_to("loop", cond="ne")
    programs._exit0(a)
    return a


def _edge_stream(nbytes=4120):
    """Stream reads whose io-mover windows end at the row's end (the second
    512-word window overhangs it) and start at its first word, then a sink
    write that sums the last words; x19 short reads first shift the fill."""
    a = Asm(APP_BASE)
    a.label("main")
    _imm(a, 21, L.MEM_LIMIT - nbytes)
    a.label("loop")
    a.emit(isa.movz(0, 3), isa.mov_r(1, 21), isa.movz(2, 8))
    a.bl_to("libc.so:read")
    a.emit(isa.subsi(19, 19, 1))
    a.b_to("loop", cond="ne")
    a.emit(isa.movz(0, 3), isa.mov_r(1, 21))
    _imm(a, 2, nbytes)
    a.bl_to("libc.so:read")
    a.emit(isa.movz(0, 3))
    _imm(a, 1, L.DATA_BASE)
    _imm(a, 2, nbytes)
    a.bl_to("libc.so:read")
    a.emit(isa.movz(0, 1), isa.mov_r(1, 21))
    _imm(a, 2, nbytes)
    a.bl_to("libc.so:write")
    programs._exit0(a)
    return a


def _edge_data():
    """Guest-kernel data loop: a file written from the lane's first words,
    read back into its last FILE_BYTES, then x19 getrandom fills of the
    last 8 words."""
    a = Asm(APP_BASE)
    a.label("main")
    _imm(a, 21, L.DATA_BASE)
    a.emit(isa.str_imm(19, 21))
    _imm(a, 24, L.HEAP_BASE + 2048)
    programs._store_path(a, 24, 25, b"edge.dat")
    a.emit(isa.movz(0, 0), isa.mov_r(1, 24))
    _imm(a, 2, L.O_CREAT)
    programs._raw(a, L.SYS_OPENAT)
    a.emit(isa.mov_r(23, 0))
    a.emit(isa.mov_r(1, 21))
    _imm(a, 2, L.FILE_BYTES)
    a.bl_to("libc.so:write")
    a.emit(isa.mov_r(0, 23), isa.movz(1, 0), isa.movz(2, L.SEEK_SET))
    programs._raw(a, L.SYS_LSEEK)
    a.emit(isa.mov_r(0, 23))
    _imm(a, 1, L.MEM_LIMIT - L.FILE_BYTES)
    _imm(a, 2, L.FILE_BYTES)
    a.bl_to("libc.so:read")
    a.label("loop")
    _imm(a, 0, L.MEM_LIMIT - 64)
    a.emit(isa.movz(1, 64), isa.movz(2, 0))
    programs._raw(a, L.SYS_GETRANDOM)
    a.emit(isa.subsi(19, 19, 1))
    a.b_to("loop", cond="ne")
    programs._exit0(a)
    return a


EDGE_CASES = {
    "store_pair_clip": (_edge_stores, Mechanism.NONE),
    "sigframe_push_and_sigreturn": (programs.getpid_loop_param,
                                    Mechanism.SIGNAL),
    "fstat_pipe2_result_words": (_edge_results, Mechanism.NONE),
    "io_mover_row_end": (_edge_stream, Mechanism.NONE),
    "data_loop_row_end": (_edge_data, Mechanism.NONE),
}


@pytest.mark.parametrize("case", sorted(EDGE_CASES))
def test_flat_plane_lane_edges_match_scalar(case):
    """Three lanes writing at their rows' first and last words: every field
    of every lane equals the scalar engine bit for bit."""
    builder, mech = EDGE_CASES[case]
    pp = prepare(builder(), mech, virtualize=True)
    regs = [{19: i + 1} for i in range(EDGE_LANES)]
    refs = [run_prepared(pp, fuel=FUEL, regs=r) for r in regs]
    out = run_fleet_prepared([pp] * EDGE_LANES, fuel=FUEL, regs=regs)
    for i, ref in enumerate(refs):
        assert int(ref.icount) > 0
        _assert_lane_equal(ref, unstack_state(out, i), (case, i))
