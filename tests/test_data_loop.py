"""The guest kernel's data loop walks only the lanes that move data.

:func:`repro.emul.engine.run_data_loop` orders the moving lanes first and
walks them in blocks of ``K_KIO``.  These tests lower the block size so
that a small fleet spans several blocks, a partial last block and exactly
one full block, and hold the loop to the word-by-word transfer it
implements: directly against a plain model, and through whole guest
programs against the scalar engine, bit for bit on every field.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Mechanism, prepare, programs, run_fleet_prepared,
                        run_prepared, unstack_state)
from repro.core import isa
from repro.core import layout as L
from repro.core.image import APP_BASE
from repro.core.isa import Asm
from repro.emul import engine as E
from repro.emul.state import PROC_KEY, path_key

pytestmark = pytest.mark.emul

FUEL = 10_000
IPL = L.MAX_INODES * L.FILE_WORDS


@pytest.fixture
def block(monkeypatch):
    """Set ``K_KIO`` for one test; jitted programs are traced afresh on
    both sides, so no compile made with one block size serves another."""
    def set_k(k):
        jax.clear_caches()
        monkeypatch.setattr(E, "K_KIO", k)
    yield set_k
    monkeypatch.undo()
    jax.clear_caches()


# -- the loop against a word-by-word model -----------------------------------

def _effects(rng, B, movers):
    """Routing for ``B`` lanes where the lanes in ``movers`` move data:
    reads from an inode or /proc, getrandom fills and writes, 1 to
    FILE_WORDS words each, every window inside its own lane's region.
    Lanes that do not move carry routing too: ``fio_do`` alone decides."""
    fio = np.zeros(B, bool)
    fio[list(movers)] = True
    kind = rng.integers(0, 4, B)            # 0 ino read, 1 proc, 2 rand, 3 write
    nw = np.where(kind == 1, rng.integers(1, L.PROC_WORDS + 1, B),
                  rng.choice([1, 64, 100, 128, 129, 512], B))
    lanes = np.arange(B)
    mem_off = rng.integers(0, L.MEM_WORDS - L.FILE_WORDS, B)
    ino_off = rng.integers(0, L.MAX_INODES, B) * L.FILE_WORDS \
        + rng.integers(0, L.FILE_WORDS, B)
    ino_off = np.minimum(ino_off, IPL - nw)
    proc_off = np.minimum(rng.integers(0, L.PROC_WORDS, B),
                          L.PROC_WORDS - np.minimum(nw, L.PROC_WORDS))
    f = {f: None for f in E.EmulEffects._fields}
    f.update(
        fio_do=fio, nw=nw, mem_base=lanes * L.MEM_WORDS + mem_off,
        ino_base=lanes * IPL + ino_off,
        proc_base=lanes * L.PROC_WORDS + proc_off,
        dst_is_mem=kind < 3, src_is_ino=kind == 0, src_is_proc=kind == 1,
        src_is_rand=kind == 2, rng0=rng.integers(0, 2**40, B))
    return E.EmulEffects(**{k: v if v is None else jnp.asarray(v)
                            for k, v in f.items()})


def _model(mem, ino, proc, eff):
    mem, ino = mem.copy(), ino.copy()
    e = {k: np.asarray(v) for k, v in eff._asdict().items() if v is not None}
    for b in np.flatnonzero(e["fio_do"]):
        w = np.arange(e["nw"][b])
        m = e["mem_base"][b] + w
        if not e["dst_is_mem"][b]:
            ino[e["ino_base"][b] + w] = mem[m]
        elif e["src_is_rand"][b]:
            seed = e["rng0"][b] * 0x10001 + 1
            mem[m] = np.asarray(E.splitmix64(E.splitmix64(jnp.int64(seed))
                                             + jnp.asarray(w)))
        elif e["src_is_proc"][b]:
            mem[m] = proc[e["proc_base"][b] + w]
        else:
            mem[m] = ino[e["ino_base"][b] + w]
    return mem, ino


@pytest.mark.parametrize("k,movers", [
    (3, range(0, 11, 2)),       # six movers: two full blocks
    (4, (1, 2, 3, 5, 8, 9, 10)),  # seven movers: a partial last block
    (4, (0, 4, 7, 11)),         # exactly one full block
    (1, range(12)),             # every lane its own block
    (256, range(0, 12, 3)),     # wider than the fleet: one block of B
    (4, ()),                    # nothing moves
])
def test_data_loop_matches_word_model(block, k, movers):
    block(k)
    B = 12
    rng = np.random.default_rng(1600 + k + len(movers))
    mem = rng.integers(-2**62, 2**62, B * L.MEM_WORDS)
    ino = rng.integers(-2**62, 2**62, B * IPL)
    proc = rng.integers(-2**62, 2**62, B * L.PROC_WORDS)
    eff = _effects(rng, B, movers)
    got_mem, got_ino = jax.jit(E.run_data_loop)(
        jnp.asarray(mem), jnp.asarray(ino), jnp.asarray(proc), eff)
    want_mem, want_ino = _model(mem, ino, proc, eff)
    assert np.array_equal(np.asarray(got_mem), want_mem)
    assert np.array_equal(np.asarray(got_ino), want_ino)


# -- whole guests against the scalar engine -----------------------------------

BUF_A = L.HEAP_BASE
BUF_B = L.HEAP_BASE + 0x2000
PATH = L.HEAP_BASE + 0x4000


def _call(a, nr, x0=None, x1=0, x2=0, keep=None):
    """One syscall in a fixed twelve instructions, so that every lane of
    the fleet reaches its k-th call on the same step; ``x0=None`` passes
    the fd kept in x23, ``keep`` names a register for the return."""
    if x0 is None:
        a.emit(isa.mov_r(0, 23), isa.movz(9, 0), isa.movz(9, 0))
    else:
        a.emit(*isa.mov_imm48(0, x0))
    a.emit(*isa.mov_imm48(1, x1))
    a.emit(*isa.mov_imm48(2, x2))
    programs._raw(a, nr)
    a.emit(isa.movz(9, 0) if keep is None else isa.mov_r(keep, 0))


def _guest(kind: str, words: int = 0) -> Asm:
    """Six calls in lockstep.  ``rd``/``wr`` lanes fill a buffer from
    getrandom, write it to a file, seek back and then read it back or
    write it again; ``proc`` lanes read the /proc window; ``rand`` lanes
    fill twice; ``idle`` lanes move nothing.  Call 5 therefore moves
    reads, writes, a /proc read and a getrandom fill of 64, 128 and 512
    words on one step; calls 2 and 4 move nothing in any lane."""
    n = words * 8
    a = Asm(APP_BASE)
    a.label("main")
    a.emit(*isa.mov_imm48(24, PATH))
    a.emit(*programs._mov_imm64(25, PROC_KEY if kind == "proc"
                                else path_key(b"loop.dat")))
    a.emit(isa.str_imm(25, 24))
    filer = kind in ("rd", "wr")
    idle = lambda: _call(a, L.SYS_GETPID)
    # 1: fill the buffer
    (_call(a, L.SYS_GETRANDOM, BUF_A, n) if filer or kind == "rand"
     else idle())
    # 2: open
    if filer:
        _call(a, L.SYS_OPENAT, 0, PATH, L.O_CREAT | L.O_TRUNC, keep=23)
    elif kind == "proc":
        _call(a, L.SYS_OPENAT, 0, PATH, 0, keep=23)
    else:
        idle()
    # 3: write the file
    _call(a, L.SYS_WRITE, None, BUF_A, n) if filer else idle()
    # 4: seek back
    _call(a, L.SYS_LSEEK, None, 0, L.SEEK_SET) if filer else idle()
    # 5: read and write, /proc and getrandom, on one step
    if kind == "rd":
        _call(a, L.SYS_READ, None, BUF_B, n, keep=20)
    elif kind == "wr":
        _call(a, L.SYS_WRITE, None, BUF_B, n, keep=20)
    elif kind == "proc":
        _call(a, L.SYS_READ, None, BUF_B, L.PROC_WORDS * 8, keep=20)
    elif kind == "rand":
        _call(a, L.SYS_GETRANDOM, BUF_B, n, keep=20)
    else:
        _call(a, L.SYS_GETPID, keep=20)
    # 6: nothing moves
    idle()
    programs._exit0(a)
    return a


# 12 file lanes, 2 /proc lanes, 1 getrandom lane and 2 idle ones: call 5
# moves 15 lanes (four blocks of 4, the last partial, or one of exactly
# 15), call 1 moves 13 and call 3 moves 12 (three full blocks of 4)
FLEET = ([(kind, w) for kind in ("rd", "wr") for w in (64, 128, 512)] * 2
         + [("proc", 0), ("rand", 128), ("idle", 0), ("proc", 0),
            ("idle", 0)])


@pytest.mark.parametrize("k", [4, 15])
def test_data_loop_fleet_parity(block, k):
    block(k)
    pps = [prepare(_guest(kind, w), Mechanism.NONE) for kind, w in FLEET]
    refs = [run_prepared(pp, fuel=FUEL) for pp in pps]
    out = run_fleet_prepared(pps, fuel=FUEL, chunk=8)
    for i, ref in enumerate(refs):
        lane = unstack_state(out, i)
        for field in ref._fields:
            assert np.array_equal(np.asarray(getattr(ref, field)),
                                  np.asarray(getattr(lane, field))), \
                (FLEET[i], field)
    # every lane ran the same instructions per call, so the calls met on
    # their steps; and the transfers happened
    assert len({int(r.icount) for r in refs}) == 1
    assert all(int(r.exit_code) == 0 and int(r.enosys_count) == 0
               for r in refs)
    for (kind, w), ref in zip(FLEET, refs):
        want = {"rd": w * 8, "wr": w * 8, "proc": L.PROC_WORDS * 8,
                "rand": w * 8, "idle": None}[kind]
        if want is not None:
            assert int(ref.regs[20]) == want, kind  # call 5's return
