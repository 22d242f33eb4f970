"""The batched guest-kernel step: fd-table syscall service + data mover.

Called from the one shared executor body
(:func:`repro.core.fleet.exec_lanes`), so the XLA select-chain, the
Pallas megastep kernel and the generated scalar engine all inherit every
emulated syscall from this single implementation — exactly how the
op-spec table retired the per-engine instruction handlers.

The work is split in two, mirroring the executor's own split between
scalar effects and the memory-word loop:

* :func:`service` — the *control-plane* step: resolve fds through the
  per-lane tables, compute every errno / return value, and produce the
  updated small ``k_*`` leaves plus routing vectors for any bulk data
  movement.  Everything here is [B] / [B, MAX_FDS] / [B, MAX_INODES]
  vector math — no big-buffer access — and the whole call sits behind a
  batch-uniform ``lax.cond`` in the executor, so steps without an
  emulated syscall pay one ``jnp.any``.
* :func:`run_data_loop` — the *data-plane* step: behind a batch-uniform
  cond (no work when no lane moves data), it walks only the lanes that
  move data, K_KIO at a time, and transfers up to FILE_WORDS words per
  lane in W_KIO-word windows between guest memory, the inode data plane,
  the synthetic /proc window and the getrandom stream.  Its cost tracks
  the moving lanes and their words, not the fleet's width; the gathers
  from the planes stay inside the loop body, so XLA copies no plane.

Every transfer fits one FILE_WORDS window by construction: file and pipe
payloads are capped by the FILE_BYTES inode size, getrandom short-reads
to FILE_BYTES like the kernel short-reads to 256 bytes, and the /proc
window is PROC_WORDS long.
"""
from __future__ import annotations

from typing import NamedTuple

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import layout as L
from repro.emul.state import (ASC_IOCTL_HOOKS, ASC_IOCTL_ICOUNT,
                              ASC_IOCTL_PID, DEV_KEY, EAGAIN, EBADF, EEXIST,
                              EFAULT, EFBIG, EINVAL, EMFILE, ENFILE, ENOENT,
                              ENOSPC, ENOTTY, ESPIPE, FD_DEV, FD_FILE,
                              FD_FREE, FD_PIPE_R, FD_PIPE_W, FD_PROC,
                              FD_RSTREAM, FD_WSINK, INO_FILE, INO_FREE,
                              INO_PIPE, PROC_KEY, STAT_WORDS, KernelState,
                              kern_of)

I64 = jnp.int64
I32 = jnp.int32

_IPL = L.MAX_INODES * L.FILE_WORDS   # inode data words per lane

# splitmix64 finalizer constants (uint64 wrap-around arithmetic)
_SM_GAMMA = np.uint64(0x9E3779B97F4A7C15)
_SM_M1 = np.uint64(0xBF58476D1CE4E5B9)
_SM_M2 = np.uint64(0x94D049BB133111EB)


def splitmix64(x):
    """Deterministic 64-bit mix of an int64 counter — the getrandom
    stream.  Pure bit-cast uint64 arithmetic, so every engine (XLA,
    Pallas interpret, scalar lift) produces identical words."""
    z = lax.bitcast_convert_type(x, jnp.uint64) * _SM_GAMMA
    z = (z ^ (z >> np.uint64(30))) * _SM_M1
    z = (z ^ (z >> np.uint64(27))) * _SM_M2
    z = z ^ (z >> np.uint64(31))
    return lax.bitcast_convert_type(z, I64)


def _mem_ok(addr):
    return (addr >= L.DATA_BASE) & (addr < L.MEM_LIMIT) & ((addr & 7) == 0)


def _widx(addr):
    return jnp.clip((addr - L.DATA_BASE) >> 3, 0, L.MEM_WORDS - 1)


def _take(tab, idx):
    """Row-wise gather: ``tab[b, idx[b]]`` with idx pre-clipped."""
    return jnp.take_along_axis(tab, idx[:, None].astype(I32), axis=1)[:, 0]


def _onehot(idx, width):
    return jnp.arange(width)[None, :] == idx[:, None]


def _setcol(tab, mask, idx, val):
    """``tab[b, idx[b]] = val[b]`` where ``mask[b]`` (one-hot where)."""
    hit = _onehot(idx, tab.shape[1]) & mask[:, None]
    v = val if hasattr(val, "shape") and getattr(val, "ndim", 0) else \
        jnp.full(mask.shape, val, tab.dtype)
    return jnp.where(hit, v[:, None], tab)


class EmulEffects(NamedTuple):
    """Everything :func:`service` hands back to the executor."""

    kern: KernelState        # updated small k_* leaves (ino_data untouched)
    ret: jnp.ndarray         # [B] return value for emul-serviced lanes
    is_ret: jnp.ndarray      # [B] lanes whose x0 comes from ``ret``
    served: jnp.ndarray      # [B] lanes serviced by the guest kernel
    rd_stream: jnp.ndarray   # [B] reads taking the legacy stream path
    wr_stream: jnp.ndarray   # [B] writes taking the legacy sink path
    # bulk data-mover routing (consumed by run_data_loop)
    fio_do: jnp.ndarray      # [B] lanes with words to move
    nw: jnp.ndarray          # [B] words to move (<= FILE_WORDS)
    mem_base: jnp.ndarray    # [B] absolute word index into mem_flat
    ino_base: jnp.ndarray    # [B] absolute word index into ino_flat
    dst_is_mem: jnp.ndarray  # [B] True: fill guest memory; False: inode data
    src_is_ino: jnp.ndarray  # [B] source select (exactly one on fio lanes
    src_is_proc: jnp.ndarray  # [B]  with dst_is_mem; writes source memory)
    src_is_rand: jnp.ndarray  # [B]
    proc_base: jnp.ndarray   # [B] absolute word index into proc_flat
    rng0: jnp.ndarray        # [B] getrandom counter before this call
    # small guest-memory writes (fstat statbuf + pipe2 fd pair)
    scat_do: jnp.ndarray     # [B] any lane writing result words
    scat_idx: jnp.ndarray    # [6B] mem_flat indices (parked when unused)
    scat_val: jnp.ndarray    # [6B] values


def neutral(s, sys_read, sys_write) -> EmulEffects:
    """The no-emulated-syscall step: legacy routing, nothing changes.
    Must be bit-identical to :func:`service` on a batch where no lane
    executes an emulated operation (the executor's cond contract)."""
    B = s.pc.shape[0]
    zb = jnp.zeros((B,), bool)
    z = jnp.zeros((B,), I64)
    oob = jnp.int64(L.MEM_WORDS * B)
    return EmulEffects(
        kern=kern_of(s), ret=z, is_ret=zb, served=zb,
        rd_stream=sys_read, wr_stream=sys_write,
        fio_do=zb, nw=z, mem_base=z, ino_base=z, dst_is_mem=zb,
        src_is_ino=zb, src_is_proc=zb, src_is_rand=zb, proc_base=z,
        rng0=s.k_rng, scat_do=zb,
        scat_idx=oob + jnp.arange(6 * B, dtype=I64),
        scat_val=jnp.zeros((6 * B,), I64))


def service(s, *, en, x0, x1, x2, path_w, io_ok, io_n,
            sys_open, sys_close, sys_lseek, sys_dup, sys_fstat, sys_pipe,
            sys_rand, sys_ioctl, sys_read, sys_write) -> EmulEffects:
    """One guest-kernel step over the batch.

    ``sys_*`` masks are already gated on the executing-svc mask and (for
    the emulated families) on ``k_enabled``; ``sys_read``/``sys_write``
    are the raw I/O masks (enabled and legacy lanes both).  ``path_w`` is
    the first path word (read by the executor under its own cond),
    ``io_ok``/``io_n`` the legacy buffer check and byte count for
    read/write/getrandom argument validation.
    """
    B = s.pc.shape[0]
    k = kern_of(s)
    lanes = jnp.arange(B, dtype=I64)
    zero = jnp.zeros((B,), I64)
    false_b = jnp.zeros((B,), bool)
    lane_mem = lanes * L.MEM_WORDS
    lane_ino = lanes * _IPL
    lane_proc = lanes * L.PROC_WORDS

    # -- fd resolution (shared by close/dup/lseek/fstat/ioctl/read/write) --
    fd = x0
    fd_inr = (fd >= 0) & (fd < L.MAX_FDS)
    fdc = jnp.clip(fd, 0, L.MAX_FDS - 1)
    ofd = _take(k.fd_ofd, fdc)
    fd_valid = fd_inr & (ofd >= 0)
    ofdc = jnp.clip(ofd, 0, L.MAX_FDS - 1)
    okind = _take(k.ofd_kind, ofdc)
    oino = _take(k.ofd_ino, ofdc)
    ooff = _take(k.ofd_off, ofdc)
    oflags = _take(k.ofd_flags, ofdc)
    oref = _take(k.ofd_ref, ofdc)
    inoc = jnp.clip(oino, 0, L.MAX_INODES - 1)
    isize = _take(k.ino_size, inoc)

    # -- free-slot scans ---------------------------------------------------
    free_fd_m = k.fd_ofd < 0
    n_free_fd = jnp.sum(free_fd_m, axis=1)
    fd_a = jnp.argmax(free_fd_m, axis=1).astype(I64)
    fd_b_m = free_fd_m & ~_onehot(fd_a, L.MAX_FDS)
    fd_b = jnp.argmax(fd_b_m, axis=1).astype(I64)
    free_ofd_m = k.ofd_kind == FD_FREE
    n_free_ofd = jnp.sum(free_ofd_m, axis=1)
    ofd_a = jnp.argmax(free_ofd_m, axis=1).astype(I64)
    ofd_b_m = free_ofd_m & ~_onehot(ofd_a, L.MAX_FDS)
    ofd_b = jnp.argmax(ofd_b_m, axis=1).astype(I64)
    free_ino_m = k.ino_kind == INO_FREE
    has_ino = jnp.any(free_ino_m, axis=1)
    ino_a = jnp.argmax(free_ino_m, axis=1).astype(I64)

    # ======================================================================
    # openat(dirfd, path, flags)
    # ======================================================================
    pvalid = _mem_ok(x1)
    name = path_w
    is_proc = name == jnp.int64(PROC_KEY)
    is_dev = name == jnp.int64(DEV_KEY)
    is_file = ~is_proc & ~is_dev
    fmatch = (k.ino_kind == INO_FILE) & (k.ino_name == name[:, None])
    exists = jnp.any(fmatch, axis=1)
    ino_hit = jnp.argmax(fmatch, axis=1).astype(I64)
    o_creat = (x2 & L.O_CREAT) != 0
    o_excl = (x2 & L.O_EXCL) != 0
    o_trunc = (x2 & L.O_TRUNC) != 0
    need_create = is_file & ~exists
    open_err = jnp.select(
        [~pvalid,
         is_file & ~exists & ~o_creat,
         is_file & exists & o_creat & o_excl,
         n_free_fd < 1,
         n_free_ofd < 1,
         need_create & ~has_ino],
        [jnp.full((B,), -EFAULT, I64),
         jnp.full((B,), -ENOENT, I64),
         jnp.full((B,), -EEXIST, I64),
         jnp.full((B,), -EMFILE, I64),
         jnp.full((B,), -ENFILE, I64),
         jnp.full((B,), -ENOSPC, I64)],
        zero)
    open_ok = sys_open & (open_err == 0)
    open_ino = jnp.where(need_create, ino_a, ino_hit)
    open_kind = jnp.select([is_proc, is_dev],
                           [jnp.full((B,), FD_PROC, I64),
                            jnp.full((B,), FD_DEV, I64)],
                           jnp.full((B,), FD_FILE, I64))
    ret_open = jnp.where(open_ok, fd_a, open_err)
    do_create = open_ok & need_create
    do_trunc = open_ok & is_file & exists & o_trunc

    # ======================================================================
    # close(fd) / dup(fd)
    # ======================================================================
    close_ok = sys_close & fd_valid
    ret_close = jnp.where(fd_valid, zero, jnp.full((B,), -EBADF, I64))
    free_ofd_now = close_ok & (oref <= 1)

    dup_ok = sys_dup & fd_valid & (n_free_fd >= 1)
    ret_dup = jnp.select([~fd_valid, n_free_fd < 1],
                         [jnp.full((B,), -EBADF, I64),
                          jnp.full((B,), -EMFILE, I64)],
                         fd_a)

    # ======================================================================
    # lseek(fd, off, whence)
    # ======================================================================
    whence_ok = (x2 >= L.SEEK_SET) & (x2 <= L.SEEK_END)
    seek_new = jnp.select([x2 == L.SEEK_SET, x2 == L.SEEK_CUR],
                          [x1, ooff + x1], isize + x1)
    seek_err = jnp.select(
        [~fd_valid, okind != FD_FILE, ~whence_ok, seek_new < 0],
        [jnp.full((B,), -EBADF, I64), jnp.full((B,), -ESPIPE, I64),
         jnp.full((B,), -EINVAL, I64), jnp.full((B,), -EINVAL, I64)],
        zero)
    seek_ok = sys_lseek & (seek_err == 0)
    ret_seek = jnp.where(seek_ok, seek_new, seek_err)

    # ======================================================================
    # fstat(fd, statbuf) — writes STAT_WORDS result words
    # ======================================================================
    sbuf_ok = _mem_ok(x1) & (x1 + STAT_WORDS * 8 <= L.MEM_LIMIT)
    stat_size = jnp.select(
        [okind == FD_PROC,
         (okind == FD_PIPE_R) | (okind == FD_PIPE_W) | (okind == FD_FILE)],
        [jnp.full((B,), L.PROC_WORDS * 8, I64), isize], zero)
    stat_err = jnp.select([~fd_valid, ~sbuf_ok],
                          [jnp.full((B,), -EBADF, I64),
                           jnp.full((B,), -EFAULT, I64)], zero)
    stat_ok = sys_fstat & (stat_err == 0)
    ret_stat = jnp.where(stat_ok, zero, stat_err)

    # ======================================================================
    # pipe2(pipefd, flags) — writes the two fds, allocates 2 fds + 2 OFDs
    # + 1 pipe inode (pipe inodes are not reclaimed on close: a
    # documented leak that keeps close() branch-free; MAX_INODES bounds
    # the damage per lane)
    # ======================================================================
    pbuf_ok = _mem_ok(x0) & (x0 + 16 <= L.MEM_LIMIT)
    pipe_err = jnp.select(
        [x1 != 0, ~pbuf_ok, n_free_fd < 2, n_free_ofd < 2, ~has_ino],
        [jnp.full((B,), -EINVAL, I64), jnp.full((B,), -EFAULT, I64),
         jnp.full((B,), -EMFILE, I64), jnp.full((B,), -ENFILE, I64),
         jnp.full((B,), -ENOSPC, I64)],
        zero)
    pipe_ok = sys_pipe & (pipe_err == 0)
    ret_pipe = jnp.where(pipe_ok, zero, pipe_err)

    # ======================================================================
    # getrandom(buf, len, flags) — short-reads to FILE_BYTES
    # ======================================================================
    rand_n = jnp.clip(x1, 0, L.FILE_BYTES)
    rand_err = jnp.select(
        [(x1 < 0) | ((x1 & 7) != 0),
         ~(_mem_ok(x0) & (x0 + rand_n <= L.MEM_LIMIT))],
        [jnp.full((B,), -EINVAL, I64), jnp.full((B,), -EFAULT, I64)],
        zero)
    rand_ok = sys_rand & (rand_err == 0)
    ret_rand = jnp.where(rand_ok, rand_n, rand_err)

    # ======================================================================
    # ioctl(fd, req, arg) — the FD_DEV control surface
    # ======================================================================
    ioctl_val = jnp.select(
        [x1 == ASC_IOCTL_ICOUNT, x1 == ASC_IOCTL_HOOKS, x1 == ASC_IOCTL_PID],
        [s.icount, s.hook_count, s.pid],
        jnp.full((B,), -EINVAL, I64))
    ret_ioctl = jnp.select([~fd_valid, okind != FD_DEV],
                           [jnp.full((B,), -EBADF, I64),
                            jnp.full((B,), -ENOTTY, I64)], ioctl_val)

    # ======================================================================
    # read/write routing: stream (legacy), data (file/proc/pipe), dev
    # ======================================================================
    rd_stream = (sys_read & ~en) | (sys_read & en & fd_valid
                                    & (okind == FD_RSTREAM))
    wr_stream = (sys_write & ~en) | (sys_write & en & fd_valid
                                     & (okind == FD_WSINK))
    rd_en = sys_read & en
    wr_en = sys_write & en

    rd_data = rd_en & fd_valid & ((okind == FD_FILE) | (okind == FD_PROC)
                                  | (okind == FD_PIPE_R))
    rd_dev = rd_en & fd_valid & (okind == FD_DEV)
    rd_bad = rd_en & ~(rd_stream | rd_data | rd_dev)   # bad fd / wrong dir

    src_size = jnp.select(
        [okind == FD_PROC, okind == FD_FILE],
        [jnp.full((B,), L.PROC_WORDS * 8, I64), isize],
        isize)  # pipes: write position
    off_align = (ooff & 7) == 0
    rd_err = jnp.select([~io_ok, ~off_align],
                        [jnp.full((B,), -EFAULT, I64),
                         jnp.full((B,), -EINVAL, I64)], zero)
    rd_n = jnp.clip(jnp.minimum(io_n, src_size - ooff), 0, None)
    rd_data_ok = rd_data & (rd_err == 0)
    ret_read = jnp.where(rd_data, jnp.where(rd_err == 0, rd_n, rd_err),
                         jnp.where(rd_dev, zero,
                                   jnp.full((B,), -EBADF, I64)))

    wr_data = wr_en & fd_valid & ((okind == FD_FILE)
                                  | (okind == FD_PIPE_W))
    wr_dev = wr_en & fd_valid & (okind == FD_DEV)
    wr_bad = wr_en & ~(wr_stream | wr_data | wr_dev)

    w_is_pipe = okind == FD_PIPE_W
    w_off = jnp.where(w_is_pipe, isize,
                      jnp.where((oflags & L.O_APPEND) != 0, isize, ooff))
    w_end = w_off + io_n
    wr_err = jnp.select(
        [~io_ok,
         (w_off & 7) != 0,
         w_is_pipe & (w_end > L.FILE_BYTES),
         ~w_is_pipe & (w_end > L.FILE_BYTES)],
        [jnp.full((B,), -EFAULT, I64), jnp.full((B,), -EINVAL, I64),
         jnp.full((B,), -EAGAIN, I64), jnp.full((B,), -EFBIG, I64)],
        zero)
    wr_data_ok = wr_data & (wr_err == 0)
    dev_err = jnp.where(io_ok, io_n, jnp.full((B,), -EFAULT, I64))
    ret_write = jnp.where(wr_data, jnp.where(wr_err == 0, io_n, wr_err),
                          jnp.where(wr_dev, dev_err,
                                    jnp.full((B,), -EBADF, I64)))

    # ======================================================================
    # combined return value + masks
    # ======================================================================
    is_ret = (sys_open | sys_close | sys_lseek | sys_dup | sys_fstat
              | sys_pipe | sys_rand | sys_ioctl
              | rd_data | rd_dev | rd_bad | wr_data | wr_dev | wr_bad)
    ret = jnp.select(
        [sys_open, sys_close, sys_dup, sys_lseek, sys_fstat, sys_pipe,
         sys_rand, sys_ioctl,
         rd_data | rd_dev | rd_bad,
         wr_data | wr_dev | wr_bad],
        [ret_open, ret_close, ret_dup, ret_seek, ret_stat, ret_pipe,
         ret_rand, ret_ioctl, ret_read, ret_write],
        zero)
    served = is_ret | (rd_stream & en) | (wr_stream & en)

    # ======================================================================
    # table updates (one syscall per lane => row-disjoint one-hot writes)
    # ======================================================================
    fd_tab = k.fd_ofd
    fd_tab = _setcol(fd_tab, open_ok, fd_a, ofd_a)
    fd_tab = _setcol(fd_tab, close_ok, fdc, jnp.full((B,), -1, I64))
    fd_tab = _setcol(fd_tab, dup_ok, fd_a, ofd)
    fd_tab = _setcol(fd_tab, pipe_ok, fd_a, ofd_a)
    fd_tab = _setcol(fd_tab, pipe_ok, fd_b, ofd_b)

    okind_t = k.ofd_kind
    okind_t = _setcol(okind_t, open_ok, ofd_a, open_kind)
    okind_t = _setcol(okind_t, free_ofd_now, ofdc,
                      jnp.full((B,), FD_FREE, I64))
    okind_t = _setcol(okind_t, pipe_ok, ofd_a, jnp.full((B,), FD_PIPE_R, I64))
    okind_t = _setcol(okind_t, pipe_ok, ofd_b, jnp.full((B,), FD_PIPE_W, I64))

    oino_t = k.ofd_ino
    oino_t = _setcol(oino_t, open_ok, ofd_a, open_ino)
    oino_t = _setcol(oino_t, free_ofd_now, ofdc, zero)
    oino_t = _setcol(oino_t, pipe_ok, ofd_a, ino_a)
    oino_t = _setcol(oino_t, pipe_ok, ofd_b, ino_a)

    adv_rd = rd_data_ok
    adv_off = jnp.where(adv_rd, ooff + rd_n, zero)
    wr_adv = wr_data_ok & ~w_is_pipe      # pipe writes track ino_size only
    ooff_t = k.ofd_off
    ooff_t = _setcol(ooff_t, open_ok, ofd_a, zero)
    ooff_t = _setcol(ooff_t, free_ofd_now, ofdc, zero)
    ooff_t = _setcol(ooff_t, pipe_ok, ofd_a, zero)
    ooff_t = _setcol(ooff_t, pipe_ok, ofd_b, zero)
    ooff_t = _setcol(ooff_t, seek_ok, ofdc, seek_new)
    ooff_t = _setcol(ooff_t, adv_rd, ofdc, adv_off)
    ooff_t = _setcol(ooff_t, wr_adv, ofdc, w_end)

    oflags_t = k.ofd_flags
    oflags_t = _setcol(oflags_t, open_ok, ofd_a, x2)
    oflags_t = _setcol(oflags_t, free_ofd_now, ofdc, zero)
    oflags_t = _setcol(oflags_t, pipe_ok, ofd_a, zero)
    oflags_t = _setcol(oflags_t, pipe_ok, ofd_b, zero)

    oref_t = k.ofd_ref
    oref_t = _setcol(oref_t, open_ok, ofd_a, jnp.full((B,), 1, I64))
    oref_t = _setcol(oref_t, close_ok, ofdc, jnp.maximum(oref - 1, 0))
    oref_t = _setcol(oref_t, dup_ok, ofdc, oref + 1)
    oref_t = _setcol(oref_t, pipe_ok, ofd_a, jnp.full((B,), 1, I64))
    oref_t = _setcol(oref_t, pipe_ok, ofd_b, jnp.full((B,), 1, I64))

    ikind_t = k.ino_kind
    ikind_t = _setcol(ikind_t, do_create, ino_a, jnp.full((B,), INO_FILE, I64))
    ikind_t = _setcol(ikind_t, pipe_ok, ino_a, jnp.full((B,), INO_PIPE, I64))

    iname_t = k.ino_name
    iname_t = _setcol(iname_t, do_create, ino_a, name)
    iname_t = _setcol(iname_t, pipe_ok, ino_a, zero)

    isize_t = k.ino_size
    isize_t = _setcol(isize_t, do_create, ino_a, zero)
    isize_t = _setcol(isize_t, do_trunc, ino_hit, zero)
    isize_t = _setcol(isize_t, pipe_ok, ino_a, zero)
    isize_t = _setcol(isize_t, wr_data_ok, inoc,
                      jnp.where(w_is_pipe, w_end, jnp.maximum(isize, w_end)))

    rng_t = k.rng + jnp.where(rand_ok, rand_n >> 3, zero)

    # ======================================================================
    # data-mover routing
    # ======================================================================
    rd_words = rd_n >> 3
    wr_words = jnp.where(wr_data_ok, io_n >> 3, zero)
    rand_words = jnp.where(rand_ok, rand_n >> 3, zero)
    nw = jnp.select([rd_data_ok, wr_data_ok, rand_ok],
                    [rd_words, wr_words, rand_words], zero)
    fio_do = ((rd_data_ok & (rd_words > 0)) | (wr_data_ok & (wr_words > 0))
              | (rand_ok & (rand_words > 0)))
    dst_is_mem = rd_data_ok | rand_ok
    buf = jnp.where(sys_rand, x0, x1)
    mem_base = lane_mem + _widx(buf)
    data_off_w = jnp.where(wr_data, w_off, ooff) >> 3
    ino_base = lane_ino + inoc * L.FILE_WORDS \
        + jnp.clip(data_off_w, 0, L.FILE_WORDS - 1)
    src_is_proc = rd_data_ok & (okind == FD_PROC)
    src_is_ino = rd_data_ok & ~src_is_proc
    src_is_rand = rand_ok
    proc_base = lane_proc + jnp.clip(data_off_w, 0, L.PROC_WORDS - 1)

    # ======================================================================
    # result-word scatter (fstat statbuf / pipe2 fd pair), parked when off
    # ======================================================================
    oob = jnp.int64(L.MEM_WORDS * B)
    park = oob + jnp.arange(6 * B, dtype=I64)
    sbase = lane_mem + _widx(x1)
    pbase = lane_mem + _widx(x0)
    col = lambda m, base, j, v: (jnp.where(m, base + j, park[j * B:(j + 1) * B]), v)
    i0, v0 = col(stat_ok, sbase, 0, okind)
    i1, v1 = col(stat_ok, sbase, 1, oino)
    i2, v2 = col(stat_ok, sbase, 2, stat_size)
    i3, v3 = col(stat_ok, sbase, 3, jnp.ones((B,), I64))
    i4, v4 = (jnp.where(pipe_ok, pbase, park[4 * B:5 * B]), fd_a)
    i5, v5 = (jnp.where(pipe_ok, pbase + 1, park[5 * B:6 * B]), fd_b)
    scat_idx = jnp.concatenate([i0, i1, i2, i3, i4, i5])
    scat_val = jnp.concatenate([v0, v1, v2, v3, v4, v5])
    scat_do = stat_ok | pipe_ok

    kern = KernelState(
        enabled=k.enabled, rng=rng_t, fd_ofd=fd_tab, ofd_kind=okind_t,
        ofd_ino=oino_t, ofd_off=ooff_t, ofd_flags=oflags_t, ofd_ref=oref_t,
        ino_kind=ikind_t, ino_name=iname_t, ino_size=isize_t,
        ino_data=k.ino_data)
    return EmulEffects(
        kern=kern, ret=ret, is_ret=is_ret, served=served,
        rd_stream=rd_stream, wr_stream=wr_stream,
        fio_do=fio_do, nw=nw, mem_base=mem_base, ino_base=ino_base,
        dst_is_mem=dst_is_mem, src_is_ino=src_is_ino,
        src_is_proc=src_is_proc, src_is_rand=src_is_rand,
        proc_base=proc_base, rng0=k.rng, scat_do=scat_do,
        scat_idx=scat_idx, scat_val=scat_val)


def proc_rows(s) -> jnp.ndarray:
    """The synthetic /proc window, [B, PROC_WORDS]: live lane counters
    rendered as one word each (a numeric /proc/self/stat).  Regenerated
    from the carry every read, so checkpoints/recovery need no extra
    state and every engine sees identical content."""
    # word 0 mirrors getpid-level virtualisation: a lane whose pid is
    # virtualised must see the same identity through /proc (transparency)
    vpid = jnp.where(s.virt_getpid != 0, jnp.int64(L.VIRT_PID), s.pid)
    cols = [vpid, s.icount, s.cycles, s.hook_count, s.enosys_count,
            s.emul_served, s.in_off, s.out_count, s.out_sum, s.fuel]
    body = jnp.stack(cols, axis=1)
    pad = jnp.zeros((s.pc.shape[0], L.PROC_WORDS - len(cols)), I64)
    return jnp.concatenate([body, pad], axis=1)


W_KIO = 64    # data-mover window: words per lane per gather/scatter
# data-mover block: moving lanes per gather/scatter.  With blocks of 1,024
# lanes or fewer the TPU compiler re-places the decode table of a
# 4,096-lane step (an 8 MiB eviction and refetch every step, about 1% of a
# getpid step); with 2,048 the table is sliced into VMEM once a step.
K_KIO = 2048


def run_data_loop(mem_flat, ino_flat, proc_flat, eff: EmulEffects):
    """Move the data lanes' words, ``K_KIO`` lanes by ``W_KIO`` words at
    a time.

    Behind a batch-uniform ``lax.cond`` (zero work on steps where no lane
    moves data) the moving lanes are ordered first, by a stable argsort
    of ``fio_do``, and walked in blocks of ``K = min(B, K_KIO)``.  Each
    block gathers its lanes' routing, then runs ceil(its longest transfer
    / W_KIO) windows of one ``[K, W_KIO]`` masked gather and parked-index
    scatter into each plane.  A TPU scatter costs the same per index
    whether it lands or is parked, so the index count tracks the moving
    lanes, ceil(n_move / K) * K * W_KIO a window, not the fleet's width:
    at 4,096 lanes with 1,024 moving, half of a ``[B, W_KIO]`` window's.
    Blocks keep many lanes in one scatter: an earlier per-lane loop (one
    512-word slice per lane per iteration) was sequential in the number
    of moving lanes, and a census cell's lanes hit ``read`` in lockstep.  Windows are lane-private (fd buffers and inode regions
    never cross lanes), so live scatter indices are genuinely unique;
    masked words and the slots past the last mover park on distinct
    out-of-bounds ids and drop.  A fleet of at most ``K_KIO`` lanes (the
    served rungs, the scalar engine) is one block, walked in place with no
    ordering.  Returns ``(mem_flat, ino_flat)``.
    """
    B = eff.nw.shape[0]
    K = min(B, K_KIO)
    W = W_KIO
    woff = jnp.arange(W, dtype=I64)
    slots = jnp.arange(K, dtype=I64)
    MTOT = B * L.MEM_WORDS
    ITOT = B * _IPL
    PTOT = B * L.PROC_WORDS
    park_m = (jnp.int64(MTOT) + jnp.arange(K * W, dtype=I64)).reshape(K, W)
    park_i = (jnp.int64(ITOT) + jnp.arange(K * W, dtype=I64)).reshape(K, W)

    def windows(pick, live, inner):
        """Every window of one block: lane ``k`` of the block is the lane
        whose routing ``pick`` selects; ``live`` masks the block's slots."""
        nw = jnp.where(live, pick(eff.nw), jnp.int64(0))
        mem_base = pick(eff.mem_base)[:, None]
        ino_base = pick(eff.ino_base)[:, None]
        proc_base = pick(eff.proc_base)[:, None]
        src_is_rand = pick(eff.src_is_rand)[:, None]
        src_is_proc = pick(eff.src_is_proc)[:, None]
        to_mem = (live & pick(eff.dst_is_mem))[:, None]
        to_ino = (live & ~pick(eff.dst_is_mem))[:, None]
        rng = splitmix64(pick(eff.rng0) * jnp.int64(0x10001) + 1)

        def win_body(c, inner):
            mf, inf = inner
            rel = (c * W + woff)[None, :]                      # [1, W]
            within = rel < nw[:, None]                         # [K, W]
            # sources for guest-memory destinations (read/getrandom)
            v_ino = inf[jnp.clip(ino_base + rel, 0, ITOT - 1)]
            v_proc = proc_flat[jnp.clip(proc_base + rel, 0, PTOT - 1)]
            v_rand = splitmix64(rng[:, None] + rel)
            v = jnp.where(src_is_rand, v_rand,
                          jnp.where(src_is_proc, v_proc, v_ino))
            # source for inode destinations (write): the guest buffer —
            # gathered before the mem scatter below; a lane is either a
            # reader or a writer this step and windows are lane-private,
            # so the ordering cannot alias
            v_mem = mf[jnp.clip(mem_base + rel, 0, MTOT - 1)]
            idx_m = jnp.where(within & to_mem, mem_base + rel,
                              park_m).reshape(-1)
            idx_i = jnp.where(within & to_ino, ino_base + rel,
                              park_i).reshape(-1)
            mf = mf.at[idx_m].set(v.reshape(-1), mode="drop",
                                  unique_indices=True)
            inf = inf.at[idx_i].set(v_mem.reshape(-1), mode="drop",
                                    unique_indices=True)
            return mf, inf

        nwin = jnp.max((nw + W - 1) // W)
        return lax.fori_loop(jnp.int64(0), nwin, win_body, inner)

    def move(operands):
        if K == B:             # one block holds the fleet: walk it in place
            return windows(lambda x: x, eff.fio_do, operands)
        n_move = jnp.sum(eff.fio_do, dtype=I64)
        order = jnp.argsort(~eff.fio_do, stable=True)     # movers first

        def block_body(j, inner):
            slot = j * K + slots
            lane = order[jnp.minimum(slot, B - 1)]
            return windows(lambda x: x[lane], slot < n_move, inner)

        return lax.fori_loop(jnp.int64(0), (n_move + K - 1) // K,
                             block_body, operands)

    with jax.named_scope("data_loop"):
        return lax.cond(jnp.any(eff.fio_do), move, lambda o: o,
                        (mem_flat, ino_flat))
