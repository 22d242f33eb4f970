"""Plain reference for the four I/O loops seen mid-flight: what a process
that never exits has shown after any number of its syscalls.

A long-running lane holds thousands of records, so replaying
:func:`reference._run` per lane does not fit a run.  Every iteration of
these loops issues the same syscalls with the same arguments and returns
(each loop's iteration repeats in everything but the input stream's
offset), so the reference takes one iteration from ``_run`` and builds
the rows of any number of them in closed form with numpy; a unit test
ties that form to ``_run`` call by call.

The effects of a call follow the ABI :mod:`reference` documents: a
``read`` of fd 3 takes bytes from the input stream (the heap's words then
hold their stream offsets), a ``write`` of fd 1 goes to the sink (which
counts bytes and sums the words), an ``openat`` with ``O_TRUNC`` empties
the one file a loop uses and a ``write`` to it sets its size.  Like
:mod:`reference` it imports nothing of the program under test.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from reference import NBYTES, NR, O_TRUNC, RUNNING, _run, hooks

STREAM_FD, SINK_FD = 3, 1
FIRST_FILE_FD = 4            # fds 0..3 are preopened
# a raw trace row: step, pc, nr, x0, x1, x2, ret, verdict; the reference
# speaks for the last six
ROW_COLS = slice(2, 8)
RET = 4                      # column of ``ret`` in a reference row
RING_RECORD_BYTES = 64       # one trace-ring record: 8 int64 words

_SIGRETURN = NR["rt_sigreturn"]


class Loop:
    """One workload's loop under one mechanism: its iteration's rows and
    the effect of each of its syscalls."""

    def __init__(self, workload: str, mechanism: str):
        if workload not in NBYTES:
            raise KeyError(f"no I/O loop {workload!r}")
        self.mechanism = mechanism
        one, _ = _run(workload, mechanism, 1)
        two, _ = _run(workload, mechanism, 2)
        r = len(two.records) - len(one.records)
        unit = np.array(two.records[:r], np.int64).reshape(r, 6)
        if not np.array_equal(unit, np.array(two.records[r:2 * r])):
            raise ValueError(f"{workload}: iterations differ in their rows")
        self.rows = unit                                   # [R, 6]
        is_call = unit[:, 0] != _SIGRETURN
        # syscalls among a prefix of p rows of an iteration
        self.calls_in = np.concatenate([[0], np.cumsum(is_call)])
        calls = unit[is_call]
        nr, fd, ret = calls[:, 0], calls[:, 1], calls[:, RET]
        io = np.isin(nr, (NR["read"], NR["write"])) & (ret > 0)
        self.d_in = np.where((nr == NR["read"]) & (fd == STREAM_FD), ret, 0)
        self.d_out = np.where((nr == NR["write"]) & (fd == SINK_FD), ret, 0)
        self.payload = np.where(io, ret, 0)
        # each sink write sums the heap, filled by the iteration's latest
        # stream read: (words written, stream offset of that read within
        # the iteration) per write
        self.sums = []
        for j in np.flatnonzero(self.d_out):
            src = [i for i in range(j) if self.d_in[i]]
            if not src:
                raise ValueError(f"{workload}: a sink write reads no "
                                 f"stream data of its iteration")
            self.sums.append((j, int(ret[j]) // 8,
                              int(self.d_in[:src[-1]].sum())))
        # the file's size after each syscall of an iteration
        size, self.file_after = 0, []
        for n, f, rv, x2 in zip(nr, fd, ret, calls[:, 3]):
            if n == NR["openat"] and rv >= 0 and x2 & O_TRUNC:
                size = 0
            elif n == NR["write"] and f >= FIRST_FILE_FD and rv > 0:
                size = int(rv)
            self.file_after.append(size)

    # -- counts of a run cut after k records -----------------------------------
    def syscalls(self, records: int) -> int:
        q, p = divmod(int(records), len(self.rows))
        return q * int(self.calls_in[-1]) + int(self.calls_in[p])

    def expected_rows(self, records: int) -> np.ndarray:
        """The first ``records`` rows of the run, ``int64[records, 6]``."""
        reps = -(-int(records) // len(self.rows))
        return np.tile(self.rows, (reps, 1))[:records]

    def _part(self, d: np.ndarray, s: int) -> int:
        q, p = divmod(s, len(d))
        return q * int(d.sum()) + int(d[:p].sum())

    def payload_bytes(self, records: int) -> int:
        """Syscall data the calls among the first ``records`` rows move:
        stream and sink transfers and file reads and writes."""
        return self._part(self.payload, self.syscalls(records))

    def state(self, s: int) -> Dict[str, int]:
        """The observables other than the hook counts after ``s``
        syscalls (every one served by the guest kernel)."""
        q, p = divmod(s, len(self.d_in))
        u_in = int(self.d_in.sum())
        out_sum = 0
        for j, w, off in self.sums:
            n = q + (1 if j < p else 0)
            # sum over iterations t < n of the words t*u_in + off + 8i
            out_sum += w * u_in * n * (n - 1) // 2 \
                + n * (w * off + 4 * w * (w - 1))
        file_bytes = self.file_after[p - 1] if p else \
            (self.file_after[-1] if q else 0)
        return {"in_off": self._part(self.d_in, s),
                "out_count": self._part(self.d_out, s),
                "out_sum": out_sum,
                "emul_served": s,
                "enosys_count": 0, "file_bytes": file_bytes,
                "halted": RUNNING}


_LOOPS: Dict[Tuple[str, str], Loop] = {}


def loop(workload: str, mechanism: str) -> Loop:
    key = (workload, mechanism)
    if key not in _LOOPS:
        _LOOPS[key] = Loop(workload, mechanism)
    return _LOOPS[key]


def midflight(workload: str, mechanism: str, x19_start: int, obs: dict,
              rows: np.ndarray) -> List[str]:
    """Observables of a still-running lane that no point of the reference's
    run shows.  ``rows`` are the raw trace rows the lane has delivered
    (``int64[k, 8]``), ``obs`` holds ``x19``, ``records_made`` (the lane's
    lifetime record count on the device) and the fields of
    :meth:`Loop.state`; returns the names of the fields that disagree."""
    lp = loop(workload, mechanism)
    k = len(rows)
    bad = []
    if obs["records_made"] != k:
        bad.append("records_made")
    if not np.array_equal(np.asarray(rows)[:, ROW_COLS],
                          lp.expected_rows(k)):
        bad.append("records")
    # x19 counts down once an iteration's syscalls have all been issued
    done = x19_start - obs["x19"]
    r = len(lp.rows)
    if not (0 <= done < x19_start and r * done <= k <= r * (done + 1)):
        bad.append("x19")
    s = lp.syscalls(k)
    bad += [f for f, v in lp.state(s).items() if obs[f] != v]
    # a hook counts a syscall before issuing it, so the next one's may
    # already be counted (these loops issue no getpid)
    lo, hi = hooks(mechanism, s, 0), hooks(mechanism, s + 1, 0)
    bad += [f for i, f in enumerate(("counter", "hook_count"))
            if not lo[i] <= obs[f] <= hi[i]]
    return bad
