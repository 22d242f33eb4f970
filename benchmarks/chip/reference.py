"""Plain reference: what a guest process of the benchmark's workloads is
seen to do, written from the workloads' documented behaviour alone.

The model works at the level a user of an interception service observes:
the syscalls a process issues (number, the three argument registers, the
return value), the values it leaves behind (exit code, the loop registers,
the word it stores at SCRATCH), the effects of its I/O on the modelled
streams and files, and how many hook invocations each interception
mechanism counts.  It imports nothing of the program under test and takes
nothing the program made (no image, no decode table, no state): only the
request's workload name, mechanism and iteration count.

Guest conventions it encodes (the ABI of the simulated machine):

* fd 3 is an input stream whose byte ``b`` belongs to the 64-bit word of
  value ``b`` at that word's first byte (reads fill ``in_off + 8*j``);
  fd 1 is an output sink that counts bytes and sums the words written.
* fds 0..3 are preopened, so the first file a process opens gets fd 4.
* ``openat`` with a NULL path returns -EFAULT; any call on a negative fd
  returns -EBADF.
* Hook invocations: ASC and SIGNAL count every syscall (exit included) in
  the hook's COUNTER word; PTRACE counts every syscall in the tracer's
  ``hook_count``; LD_PRELOAD wraps only ``getpid``; NONE hooks nothing.
* The trace shows each syscall as the kernel executed it.  A syscall
  that reaches the hook through SIGSYS (every syscall but ``exit`` under
  SIGNAL; under ASC the raw-``svc`` sites whose x8 the caller sets, which
  static rewriting cannot prove) is followed by an ``rt_sigreturn``
  record carrying the restored x0.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

PID = 4242
DATA_BASE = 0x40000                  # guest memory words start here
COUNTER_WORD = (0x40008 - DATA_BASE) // 8   # the hook's invocation counter
SCRATCH_WORD = (0x40010 - DATA_BASE) // 8   # where workloads store a result
HEAP = 0x48000
PATH_BUF = HEAP + 2048
O_CREAT, O_TRUNC = 0o100, 0o1000
EFAULT, EBADF = 14, 9
HALT_EXIT, RUNNING = 1, 0

NR = {"read": 63, "write": 64, "getpid": 172, "exit": 93,
      "rt_sigreturn": 139, "openat": 56, "close": 57, "lseek": 62}
NAME = {v: k for k, v in NR.items()}

# per workload: the buffer size each iteration moves
NBYTES = {"read_loop_param": 1024, "io_bandwidth_param": 4096,
          "mixed_ops_param": 512, "file_churn_param": 512}
# syscalls issued through the raw svc stub (caller-set x8)
RAW_SVC = {"file_churn_param": ("openat", "lseek")}

Record = Tuple[int, int, int, int, int, int]   # nr, x0, x1, x2, ret, verdict


class _Guest:
    """Register file x0..x2 plus the observable effects of one process."""

    def __init__(self, workload: str, mechanism: str):
        self.workload, self.mechanism = workload, mechanism
        self.x = [0, 0, 0]
        self.records: List[Record] = []
        self.syscalls = 0
        self.getpids = 0
        self.emul = 0
        self.in_off = 0
        self.out_count = 0
        self.out_sum = 0

    def _via_signal(self, name: str) -> bool:
        if name == "exit":
            return False
        if self.mechanism == "SIGNAL":
            return True
        return (self.mechanism == "ASC"
                and name in RAW_SVC.get(self.workload, ()))

    def call(self, name: str, args, ret: int) -> int:
        for i, a in enumerate(args):
            self.x[i] = a
        self.records.append((NR[name], *self.x, ret, 0))
        self.syscalls += 1
        if name == "getpid":
            self.getpids += 1
        elif name != "exit":
            self.emul += 1
        self.x[0] = ret
        if self._via_signal(name):
            self.records.append((NR["rt_sigreturn"], *self.x, ret, 0))
        return ret

    def read_stream(self, nbytes: int) -> int:
        start = self.in_off
        self.in_off += nbytes
        self.heap = (start, nbytes)        # words hold their byte offsets
        return nbytes

    def write_sink(self, nbytes: int) -> None:
        start, _ = self.heap
        words = range(start, start + nbytes, 8)
        self.out_count += nbytes
        self.out_sum += sum(words)


def _run(workload: str, mechanism: str, n: int) -> Tuple[_Guest, dict]:
    assert n >= 1, n
    g = _Guest(workload, mechanism)
    regs = {19: 0, 20: 0}
    scratch = 0
    files: Dict[str, int] = {}
    if workload == "getpid_loop_param":
        for _ in range(n):
            regs[20] = g.call("getpid", (), PID)
        scratch = regs[20]
    elif workload == "read_loop_param":
        nb = NBYTES[workload]
        for _ in range(n):
            g.call("read", (3, HEAP, nb), g.read_stream(nb))
        g.write_sink(nb)
        g.call("write", (1, HEAP, nb), nb)
    elif workload == "io_bandwidth_param":
        nb = NBYTES[workload]
        for _ in range(n):
            g.call("read", (3, HEAP, nb), g.read_stream(nb))
            g.write_sink(nb)
            g.call("write", (1, HEAP, nb), nb)
    elif workload == "mixed_ops_param":
        nb = NBYTES[workload]
        for _ in range(n):
            fd = g.call("openat", (0, 0, 0), -EFAULT)
            g.call("read", (fd, HEAP, nb), -EBADF)
            g.call("write", (fd, HEAP, nb), -EBADF)
            g.call("close", (fd,), -EBADF)
    elif workload == "file_churn_param":
        nb = NBYTES[workload]
        for _ in range(n):
            fd = g.call("openat", (0, PATH_BUF, O_CREAT | O_TRUNC), 4)
            g.call("write", (fd, HEAP, nb), nb)
            g.call("lseek", (fd, 0, 0), 0)
            regs[20] = g.call("read", (fd, HEAP, nb), nb)
            g.call("close", (fd,), 0)
            files["churn.da"] = nb
        scratch = regs[20]
    else:
        raise KeyError(f"no reference for workload {workload!r}")
    g.call("exit", (0,), 0)
    return g, {"regs": regs, "scratch": scratch, "files": files}


def hooks(mechanism: str, syscalls: int, getpids: int) -> Tuple[int, int]:
    """(COUNTER word, tracer hook_count) after the given syscalls."""
    if mechanism in ("ASC", "SIGNAL"):
        return syscalls, 0
    if mechanism == "PTRACE":
        return 0, syscalls
    if mechanism == "LD_PRELOAD":
        return getpids, 0
    return 0, 0


def finished(workload: str, mechanism: str, n: int) -> dict:
    """Every observable of a process of ``workload`` run to its exit with
    ``n`` iterations under ``mechanism``."""
    g, extra = _run(workload, mechanism, n)
    counter, hook_count = hooks(mechanism, g.syscalls, g.getpids)
    hist: Dict[str, Dict[str, int]] = {}
    for r in g.records:
        row = hist.setdefault(NAME[r[0]], {})
        row["ALLOW"] = row.get("ALLOW", 0) + 1
    return {
        "halted": HALT_EXIT, "exit_code": 0, "pid": PID,
        "x19": 0, "x20": extra["regs"][20], "scratch": extra["scratch"],
        "counter": counter, "hook_count": hook_count,
        "in_off": g.in_off, "out_count": g.out_count, "out_sum": g.out_sum,
        "emul_served": g.emul, "enosys_count": 0,
        "file_bytes": extra["files"].get("churn.da", 0),
        "records": g.records, "histogram": hist,
    }


def midflight(workload: str, mechanism: str, x19_start: int,
              obs: dict) -> List[str]:
    """Fields of a still-running process that no point of the reference's
    own run would show, given how far its loop counter says it got.
    ``obs`` holds the observables of ``finished`` (records aside) read from
    the lane; returns the names of the fields that disagree."""
    if workload != "getpid_loop_param":
        raise KeyError(f"no mid-flight reference for workload {workload!r}")
    done = x19_start - obs["x19"]          # loop iterations completed
    bad = []
    if not 0 <= done < x19_start:
        return ["x19"]
    want = {"halted": RUNNING, "exit_code": 0, "pid": PID, "scratch": 0,
            "in_off": 0, "out_count": 0, "out_sum": 0, "emul_served": 0,
            "enosys_count": 0, "file_bytes": 0}
    for k, v in want.items():
        if obs[k] != v:
            bad.append(k)
    # x20 takes the pid just before the counter drops
    if obs["x20"] not in ((PID,) if done else (0, PID)):
        bad.append("x20")
    # the getpid of the next iteration may already have been hooked
    lo = hooks(mechanism, done, done)
    hi = hooks(mechanism, done + 1, done + 1)
    for i, k in enumerate(("counter", "hook_count")):
        if not lo[i] <= obs[k] <= hi[i]:
            bad.append(k)
    return bad
