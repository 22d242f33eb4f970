"""What decides ``correct``: the program's published results against the
plain reference (:mod:`reference`), and a sample of lanes against the
program's own scalar engine bit for bit.

Every comparison is exact, so each number compared has the limit 0: a
count of requests or lanes that disagree.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import reference as R

INO_FILE = 1


def observe(state, records=None, histogram=None) -> dict:
    """The reference's observables read from one lane's machine state
    (host numpy leaves or device arrays) and, for a published traced
    request, its decoded records and histogram."""
    g = lambda f: np.asarray(getattr(state, f))
    regs, mem = g("regs"), g("mem")
    ino_kind, ino_size = g("k_ino_kind"), g("k_ino_size")
    out = {
        "halted": int(g("halted")), "exit_code": int(g("exit_code")),
        "pid": int(g("pid")), "x19": int(regs[19]), "x20": int(regs[20]),
        "scratch": int(mem[R.SCRATCH_WORD]),
        "counter": int(mem[R.COUNTER_WORD]),
        "hook_count": int(g("hook_count")), "in_off": int(g("in_off")),
        "out_count": int(g("out_count")), "out_sum": int(g("out_sum")),
        "emul_served": int(g("emul_served")),
        "enosys_count": int(g("enosys_count")),
        "file_bytes": int(ino_size[0]) if int(ino_kind[0]) == INO_FILE else 0,
    }
    if records is not None:
        out["records"] = [(r.nr, r.x0, r.x1, r.x2, r.ret, r.verdict)
                          for r in records]
    if histogram is not None:
        out["histogram"] = {k: dict(v) for k, v in histogram.items()}
    return out


def finished_mismatch(workload: str, mechanism: str, n: int,
                      obs: dict) -> List[str]:
    """Fields of a published request that differ from the reference;
    records and histogram are compared only where ``obs`` carries them."""
    want = R.finished(workload, mechanism, n)
    return [k for k in want if k in obs and obs[k] != want[k]]


def scalar_mismatch(lane, ref) -> List[str]:
    """MachineState fields of a lane that differ from the scalar engine's
    state (both as host numpy trees), every field, ``mem`` included."""
    return [f for f, a, b in zip(lane._fields, lane, ref)
            if not np.array_equal(np.asarray(a), np.asarray(b))]


def scalar_state(pp, *, regs: Dict[int, int], fuel: int,
                 steps: Optional[int] = None):
    """The program's scalar engine (``run_prepared``) on one request, run to
    its end or, with ``steps``, for exactly that many steps; as host numpy.
    A run cut at ``steps`` halts on fuel: its ``fuel`` and ``halted`` words
    are set back to the lane's, so the two compare field for field."""
    import jax
    from repro.core import run_prepared
    if steps is None:
        return jax.device_get(run_prepared(pp, fuel=fuel, regs=regs))
    ref = jax.device_get(run_prepared(pp, fuel=steps, regs=regs))
    return ref._replace(fuel=np.int64(fuel), halted=np.int64(R.RUNNING))
