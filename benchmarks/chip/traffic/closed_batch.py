"""A closed batch of long-running lanes: the generator and its run loop.

The pool's lanes are split evenly over the configuration's mechanisms x
workloads.  Each lane's loop count x19 is drawn from the seed in
``[x19_min, 2 * x19_min)``, far beyond what set-up plus the longest
window could execute, so every seed gives the same work: all lanes run
through the whole window, and no lane is admitted or published in it.
"""
from __future__ import annotations

import time

import numpy as np


def generate(mix: dict, config: dict, seed: int, lanes: int) -> list:
    """``[(mechanism, workload, x19), ...]``, one per lane."""
    rng = np.random.default_rng(seed)
    combos = [(m, w) for m in config["mechanisms"]
              for w in config["workloads"]]
    if lanes % len(combos):
        raise ValueError(f"{lanes} lanes do not split evenly over "
                         f"{len(combos)} mechanism x workload pairs")
    picks = [combos[i % len(combos)] for i in range(lanes)]
    order = rng.permutation(lanes)
    x19 = rng.integers(mix["x19_min"], 2 * mix["x19_min"], lanes)
    return [(*picks[order[i]], int(x19[i])) for i in range(lanes)]


def drive(h) -> dict:
    srv, pps, mix = h.srv, h.pps, h.mix
    lanes = generate(mix, h.config, h.seed, srv.pool)
    spec = {}
    for m, w, x19 in lanes:
        rid = srv.submit(pps[(m, w)], regs={19: x19}, fuel=mix["fuel"])
        spec[rid] = (m, w, x19)
    warm = 0
    for _ in range(mix["warmup_generations"]):
        warm += len(srv.step())
    published = 0
    x19_begin = loop_counts(srv)
    h.window_begins()
    gens = 0
    while True:
        with h.span("step"):
            published += len(srv.step())
        gens += 1
        h.maybe_stop_trace()
        if time.perf_counter() - h.t_window >= h.seconds:
            break
    h.window_ends()
    h.drained()
    # loop iterations (one getpid each) run in the window, per mechanism;
    # nothing is admitted or published in it, so each lane keeps its request
    calls = {}
    for rid, n in loop_counts(srv).items():
        m = spec[rid][0]
        calls[m] = calls.get(m, 0) + x19_begin[rid] - n
    return {"spec": spec, "generations": gens, "published_in_window":
            published, "published_in_warmup": warm, "attempted": len(spec),
            "counts": {"loop_iterations": calls}}


def loop_counts(srv) -> dict:
    """``{rid: x19}`` of every running lane: the loop counter, which counts
    down once per iteration."""
    x19 = np.asarray(srv._states.regs[:, 19])
    out = {}
    for p in range(srv._W):
        req = srv._slots[srv._order[p]]
        if req is not None:
            out[req.rid] = int(x19[p])
    return out


FIELDS = ("halted", "exit_code", "pid", "hook_count", "in_off", "out_count",
          "out_sum", "emul_served", "enosys_count", "icount")


def check(h, out) -> dict:
    """Numbers compared, each ``name: (value, limit)``: lanes admitted or
    published inside the window; lane-steps the window's generations should
    have run and did not; lanes whose mid-flight state no point of the
    plain reference's run shows; sampled lanes that differ from the scalar
    engine run for the same number of steps."""
    import compare as C
    import reference as R
    srv, spec = h.srv, out["spec"]
    d = h.window_delta
    expected = srv._W * srv.gen_steps * out["generations"]
    st = srv._states
    cols = {f: np.asarray(getattr(st, f)) for f in FIELDS}
    regs = np.asarray(st.regs[:, 19:21])
    mem = np.asarray(st.mem[:, :R.SCRATCH_WORD + 1])
    ino_kind = np.asarray(st.k_ino_kind[:, 0])
    ino_size = np.asarray(st.k_ino_size[:, 0])
    lane_rid = {}
    for p in range(srv._W):
        req = srv._slots[srv._order[p]]
        if req is not None:
            lane_rid[p] = req.rid
    model_bad = []
    for p, rid in lane_rid.items():
        m, w, x19 = spec[rid]
        obs = {f: int(cols[f][p]) for f in FIELDS}
        obs.update(x19=int(regs[p, 0]), x20=int(regs[p, 1]),
                   counter=int(mem[p, R.COUNTER_WORD]),
                   scratch=int(mem[p, R.SCRATCH_WORD]),
                   file_bytes=int(ino_size[p]) if ino_kind[p] == C.INO_FILE
                   else 0)
        if R.midflight(w, m, x19, obs):
            model_bad.append(rid)
    rng = np.random.default_rng(h.seed)
    by_mech = {}
    for p, rid in sorted(lane_rid.items()):
        by_mech.setdefault(spec[rid][0], []).append(p)
    sample = []
    for m in sorted(by_mech):
        k = min(len(by_mech[m]), h.mix["scalar_sample_per_mechanism"])
        sample += rng.choice(by_mech[m], k, replace=False).tolist()
    lanes = {p: h.to_host(h.lane_state(p)) for p in sample}
    h.free_server()
    scalar_bad = []
    for p, lane in lanes.items():
        m, w, x19 = spec[lane_rid[p]]
        ref = C.scalar_state(h.ref_pps[(m, w)], regs={19: x19},
                             fuel=h.mix["fuel"], steps=int(lane.icount))
        if C.scalar_mismatch(lane, ref):
            scalar_bad.append(lane_rid[p])
    return {
        "published_in_warmup": (out["published_in_warmup"], 0),
        "admitted_in_window": (d["admission_waits"], 0),
        "published_in_window": (out["published_in_window"] + d["completed"],
                                0),
        "lane_steps_short": (expected - d["executed_steps"], 0),
        "lanes_running": (len(lane_rid), None),
        # a lane of the batch that no longer runs its request is wrong too
        "reference_mismatch": (len(model_bad) + h.config["pool"]
                               - len(lane_rid), 0),
        "scalar_mismatch": (len(scalar_bad), 0),
        "scalar_sampled": (len(sample), None),
    }

