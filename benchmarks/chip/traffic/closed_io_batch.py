"""A closed batch of long-running monitored I/O lanes: the generator and
its run loop.

The pool's lanes are split evenly over the configuration's workloads
(and mechanisms), in an order drawn from the seed.  Each lane's loop
count x19 is drawn from the seed in ``[x19_min, 2 * x19_min)``, far
beyond what set-up plus the longest window could execute, so every lane
runs through the whole window and none is admitted or published in it.
Every syscall is traced through the zero-drop stream, which keeps each
lane's records for as long as it runs: the check reads them all back.
"""
from __future__ import annotations

import os
import time

import numpy as np

import harness

# the same lanes as the getpid batch: even split, seeded order and x19
generate = harness.load_module("traffic", "closed_batch.py").generate


def lanes(srv) -> dict:
    """``{rid: physical lane}`` of every running lane."""
    out = {}
    for p in range(srv._W):
        req = srv._slots[srv._order[p]]
        if req is not None:
            out[req.rid] = p
    return out


def host_rss_bytes():
    """The process's resident size, or None where ``/proc`` has none."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return None


def snapshot(srv, spec: dict) -> dict:
    """What the window's two ends are compared by: per workload, the sums
    of each lane's loop iterations, input-stream offset, sink bytes,
    records made and the syscall data they moved; the stream's counters;
    the host's resident size."""
    import reference_io as RI
    st = srv._states
    x19 = np.asarray(st.regs[:, 19])
    in_off = np.asarray(st.in_off)
    out_count = np.asarray(st.out_count)
    made = np.asarray(srv._trace.count)
    per = {}
    for rid, p in lanes(srv).items():
        m, w, x0 = spec[rid]
        d = per.setdefault(w, dict.fromkeys(
            ("iterations", "in_off", "out_count", "records",
             "payload_bytes"), 0))
        d["iterations"] += x0 - int(x19[p])
        d["in_off"] += int(in_off[p])
        d["out_count"] += int(out_count[p])
        d["records"] += int(made[p])
        d["payload_bytes"] += RI.loop(w, m).payload_bytes(int(made[p]))
    s = srv.stats()["stream"]
    return {"lanes": per,
            "stream": {k: s.get(k, 0) for k in ("records_seen", "flips",
                                                "buffered_records")},
            "host_rss_bytes": host_rss_bytes()}


def drive(h) -> dict:
    srv, pps, mix = h.srv, h.pps, h.mix
    spec = {}
    for m, w, x19 in generate(mix, h.config, h.seed, srv.pool):
        rid = srv.submit(pps[(m, w)], regs={19: x19}, fuel=mix["fuel"])
        spec[rid] = (m, w, x19)
    warm = 0
    for _ in range(mix["warmup_generations"]):
        warm += len(srv.step())
    published = 0
    begin = snapshot(srv, spec)
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
    end = snapshot(srv, spec)
    lane_sums = {w: {k: end["lanes"][w][k] - v for k, v in d.items()}
                 for w, d in begin["lanes"].items()}
    window = {"lanes": lane_sums,
              "payload_bytes": sum(d["payload_bytes"]
                                   for d in lane_sums.values()),
              "ring_records": sum(d["records"] for d in lane_sums.values()),
              "stream": {k: end["stream"][k] - v
                         for k, v in begin["stream"].items()}}
    return {"spec": spec, "generations": gens, "published_in_window":
            published, "published_in_warmup": warm, "attempted": len(spec),
            "counts": {"window": window,
                       "ends": {"begin": begin, "end": end}}}


FIELDS = ("halted", "in_off", "out_count", "out_sum", "emul_served",
          "enosys_count", "hook_count")


def check(h, out) -> dict:
    """Numbers compared, each ``name: (value, limit)``: lanes admitted or
    published inside the window; lane-steps the window's generations should
    have run and did not; lanes whose records or state no point of the
    plain reference's run shows (every lane, every record it delivered);
    records dropped by the ring or the stream; sampled lanes that differ
    from the scalar engine run for the same number of steps."""
    import compare as C
    import reference as R
    import reference_io as RI
    srv, spec = h.srv, out["spec"]
    d = h.window_delta
    expected = srv._W * srv.gen_steps * out["generations"]
    st = srv._states
    cols = {f: np.asarray(getattr(st, f)) for f in FIELDS}
    x19 = np.asarray(st.regs[:, 19])
    counter = np.asarray(st.mem[:, R.COUNTER_WORD])
    ino_kind = np.asarray(st.k_ino_kind[:, 0])
    ino_size = np.asarray(st.k_ino_size[:, 0])
    made = np.asarray(srv._trace.count)
    running = lanes(srv)
    model_bad = []
    for rid, p in running.items():
        m, w, x0 = spec[rid]
        obs = {f: int(cols[f][p]) for f in FIELDS}
        obs.update(x19=int(x19[p]), counter=int(counter[p]),
                   records_made=int(made[p]),
                   file_bytes=int(ino_size[p])
                   if ino_kind[p] == C.INO_FILE else 0)
        key = srv._stream.export_key(rid)
        rows = key["rows"] if key else np.empty((0, 8), np.int64)
        if RI.midflight(w, m, x0, obs, rows):
            model_bad.append(rid)
    stats = srv.stats()
    rng = np.random.default_rng(h.seed)
    by_work = {}
    for rid, p in sorted(running.items()):
        by_work.setdefault(spec[rid][1], []).append(p)
    sample = []
    for w in sorted(by_work):
        k = min(len(by_work[w]), h.mix["scalar_sample_per_workload"])
        sample += rng.choice(by_work[w], k, replace=False).tolist()
    rid_of = {p: rid for rid, p in running.items()}
    states = {p: h.to_host(h.lane_state(p)) for p in sample}
    h.free_server()
    scalar_bad = []
    for p, lane in states.items():
        m, w, x0 = spec[rid_of[p]]
        ref = C.scalar_state(h.ref_pps[(m, w)], regs={19: x0},
                             fuel=h.mix["fuel"], steps=int(lane.icount))
        if C.scalar_mismatch(lane, ref):
            scalar_bad.append(rid_of[p])
    return {
        "published_in_warmup": (out["published_in_warmup"], 0),
        "admitted_in_window": (d["admission_waits"], 0),
        "published_in_window": (out["published_in_window"] + d["completed"],
                                0),
        "lane_steps_short": (expected - d["executed_steps"], 0),
        "lanes_running": (len(running), None),
        # a lane of the batch that no longer runs its request is wrong too
        "reference_mismatch": (len(model_bad) + h.config["pool"]
                               - len(running), 0),
        "trace_dropped": (stats["trace_dropped"], 0),
        "stream_records_dropped": (stats["stream"]["records_dropped"], 0),
        "scalar_mismatch": (len(scalar_bad), 0),
        "scalar_sampled": (len(sample), None),
    }
