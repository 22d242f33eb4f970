"""Open-loop arrivals at a fixed rate: the generator and its run loop.

Every seed gets the same work: the same inter-arrival gaps (the quantiles
of the exponential distribution at the mix's rate, scaled to fill the
window exactly) and the same multiset of requests (each workload x
mechanism x iteration count equally often), in an order drawn from the
seed.  So seeds differ in the order of arrivals only.

The order is stratified, so that a seed cannot gather the long requests
or the short gaps into one stretch of the window:

* gaps in blocks of the mix's ``gap_block`` consecutive arrivals: the
  sorted gaps are cut into strata of one gap per block and every block
  takes one gap of each stratum, so every block spans about the same
  time;
* requests in blocks of ``kind_block``: a block of as many arrivals as
  there are request kinds holds one of each.

Inside a block the order is drawn from the seed.  Without these keys the
whole window is one block.

``drive`` offers each request at its due time, whatever the server is
doing, and times it from that due time to the return of the ``step()``
that published it.  After the window it offers nothing more and serves on
until every request due in the window is published or the mix's drain
limit passes; a request still unpublished then counts as failed.
"""
from __future__ import annotations

import math
import time

import numpy as np


def generate(mix: dict, config: dict, seed: int, seconds: float) -> list:
    """``[(offset_s, mechanism, workload, iterations), ...]`` sorted by
    offset, all offsets in ``[0, seconds)``."""
    rng = np.random.default_rng(seed)
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    blocks = math.ceil(n / min(n, mix.get("gap_block", n)))
    m = min(n, mix.get("kind_block", n))
    q = (np.arange(n) + 0.5) / n
    quantiles = -np.log1p(-q)                      # unit-rate, ascending
    # a stratum is ``blocks`` consecutive sorted gaps; block b takes one
    # gap of each stratum, which one drawn from the seed
    dealt = [[] for _ in range(blocks)]
    for k in range(0, n, blocks):
        stratum = rng.permutation(quantiles[k:k + blocks])
        for b, g in enumerate(stratum):
            dealt[b].append(g)
    gaps = np.concatenate([rng.permutation(d) for d in dealt])
    gaps *= seconds / gaps.sum()
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    lo, hi = config["process_iterations"]
    combos = [(mc, w, it) for mc in config["mechanisms"]
              for w in config["workloads"] for it in range(lo, hi + 1)]
    reps = math.ceil(n / len(combos))
    picks = (combos * reps)[:n]
    order = np.concatenate([b + rng.permutation(min(m, n - b))
                            for b in range(0, n, m)])
    return [(float(offsets[i]), *picks[order[i]]) for i in range(n)]


def warm_up(srv, pps, config: dict, limit: int) -> set:
    """Visit every program the window can run: each rung of the pool's
    compaction ladder, widest first, with one-iteration requests of every
    mechanism x workload, as many as take the pool to that rung and keep
    it there under the shrink margin (admission, span, harvest and the
    moves between rungs at each width), each batch served to the end.
    Returns the warm-up requests left unpublished."""
    combos = [(m, w) for m in config["mechanisms"]
              for w in config["workloads"]]
    ladder = sorted(srv.precompile_ladder(), reverse=True)
    margin = 1.0 - srv.cfg.compact_hysteresis
    left = set()
    for k, width in enumerate(ladder):
        n = max(int(width * margin),
                ladder[k + 1] + 1 if k + 1 < len(ladder) else 1)
        batch = {srv.submit(pps[combos[j % len(combos)]], regs={19: 1})
                 for j in range(n)}
        for _ in range(limit):
            batch -= {r.rid for r in srv.step()}
            if not batch:
                break
        left |= batch
    return left


def drive(h) -> dict:
    """Warm up, run the open-loop window and the drain; returns what the
    metrics and the checks read."""
    srv, pps, mix = h.srv, h.pps, h.mix
    reqs = generate(mix, h.config, h.seed, h.seconds)
    left = warm_up(srv, pps, h.config, mix["warmup_generations_limit"])
    h.window_begins()
    spec, published, done_at = {}, {}, {}
    i, outstanding = 0, 0
    queue = []          # (seconds into the window, in flight, waiting)

    def take(results, t_ret):
        nonlocal outstanding
        for r in results:
            if r.rid in spec:
                published[r.rid] = r
                done_at[r.rid] = t_ret
                outstanding -= 1

    while True:
        now = time.perf_counter()
        t0 = h.t_window                 # moves only past a trace write-out
        closed = now - t0 >= h.seconds
        # every request due in the window is submitted, also those that
        # fell due while the window's last step() ran
        with h.span("generator"):
            due = []
            while i < len(reqs) and t0 + reqs[i][0] <= now:
                due.append(reqs[i])
                i += 1
        if due:
            with h.span("submit"):
                for off, m, w, n in due:
                    rid = srv.submit(pps[(m, w)], regs={19: n})
                    spec[rid] = (off, m, w, n)
                    outstanding += 1
        if closed:
            break
        if outstanding == 0:
            nxt = t0 + (reqs[i][0] if i < len(reqs) else h.seconds)
            with h.span("sleep"):
                time.sleep(max(0.0, min(nxt, t0 + h.seconds) - now))
            continue
        with h.span("step"):
            res = srv.step()
        t_ret = time.perf_counter()
        take(res, t_ret)
        queue.append((t_ret - t0, outstanding, len(srv._queue)))
        h.maybe_stop_trace()
    h.window_ends()
    deadline = time.perf_counter() + mix["drain_limit_s"]
    while outstanding and time.perf_counter() < deadline:
        t_step = time.perf_counter()
        res = srv.step()
        t_ret = time.perf_counter()
        take(res, t_ret)
        if not res and t_ret - t_step < 1e-3:
            time.sleep(1e-3)
    h.drained()
    t0 = h.t_window
    lat = [1e3 * (done_at[rid] - (t0 + spec[rid][0])) for rid in published]
    return {"spec": spec, "published": published, "latencies_ms": lat,
            "attempted": len(spec),
            "warmup_unpublished": len(left), "queue": queue}


def check(h, out) -> dict:
    """Numbers compared, each ``name: (value, limit)``: requests due in the
    window left unpublished; published requests that differ from the plain
    reference in any observable, their syscall records and histogram
    included; sampled requests that differ from the scalar engine in any
    state field; trace records dropped."""
    import compare as C
    spec, published = out["spec"], out["published"]
    model_bad = []
    for rid, r in published.items():
        _, m, w, n = spec[rid]
        obs = C.observe(r.state, r.trace if h.config["trace"] else None,
                        r.histogram if h.config["trace"] else None)
        if C.finished_mismatch(w, m, n, obs):
            model_bad.append(rid)
    # scalar sample: the longest request of every mechanism x workload,
    # plus requests drawn from the seed
    longest = {}
    for rid in sorted(published):
        _, m, w, n = spec[rid]
        if n > longest.get((m, w), (0, -1))[0]:
            longest[(m, w)] = (n, rid)
    sample = {rid for _, rid in longest.values()}
    rest = sorted(set(published) - sample)
    rng = np.random.default_rng(h.seed)
    k = min(len(rest), h.mix["scalar_sample_random"])
    sample |= set(rng.choice(rest, k, replace=False).tolist()) if k else set()
    scalar_bad = []
    for rid in sorted(sample):
        _, m, w, n = spec[rid]
        ref = C.scalar_state(h.ref_pps[(m, w)], regs={19: n},
                             fuel=h.config["fuel"])
        if C.scalar_mismatch(h.to_host(published[rid].state), ref):
            scalar_bad.append(rid)
    stats = h.srv.stats()
    return {
        "warmup_unpublished": (out["warmup_unpublished"], 0),
        "unpublished": (out["attempted"] - len(published), 0),
        "reference_mismatch": (len(model_bad), 0),
        "scalar_mismatch": (len(scalar_bad), 0),
        "scalar_sampled": (len(sample), None),
        "trace_dropped": (stats["trace_dropped"], 0),
    }
