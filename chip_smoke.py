#!/usr/bin/env python3
"""Bring-up smoke of the served main path on a TPU.

One process drives a :class:`repro.serve.fleet_server.FleetServer` through
its public API and checks what it publishes:

    python chip_smoke.py              # one chip: a 4096-lane pool
    python chip_smoke.py --chips 4    # four chips: a sharded 16384-lane
                                      # pool against an unsharded pool

The one-chip run serves about 1.5 x pool requests (every mechanism but
NONE crossed with the register-parameterised workloads, plus one C3
request), part of them submitted while generations are in flight, and
compares a sample per mechanism x workload with the scalar reference
(``run_prepared`` / ``run_with_c3``) on the same chip, field for field.
The four-chip run serves a 4 x wider pool lane-sharded over the chips and
compares every published result with an unsharded pool on chip 0.

Without a TPU it exits nonzero and prints no result: it never falls back
to the CPU.  The last stdout line is the JSON result; the timings above
it are smoke timings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import collections
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

POOL = 4096                 # lanes per chip
TABLE_CAPACITY = 32         # 20 mix binaries + the C3 request's re-prepares
ITERS = (1, 6)              # x19 iteration count per request, inclusive
# The four-chip phase serves fewer, shorter requests: the server's host
# path costs tens of ms per request (PERF.md), and this phase runs twice
FOUR_CHIP_REQUESTS = 512
FOUR_CHIP_ITERS = (1, 2)
FUEL = 2_000_000
SEED = 0
WORKLOADS = ("getpid_loop_param", "read_loop_param", "mixed_ops_param",
             "io_bandwidth_param", "file_churn_param")
C3_ITERS = 3                # indirect_svc(3): the Figure 4 C3 request


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent compiling (or reading its compile cache) and the
    persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def prepare_cells():
    """One prepared binary per (mechanism, workload): every request of a
    cell shares it and differs only in its x19 iteration count."""
    from repro.core import Mechanism, prepare, programs
    return {(m.name, w): prepare(getattr(programs, w)(), m)
            for m in Mechanism if m is not Mechanism.NONE
            for w in WORKLOADS}


def make_mix(cells, n: int, iters=ITERS, seed: int = SEED):
    """``n`` requests cycling through the cells, iteration counts drawn
    from ``iters`` (inclusive)."""
    import numpy as np
    keys = sorted(cells)
    counts = np.random.default_rng(seed).integers(iters[0], iters[1] + 1, n)
    return [(keys[i % len(keys)], int(counts[i])) for i in range(n)]


def make_server(pool: int, *, shard: bool = False, compact: bool = True):
    from repro.core import HookConfig
    from repro.serve.fleet_server import FleetServer
    # compaction ladder pool .. pool / 8: four rungs to compile
    return FleetServer(pool=pool,
                       cfg=HookConfig(compact_min_bucket=max(8, pool // 8)),
                       table_capacity=TABLE_CAPACITY, fuel=FUEL,
                       engine="xla", trace=True, stream=True, compact=compact,
                       obs=True, shard=shard)


def c3_builder():
    from repro.core import programs
    return programs.indirect_svc(C3_ITERS)


def serve(srv, cells, mix, *, keep: bool = True):
    """Submit a pool's worth of the mix, run two generations, submit the
    rest plus the C3 request while those lanes are in flight, then serve
    to the end.  Returns ``(specs, published)``: rid -> (cell, iters) and
    rid -> FleetResult (``keep``) or its digest, checked to be published
    once each."""
    specs, published = {}, {}

    def submit(key, n):
        specs[srv.submit(cells[key], regs={19: n})] = (key, n)

    def take(results):
        for r in results:
            check(r.rid not in published, f"rid {r.rid} published twice")
            published[r.rid] = r if keep else digest(r)

    first = min(len(mix), srv.pool)
    for key, n in mix[:first]:
        submit(key, n)
    take(srv.step())
    take(srv.step())
    for key, n in mix[first:]:
        submit(key, n)
    specs[srv.submit(c3_builder, virtualize=True)] = ("C3", C3_ITERS)
    if keep:
        take(srv.run())
    else:
        # step-by-step, so each published lane is digested and dropped
        # at once (a lane sliced from a sharded carry sits on every chip)
        for _ in range(100_000):
            if srv.completed == len(specs):
                break
            take(srv.step())
    check(sorted(published) == sorted(specs),
          f"{len(published)} of {len(specs)} rids published")
    return specs, published


def digest(r) -> str:
    """Every MachineState field (mem included) plus the trace, histogram
    and C3 history of a published result."""
    import jax
    import numpy as np
    h = hashlib.sha256()
    for name, leaf in zip(r.state._fields, jax.device_get(r.state)):
        h.update(name.encode())
        h.update(np.ascontiguousarray(leaf).tobytes())
    h.update(repr((r.trace, r.trace_dropped, sorted(r.histogram.items()),
                   r.attempts, r.events)).encode())
    return h.hexdigest()


def check_served(specs, published, stats) -> None:
    """Server-level checks: halts, counters, emulation coverage."""
    import numpy as np
    from repro.core import HALT_EXIT, HALT_FUEL
    halts = collections.Counter(int(np.asarray(r.state.halted))
                                for r in published.values())
    check(halts[HALT_FUEL] == 0, f"{halts[HALT_FUEL]} lanes ran out of fuel")
    check(halts == {HALT_EXIT: len(specs)}, f"halt codes {dict(halts)}")
    check(stats["scalar_reexecutions"] == 0,
          f"scalar_reexecutions {stats['scalar_reexecutions']}")
    check(stats["trace_dropped"] == 0, f"trace_dropped {stats['trace_dropped']}")
    check(stats["enosys_total"] == 0, f"enosys_total {stats['enosys_total']}")
    check(stats["emul_served_total"] > 0, "the guest kernel served nothing")
    check(stats["c3_readmissions"] >= 1, "the C3 request was not recycled")


def check_against_scalar(cells, specs, published) -> int:
    """The first request of every cell and the C3 request against the
    scalar engine, every MachineState field.  Returns the sample size."""
    import jax
    import numpy as np
    from repro.core import HookConfig, run_prepared, run_with_c3
    first = {}
    for rid in sorted(specs):
        first.setdefault(specs[rid][0], rid)
    check(len(first) == len(cells) + 1, f"sample covers {list(first)}")
    for key, rid in first.items():
        r = published[rid]
        if key == "C3":
            ref, _, events, runs = run_with_c3(c3_builder, cfg=HookConfig(),
                                               virtualize=True, fuel=FUEL)
            check(r.events == events and r.attempts == runs,
                  f"C3 rid {rid}: events/attempts differ from run_with_c3")
        else:
            ref = run_prepared(cells[key], fuel=FUEL,
                               regs={19: specs[rid][1]})
        got, want = jax.device_get(r.state), jax.device_get(ref)
        for name, a, b in zip(got._fields, got, want):
            check(np.array_equal(a, b),
                  f"rid {rid} {key}: field {name} differs from the scalar "
                  f"reference")
    return len(first)


def carry_bytes(pool: int) -> int:
    """Machine-state plus trace carry of a ``make_server`` pool."""
    import jax
    import numpy as np
    from repro.core import HookConfig
    from repro.core import fleet as F
    cap = HookConfig().trace_cap
    tree = jax.eval_shape(lambda: (F.make_halted_states(pool),
                                   F.make_empty_trace(pool, cap)))
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def peak_bytes(dev):
    stats = dev.memory_stats()   # None on the CPU backend
    return stats["peak_bytes_in_use"] if stats else "not reported"


def check_quarters(srv, devices) -> str:
    """Every carry leaf holds width / len(devices) lanes on each device."""
    import jax
    width = int(srv._states.pc.shape[0])
    per = width // len(devices)
    for leaf in jax.tree_util.tree_leaves((srv._states, srv._trace)):
        shards = {s.device: s.data.shape[0] for s in leaf.addressable_shards}
        check(set(shards) == set(devices) and set(shards.values()) == {per},
              f"carry leaf {leaf.shape} sharded as {shards}")
    mem = srv._states.mem
    return ", ".join(f"{s.device.id}:{tuple(s.data.shape)}"
                     for s in mem.addressable_shards)


def phases(srv) -> str:
    """The server's own host-side phase totals (repro.obs), seconds."""
    return ", ".join(f"{name} {p['total_s']:.2f}" for name, p in
                     srv.metrics()["phases"].items())


def run_one_chip(clock, pool: int = POOL) -> None:
    import jax
    t0 = time.perf_counter()
    cells = prepare_cells()
    mix = make_mix(cells, pool * 3 // 2)
    srv = make_server(pool)
    hist = collections.Counter(n for _, n in mix)
    log(f"pool {pool} lanes, {len(mix) + 1} requests "
        f"({len(cells)} cells x iterations {ITERS[0]}..{ITERS[1]}, "
        f"counts {dict(sorted(hist.items()))}, + indirect_svc({C3_ITERS}) "
        f"for C3), table_capacity {TABLE_CAPACITY}, ladder {srv._ladder}")
    log(f"carry bytes {carry_bytes(pool)}")
    specs, published = serve(srv, cells, mix)
    served_s = time.perf_counter() - t0
    stats = srv.stats()
    check_served(specs, published, stats)
    sampled = check_against_scalar(cells, specs, published)
    log(f"served {stats['completed']} requests in {stats['generations']} "
        f"generations, {stats['harvested_steps']} guest steps, "
        f"{stats['c3_readmissions']} C3 re-admissions, "
        f"{stats['emul_served_total']} emulated syscalls, "
        f"{stats['trace_records']} trace records, "
        f"min bucket {stats['min_bucket_seen']}")
    log(f"checked: every rid once, all HALT_EXIT, 0 scalar re-executions, "
        f"0 trace drops, 0 ENOSYS; {sampled} sampled requests equal the "
        f"scalar reference on every field")
    log(f"peak_bytes_in_use {peak_bytes(jax.devices()[0])}")
    log(f"smoke phase seconds: {phases(srv)}")
    log(f"smoke timings (not benchmark numbers): serve {served_s:.1f} s, "
        f"compile {clock.seconds:.1f} s, {clock.cache_hits} compile-cache "
        f"hits")


def run_four_chips(clock, pool: int = POOL,
                   requests: int = FOUR_CHIP_REQUESTS) -> None:
    import jax
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
          f"{len(devices)}")
    cells = prepare_cells()
    mix = make_mix(cells, requests, FOUR_CHIP_ITERS)
    log(f"sharded pool {4 * pool} lanes over {len(devices)} chips vs an "
        f"unsharded {pool}-lane pool on chip 0, {len(mix) + 1} requests, "
        f"iterations {FOUR_CHIP_ITERS[0]}..{FOUR_CHIP_ITERS[1]}")
    log(f"carry bytes {carry_bytes(4 * pool)} sharded, "
        f"{carry_bytes(pool)} unsharded")
    t0 = time.perf_counter()
    # no compaction: the pool keeps its full width (a quarter per chip)
    # though the mix occupies only part of it
    srv = make_server(4 * pool, shard=True, compact=False)
    log(f"shards of mem at start: {check_quarters(srv, devices)}")
    specs, sharded = serve(srv, cells, mix, keep=False)
    log(f"shards of mem at end: {check_quarters(srv, devices)}")
    t1 = time.perf_counter()
    log("peak_bytes_in_use per chip after the sharded pool: "
        + ", ".join(f"{d.id}:{peak_bytes(d)}" for d in devices))
    stats = srv.stats()
    check(stats["scalar_reexecutions"] == 0 and stats["trace_dropped"] == 0
          and stats["enosys_total"] == 0, "sharded pool counters")
    log(f"sharded smoke phase seconds: {phases(srv)}")
    del srv
    ref = make_server(pool)
    ref_specs, unsharded = serve(ref, cells, mix, keep=False)
    t2 = time.perf_counter()
    log(f"unsharded smoke phase seconds: {phases(ref)}")
    check(ref_specs == specs, "the two pools numbered the requests apart")
    bad = [rid for rid in specs if sharded[rid] != unsharded[rid]]
    check(not bad, f"{len(bad)} results differ between the sharded and the "
          f"unsharded pool, e.g. rids {bad[:5]}")
    log(f"checked: all {len(specs)} results equal per rid between the "
        f"sharded and the unsharded pool")
    log(f"peak_bytes_in_use chip 0 after both pools {peak_bytes(devices[0])}")
    log(f"smoke timings (not benchmark numbers): sharded {t1 - t0:.1f} s, "
        f"unsharded {t2 - t1:.1f} s, compile {clock.seconds:.1f} s, "
        f"{clock.cache_hits} compile-cache hits")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded four-chip phase")
    args = ap.parse_args(argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{devices[0].platform!r} ({devices[0].device_kind}), so "
              f"nothing ran", file=sys.stderr)
        return 2
    from repro.core.runtime import enable_compile_cache
    cache = enable_compile_cache()
    clock = CompileClock()
    t0 = time.perf_counter()
    log(f"device_kind {devices[0].device_kind}, {len(devices)} device(s), "
        f"compile cache {cache}")
    if args.chips == 4:
        run_four_chips(clock)
    else:
        run_one_chip(clock)
    log(f"smoke wall seconds {time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
