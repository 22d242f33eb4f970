# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness:

  * hook_overhead            — paper Table 3 (getpid interception cost),
                               one fleet dispatch for the whole grid
  * svc_census               — paper Tables 1 & 2 (svc population);
                               writes BENCH_census.json itself
  * app_bandwidth            — paper Figures 5 & 6 (app-level overhead)
  * collective_census        — adapted Table 1 (collective sites per arch)
  * collective_hook_overhead — one-dispatch mechanisms x programs x
                               iteration-counts census; scalar vs fleet
                               steps/sec (the perf-tracking suite)
  * serving_throughput       — continuous batching vs drain-the-fleet on a
                               mixed-length workload (+ fleet-native C3);
                               writes BENCH_serving.json itself
  * trace_overhead           — traced vs untraced fleet census (the
                               repro.trace subsystem's 3.7%-claim analog);
                               writes BENCH_trace.json itself
  * compaction_speedup       — live-lane compaction vs fixed width on a
                               tail-heavy census + bimodal serving mix;
                               writes BENCH_compaction.json itself
  * policy_scheduler         — noisy-neighbor isolation (tenant budgets,
                               SLO preemption, quarantine) + mid-flight
                               policy updates; writes BENCH_sched.json
                               itself
  * durability_overhead      — write-ahead journal + snapshot cost on the
                               500-lane census (<10% bar) and a
                               kill-and-recover wall-clock; writes
                               BENCH_durability.json itself
  * obs_overhead             — telemetry layer (registry + phase profiler
                               + spans) cost on the 500-lane census (<5%
                               bar, >=90% phase coverage, bit-identical
                               states); writes BENCH_obs.json itself
  * emul_overhead            — guest-kernel emulation (repro.emul) vs the
                               legacy enosys stubs on a 400-lane
                               file-churn census (<15% bar, zero -ENOSYS
                               fall-throughs, xla==pallas bit-identity);
                               writes BENCH_emul.json itself

Besides the CSV stream, writes ``benchmarks/results/BENCH_fleet.json`` with
machine-readable per-mechanism per-call cycles and the scalar-vs-fleet
throughput numbers — one ``python -m benchmarks.run`` refreshes every
``BENCH_*.json``.  ``--only <name>`` runs a single suite (substring match
allowed), e.g. ``--only trace`` to refresh just BENCH_trace.json;
``--only fleet`` refreshes just BENCH_fleet.json (census + xla-vs-pallas
engine race + Table 3) without the per-suite CSV passes.
"""
import argparse
import importlib
import inspect
import json
import pathlib
import sys
import traceback

SUITES = ["hook_overhead", "svc_census", "app_bandwidth", "collective_census",
          "collective_hook_overhead", "serving_throughput", "trace_overhead",
          "compaction_speedup", "policy_scheduler", "durability_overhead",
          "obs_overhead", "emul_overhead"]

# suites feeding the BENCH_fleet.json record (collect_fleet_bench)
_FLEET_BENCH_INPUTS = {"hook_overhead", "collective_hook_overhead"}

BENCH_PATH = pathlib.Path(__file__).parent / "results" / "BENCH_fleet.json"


def write_bench_json(payload: dict, path: pathlib.Path = BENCH_PATH) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=1, sort_keys=True))


def collect_fleet_bench() -> dict:
    """The machine-readable fleet benchmark record (BENCH_fleet.json).

    Schema v2 adds the ``engines`` block: the xla-vs-pallas (megastep
    kernel) race on the 500-lane census — interleaved median-ratio pairs,
    with final states, decoded traces and histograms asserted bit-identical
    inside the benchmark before anything is timed.  ``platform`` /
    ``interpret`` qualify the ratio: on hosts without a Pallas backend both
    arms lower to the same XLA ops, so the >= 1.3x target applies to
    accelerator backends.
    """
    from benchmarks import collective_hook_overhead, hook_overhead
    census = collective_hook_overhead.run_census()
    race = collective_hook_overhead.run_engine_race()
    table3 = hook_overhead.run(engine="fleet")
    return {
        "schema": "BENCH_fleet/v2",
        "table3_per_mechanism": {
            r["mechanism"]: {
                "cycles_per_call": r["cycles_per_call"],
                "ns_per_call": r["ns_per_call"],
                "paper_ns": r["paper_ns"],
                "x_vs_asc": r["x_vs_asc"],
            } for r in table3
        },
        "census": census,
        "engines": race,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", metavar="NAME", default=None,
                    help="run a single suite (exact or substring match)")
    args = ap.parse_args(argv)
    suites = SUITES
    fleet_only = False
    if args.only:
        suites = [s for s in SUITES if args.only == s] or \
                 [s for s in SUITES if args.only in s]
        if not suites:
            # "--only fleet" refreshes just BENCH_fleet.json (census +
            # engine race + table 3) without running every suite's CSV pass
            if args.only in ("fleet", "bench_fleet", "BENCH_fleet"):
                suites, fleet_only = [], True
            else:
                ap.error(f"--only {args.only!r} matches none of {SUITES} "
                         f"(or 'fleet' for BENCH_fleet.json)")
    from repro.core.runtime import enable_compile_cache
    enable_compile_cache()

    failures = 0
    for name in suites:
        print(f"# === {name} ===", flush=True)
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            if inspect.signature(mod.main).parameters:
                mod.main([])  # keep the harness argv out of suite parsers
            else:
                mod.main()
        except Exception:
            failures += 1
            print(f"{name}/ERROR,0,{traceback.format_exc(limit=2)!r}")
    if not args.only or fleet_only or _FLEET_BENCH_INPUTS.intersection(suites):
        print("# === BENCH_fleet.json ===", flush=True)
        try:
            payload = collect_fleet_bench()
            write_bench_json(payload)
            c = payload["census"]
            e = payload["engines"]
            print(f"bench_fleet/written,0,path={BENCH_PATH} "
                  f"speedup={c['speedup']}x "
                  f"fleet={c['fleet_steps_per_sec']:.0f}sps "
                  f"pallas_vs_xla={e['pallas_speedup_vs_xla']}x "
                  f"({e['platform']}, interpret={e['interpret']})")
        except Exception:
            failures += 1
            print(f"bench_fleet/ERROR,0,{traceback.format_exc(limit=2)!r}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
