#!/usr/bin/env python3
"""The chip benchmark of the served fleet: one process runs one cell.

    python benchmarks/chip/run.py --workload io_monitor.served \
        --seed 7 --seconds 40 --trace 0

``--workload`` names an entry of ``workloads`` in ``BENCHMARK.json``;
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled run.  The last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit).  The same numbers are the last lines of
standard error.

It needs a TPU: without one, or with fewer chips than the cell asks for,
it exits 2 and prints no result.  ``--control`` runs the cell's control
(the configuration with one of its stated guarantees broken), which has
to come out not correct; benchmark runs never pass it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

# JAX's persistent compilation cache (unless JAX_COMPILATION_CACHE_DIR
# names one) and libtpu's logs live at fixed paths inside the checkout:
# the path is part of the cache's key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".tpu_logs"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the cell's control instead of the program "
                         "as configured")
    return ap.parse_args(argv)


def find_chips(n: int):
    """The TPU devices, or None (with the reason on stderr)."""
    try:
        import jax
        devs = jax.devices()
    except Exception as e:          # no backend could start
        print(f"chipbench: JAX found no accelerator ({e})", file=sys.stderr)
        return None
    if devs[0].platform != "tpu":
        print(f"chipbench: needs a TPU; JAX found platform "
              f"{devs[0].platform!r}, so nothing ran", file=sys.stderr)
        return None
    if len(devs) < n:
        print(f"chipbench: the cell needs {n} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return None
    return devs


def enable_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def report(result: dict, device: dict) -> str:
    """The result line, ``checks`` last; the checks also go to stderr."""
    out = {k: result[k] for k in ("correct", "attempted", "failed",
                                  "metrics")}
    out["device"] = device
    for k in ("breakdown", "diagnostics"):
        if k in result:
            out[k] = result[k]
    out["checks"] = result["checks"]
    for name, c in result["checks"].items():
        lim = "none" if c["limit"] is None else c["limit"]
        print(f"check {name} {c['value']} limit {lim}", file=sys.stderr)
    return json.dumps(out)


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: the program under test is not at "
              f"{os.path.join(ROOT, 'src', 'repro')}", file=sys.stderr)
        return 2
    import harness
    bench = harness.benchmark()
    cell, _, _ = harness.cell_parts(bench, args.workload)
    devs = find_chips(cell["chips"])
    if devs is None:
        return 2
    if devs[0].device_kind not in harness.load_json(HERE, "peaks.json"):
        print(f"chipbench: no peaks for device kind "
              f"{devs[0].device_kind!r} in peaks.json", file=sys.stderr)
        return 2
    enable_cache()
    result = harness.run_cell(workload=args.workload, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t_start=T_START, control=args.control,
                              device_kind=devs[0].device_kind, bench=bench)
    used = devs[:cell["chips"]]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(used),
              "memory_peak_bytes": result.pop("peak_bytes", None)}
    if args.trace:
        device.update(result.pop("trace"))
    line = report(result, device)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
