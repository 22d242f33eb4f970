"""One run of one cell: build the server from the cell's configuration,
drive it with the cell's traffic, reduce what was measured to metrics,
and decide ``correct``.

Everything that belongs to one configuration, traffic mix or metric is
found by name under this directory:

* ``configs/<config>.json``: the deployment;
* ``traffic/<mix>.json``: the mix's parameters, whose ``kind`` names the
  generator and its ``drive`` loop, ``traffic/<kind>.py``;
* ``metrics/<metric>.py``: ``UNIT``, ``LAYER``, ``MOVES``, ``SOURCE`` and
  ``reduce(ctx)``, which returns the value or None where the run gave it
  nothing to read.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import time
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# a traced run profiles the window's first seconds, up to the next
# generation boundary.  On a TPU v5e a traced run of the served cell took
# 266-278 s of the 360 s a run may take (4 s traced, host tracer at level
# 1), against about 100 s untraced
TRACE_SECONDS = 4.0


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(HERE, *parts)
    name = "chipbench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def cell_parts(bench: dict, workload: str):
    """The cell's entry, its configuration and its mix, by name."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = load_json(HERE, "configs", cell["config"] + ".json")
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, config, mix


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries this cell reports in a run with or without
    ``--trace``."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


class CompileClock:
    """Compilations (or compile-cache reads) JAX made, from its own
    monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def make_server(config: dict, control: bool = False):
    """The server and one prepared binary per mechanism x workload, as the
    configuration states them (or, with ``control``, with the control's
    broken guarantee), plus the binaries prepared as stated for the
    reference."""
    from repro.core import HookConfig, Mechanism, prepare, programs
    from repro.serve.fleet_server import FleetServer
    ctl = config.get("control", {}) if control else {}

    def binaries(c):
        cfg = HookConfig(emul_enabled=c.get("emulation", config["emulation"]))
        return {(m, w): prepare(getattr(programs, w)(),
                                Mechanism[c.get("mechanism", m)],
                                virtualize=config["virtualize"], cfg=cfg)
                for m in config["mechanisms"] for w in config["workloads"]}

    cfg = HookConfig(compact_min_bucket=config["compact_min_bucket"],
                     emul_enabled=config["emulation"])
    srv = FleetServer(pool=config["pool"], cfg=cfg,
                      gen_steps=config["gen_steps"], chunk=config["chunk"],
                      table_capacity=config["table_capacity"],
                      fuel=config["fuel"], engine=config["engine"],
                      trace=config["trace"], stream=config["stream"],
                      compact=config["compact"], obs=config["obs"])
    pps = binaries(ctl)
    ref_pps = binaries({}) if ctl else pps
    return srv, pps, ref_pps


class Run:
    """The state of one run, handed to the traffic kind's ``drive``."""

    def __init__(self, *, config: dict, mix: dict, seed: int, seconds: float,
                 trace: bool, t_start: float, control: bool = False):
        self.config, self.mix, self.seed = config, mix, seed
        self.seconds, self.trace, self.t_start = seconds, trace, t_start
        self.clock = CompileClock()
        self.srv, self.pps, self.ref_pps = make_server(config, control)
        self.t_window = self.t_end = None
        self.tracing = False
        self.traced = {}
        self._annot = None

    # -- spans and the profiler ----------------------------------------------
    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    def counters(self) -> dict:
        st = self.srv.stats()
        out = {k: st[k] for k in ("executed_steps", "dispatched_steps",
                                  "completed", "admission_waits",
                                  "generations")}
        phases = self.srv.metrics().get("phases", {})
        out["phases"] = {k: v["total_s"] for k, v in phases.items()}
        out["compiles"] = self.clock.count
        return out

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        out = {k: b[k] - a[k] for k in a if k != "phases"}
        out["phases"] = {k: v - a["phases"].get(k, 0.0)
                         for k, v in b["phases"].items()}
        return out

    def window_begins(self) -> None:
        if self.trace:
            import jax
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans are the harness's
            opts.host_tracer_level = 1       # its annotations, not libtpu's
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self.tracing = True
            self._annot = jax.profiler.TraceAnnotation("bench.window")
            self._annot.__enter__()
        self.c_begin = self.counters()
        self.t_window = time.perf_counter()

    def maybe_stop_trace(self, force: bool = False) -> None:
        if not self.tracing:
            return
        now = time.perf_counter()
        if not force and now - self.t_window < TRACE_SECONDS:
            return
        import jax
        c = self.counters()
        self._annot.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing = False
        self.traced = {"seconds": now - self.t_window,
                       "delta": self.delta(self.c_begin, c)}
        # writing the trace out stalls the loop: the window resumes after
        # it, so a traced run still offers its whole schedule
        self.t_window += time.perf_counter() - now

    def window_ends(self) -> None:
        self.t_end = time.perf_counter()
        self.window_delta = self.delta(self.c_begin, self.counters())
        self.maybe_stop_trace(force=True)

    def drained(self) -> None:
        """After the last request: read the device's memory peak before any
        reference runs."""
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        self.peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    # -- lanes -----------------------------------------------------------------
    def lane_state(self, p: int):
        import jax
        return jax.tree_util.tree_map(lambda x: x[p], self.srv._states)

    @staticmethod
    def to_host(tree):
        import jax
        return jax.device_get(tree)

    def lane_shapes(self) -> dict:
        return {f: tuple(getattr(self.srv._states, f).shape[1:])
                for f in self.srv._states._fields}

    def free_server(self) -> None:
        import gc
        self.srv._states = self.srv._trace = None
        gc.collect()

    def read_trace(self) -> Optional[dict]:
        import glob
        import trace_reduce
        paths = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            return None
        ev = trace_reduce.load_events(paths[0])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return ev


def run_cell(*, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, device_kind: str, control: bool = False,
             bench: Optional[dict] = None,
             resize: Optional[dict] = None) -> dict:
    """One run; returns the result object (without the device fields).
    ``resize`` replaces keys of the configuration and the mix: the tests
    run cells at a size the CPU holds through it."""
    import trace_reduce
    bench = bench or benchmark()
    cell, config, mix = cell_parts(bench, workload)
    for k, v in (resize or {}).items():
        (config if k in config else mix)[k] = v
    kind = load_module("traffic", mix["kind"] + ".py")
    h = Run(config=config, mix=mix, seed=seed, seconds=seconds, trace=trace,
            t_start=t_start, control=control)
    out = kind.drive(h)
    lane_shapes = h.lane_shapes()
    ev = h.read_trace() if trace else None
    ctx = {"setup_s": h.t_window - h.t_start,
           "window_s": h.t_end - h.t_window,
           "window": h.window_delta, "peak_bytes": h.peak_bytes,
           "latencies_ms": out.get("latencies_ms"),
           "counts": out.get("counts", {}),
           "lane_shapes": lane_shapes,
           "peaks": load_json(HERE, "peaks.json"),
           "device_kind": device_kind, "traced": None}
    breakdown = None
    if ev is not None:
        win = trace_reduce.window(ev)
        if win is not None:
            ctx["traced"] = {
                "window_s": (win[1] - win[0]) / 1e9,
                "busy_s": trace_reduce.busy_seconds(ev, win),
                "span_s": trace_reduce.span_seconds(ev, win),
                "delta": h.traced["delta"]}
            breakdown = {"device_ops": trace_reduce.top_ops(ev),
                         "idle_gaps": trace_reduce.idle_gaps(ev, win)}
    checks = kind.check(h, out)
    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        mod = load_module("metrics", m["name"] + ".py")
        val = mod.reduce(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    failed_names = [k for k, (v, lim) in checks.items()
                    if lim is not None and v > lim]
    queue = out.get("queue")
    diag_queue = {}
    if queue:
        def mean(lo, hi):
            q = [o for t, o, _ in queue if lo <= t / h.seconds < hi]
            return sum(q) / len(q) if q else None
        # in flight over the window's last two quarters: a server that
        # keeps up holds it level once the longest requests have cycled
        diag_queue = {"in_flight_q3": mean(0.5, 0.75),
                      "in_flight_q4": mean(0.75, 1.01),
                      "in_flight_end": queue[-1][1],
                      "in_flight_max": max(o for _, o, _ in queue),
                      "waiting_end": queue[-1][2],
                      "waiting_max": max(w for _, _, w in queue)}
    result = {
        "correct": not failed_names,
        "attempted": out["attempted"],
        "failed": sum(checks[k][0] for k in ("unpublished",
                                             "reference_mismatch",
                                             "scalar_mismatch")
                      if k in checks),
        "metrics": metrics,
        "peak_bytes": h.peak_bytes,
        "diagnostics": {"compiles_in_window": h.window_delta["compiles"],
                        "compile_s": h.clock.seconds,
                        "compile_cache_hits": h.clock.cache_hits,
                        "window_phases_s": h.window_delta["phases"],
                        "generations_in_window":
                            h.window_delta["generations"],
                        "counts": ctx["counts"], **diag_queue},
    }
    if trace:
        tr = ctx["traced"] or {}
        result["trace"] = {"busy_s": tr.get("busy_s", 0.0),
                           "window_s": tr.get("window_s", 0.0)}
        if breakdown is not None:
            result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result
