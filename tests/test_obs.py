"""Serving telemetry suite (marked ``obs``).

Two invariants anchor everything:

* **Observation never steers** — a FleetServer with ``obs_enabled=True``
  publishes guest states bit-identical to the same run unobserved; the
  layer is counters, clocks and spans on the host side only.
* **Zero cost when off** — a disabled server constructs no registry at
  all (``MetricsRegistry.created_total``), and every phase wrap
  degrades to one shared null context manager.

Around them: registry units (label series, log-bucketed histogram
quantiles, Prometheus v0 rendering, export/restore round-trip,
watermark floors), HookConfig knob round-trip and ``obs_sink``
validation, phase-profiler coverage of the generation loop, lifecycle
spans (admit / preempt / resume / C3 re-admit / complete) aggregated
per tenant, the satellite resume-wait ledger split out of the
first-admission waits, ledger gauges, scheduler/chaos decision
counters, snapshot sinks, and the kill-and-recover regression: after a
crash + ``FleetServer.recover()``, counters and profiler counts are
monotone (never below any value a ``metrics()`` caller could have
read) and every span still completes.  Example counts scale via
ASC_TEST_EXAMPLES.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core import HookConfig, Mechanism, prepare, programs
from repro.obs import (ObsHub, PHASES, MetricsRegistry, make_sink, now,
                       phase as obs_phase,
                       step_annotation as obs_step_annotation)
from repro.obs.metrics import (JsonlSink, MemorySink, PromFileSink,
                               _bucket_index, _bucket_upper)
from repro.sched import PolicyScheduler, TenantBudget
from repro.serve.durability import (BUILDERS, DurabilityManager,
                                    register_builder)
from repro.serve.fleet_server import FleetServer

pytestmark = pytest.mark.obs

FUEL = 25_000
MAX_EXAMPLES = int(os.environ.get("ASC_TEST_EXAMPLES", "5"))

register_builder("obs-getpid", lambda: programs.getpid_loop(300))
register_builder("obs-mixed", lambda: programs.mixed_ops(24, 128))

_pp_cache = {}


def _pp(wname):
    if wname not in _pp_cache:
        fns = {"getpid": programs.getpid_loop_param,
               "storm": programs.syscall_storm_param}
        _pp_cache[wname] = prepare(fns[wname](), Mechanism.ASC,
                                   virtualize=True)
    return _pp_cache[wname]


def _drain(srv, max_generations=5000):
    out = []
    for _ in range(max_generations):
        out.extend(srv.step())
        if (not srv._queue and not srv._readmit
                and all(r is None for r in srv._slots)):
            return out
    raise AssertionError("server did not drain")


def _state_key(r):
    return (r.rid, tuple(int(x) for x in np.asarray(r.state.regs)),
            int(r.state.halted), int(r.state.icount), int(r.state.pc))


# -- registry units -----------------------------------------------------------

def test_counter_and_gauge_series():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc(2, tenant="a")
    c.inc(3, tenant="a")
    c.inc(1, tenant="b")
    c.inc(1)
    assert c.get(tenant="a") == 5 and c.get(tenant="b") == 1
    assert c.get() == 1 and c.total == 7
    g = reg.gauge("depth", "queue depth")
    g.set(4)
    g.set(2)
    assert g.get() == 2
    # same name must keep its kind
    with pytest.raises(TypeError):
        reg.gauge("req_total", "oops")


def test_histogram_quantiles_bracket_observations():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency")
    vals = [10 ** (-i / 3) for i in range(30)]  # 1s .. ~1e-10 spread
    for v in vals:
        h.observe(v)
    s = h.summary()
    assert s["count"] == 30
    assert s["min"] == min(vals) and s["max"] == max(vals)
    assert abs(s["sum"] - sum(vals)) < 1e-12
    # log-bucketed quantile: upper bound of the covering bucket, so the
    # estimate can only overshoot by one sub-bucket's width (12.5%/oct)
    exact_p50 = sorted(vals)[14]
    assert exact_p50 <= s["p50"] <= exact_p50 * 1.1 + 1e-12
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


def test_histogram_bucket_index_monotone():
    prev = -1
    for v in (0.0, 1e-9, 1e-7, 1.5e-7, 1e-3, 0.5, 1.0, 3.7, 1e4):
        i = _bucket_index(v)
        assert i >= prev, v
        prev = i
        if v > 0:
            assert _bucket_upper(i) >= v * 0.999999


def test_prometheus_render_and_snapshot_json():
    reg = MetricsRegistry()
    reg.counter("a_total", "help a").inc(3, kind="x")
    reg.gauge("b", "help b").set(1.5)
    reg.histogram("c_seconds", "help c").observe(0.01, tenant="t")
    text = reg.render_prometheus()
    assert "# HELP a_total help a" in text
    assert "# TYPE a_total counter" in text
    assert 'a_total{kind="x"} 3' in text
    assert "# TYPE c_seconds histogram" in text
    assert 'c_seconds_bucket{' in text and 'le="+Inf"' in text
    assert "c_seconds_count" in text and "c_seconds_sum" in text
    # the dict snapshot is pure JSON (journal/snapshot-safe)
    snap = reg.snapshot()
    assert json.loads(json.dumps(snap)) == snap


def test_registry_export_restore_roundtrip():
    reg = MetricsRegistry()
    reg.counter("a_total", "").inc(7, kind="x")
    reg.gauge("g", "").set(2.5)
    h = reg.histogram("h_seconds", "")
    for v in (0.001, 0.02, 0.3):
        h.observe(v, tenant="t")
    back = MetricsRegistry()
    back.restore(reg.export())
    assert back.snapshot() == reg.snapshot()
    assert back.render_prometheus() == reg.render_prometheus()


def test_counter_watermark_floors_are_elementwise_max():
    reg = MetricsRegistry()
    reg.counter("a_total", "").inc(10, kind="x")
    reg.counter("a_total", "").inc(2, kind="y")
    wm = reg.counter_watermark()
    low = MetricsRegistry()
    low.counter("a_total", "").inc(4, kind="x")   # below the floor
    low.counter("a_total", "").inc(9, kind="y")   # above it
    low.apply_watermark(wm)
    c = low.counter("a_total", "")
    assert c.get(kind="x") == 10    # raised
    assert c.get(kind="y") == 9     # kept (max, not overwrite)
    # applying twice changes nothing (idempotent)
    low.apply_watermark(wm)
    assert c.get(kind="x") == 10 and c.get(kind="y") == 9


# -- HookConfig knobs ---------------------------------------------------------

def test_hookcfg_obs_roundtrip(tmp_path):
    cfg = HookConfig(obs_enabled=True, obs_sink="jsonl:/tmp/m.jsonl",
                     obs_snapshot_interval_s=2.5)
    path = tmp_path / "obs.json"
    cfg.save(path)
    back = HookConfig.load(path)
    assert back == cfg
    assert back.obs_enabled is True
    assert back.obs_sink == "jsonl:/tmp/m.jsonl"
    assert back.obs_snapshot_interval_s == 2.5


def test_hookcfg_obs_defaults_are_inert():
    cfg = HookConfig()
    assert cfg.obs_enabled is False
    assert cfg.obs_sink == "" and cfg.obs_snapshot_interval_s == 0.0


def test_obs_sink_validation_names_the_value():
    with pytest.raises(ValueError, match="carrier-pigeon"):
        make_sink("carrier-pigeon")
    with pytest.raises(ValueError, match="carrier-pigeon"):
        FleetServer(pool=1, gen_steps=48, fuel=FUEL,
                    cfg=HookConfig(obs_enabled=True,
                                   obs_sink="carrier-pigeon"))
    assert make_sink("") is None
    assert isinstance(make_sink("memory"), MemorySink)
    assert isinstance(make_sink("jsonl:/tmp/x.jsonl"), JsonlSink)
    assert isinstance(make_sink("/tmp/x.jsonl"), JsonlSink)
    assert isinstance(make_sink("prom:/tmp/x.prom"), PromFileSink)


def test_disabled_server_allocates_no_registry():
    before = MetricsRegistry.created_total
    srv = FleetServer(pool=1, gen_steps=48, fuel=FUEL)
    srv.submit(_pp("getpid"), regs={19: 4})
    _drain(srv)
    assert MetricsRegistry.created_total == before
    assert srv.metrics() == {} and srv.metrics("prometheus") == ""
    assert srv.stats()["obs_enabled"] is False
    # the disabled phase helper is the shared null singleton
    assert obs_phase(None, "harvest") is obs_phase(None, "dispatch")


# -- observation never steers -------------------------------------------------

def test_observed_run_is_bit_identical_to_unobserved():
    def run(obs):
        srv = FleetServer(pool=2, gen_steps=48, fuel=FUEL, trace=True,
                          cfg=HookConfig(obs_enabled=obs,
                                         trace_enabled=True))
        for i in range(3):
            srv.submit(_pp("getpid"), regs={19: 4 + i}, tenant="a")
            srv.submit(_pp("storm"), regs={19: 6, 20: 2, 21: 8},
                       tenant="b")
        return sorted(_state_key(r) for r in _drain(srv))

    assert run(False) == run(True)


def test_metrics_fmt_validation():
    srv = FleetServer(pool=1, gen_steps=48, fuel=FUEL,
                      cfg=HookConfig(obs_enabled=True))
    with pytest.raises(ValueError, match="csv"):
        srv.metrics(fmt="csv")


# -- phase profiler -----------------------------------------------------------

def test_phases_cover_the_generation_loop():
    srv = FleetServer(pool=2, gen_steps=48, fuel=FUEL,
                      cfg=HookConfig(obs_enabled=True),
                      scheduler=PolicyScheduler())
    for i in range(4):
        srv.submit(_pp("getpid"), regs={19: 5}, tenant="t")
    _drain(srv)
    m = srv.metrics()
    for name in ("dispatch", "harvest", "admission", "rebucket",
                 "sched_pass", "device_sync"):
        assert name in m["phases"], name
        assert m["phases"][name]["count"] >= 1
        assert name in PHASES
    # phases explain the generation wall-clock without double counting
    assert 0.75 <= m["phase_coverage"] <= 1.05, m["phase_coverage"]
    assert m["generation"]["count"] == srv.generation
    # dispatch + device_sync dominate a compute-bound drain
    assert m["phases"]["dispatch"]["share"] > 0.2


def test_phase_timer_records_on_error():
    hub = ObsHub()
    with pytest.raises(RuntimeError):
        with hub.phase("harvest"):
            raise RuntimeError("boom")
    assert hub.profiler.counts["harvest"] == 1


def test_profiler_inflight_credit_in_exports():
    hub = ObsHub()
    with hub.phase("snapshot_write"):
        d = hub.profiler.export()
        assert d["counts"]["snapshot_write"] == 1   # in-flight credit
        assert hub.profiler.counts.get("snapshot_write") is None
    assert hub.profiler.counts["snapshot_write"] == 1
    assert hub.profiler.export()["counts"]["snapshot_write"] == 1


def _streamed_server(obs=True, pool=2):
    cfg = HookConfig(obs_enabled=obs, trace_enabled=True, trace_cap=8)
    return FleetServer(pool=pool, cfg=cfg, gen_steps=48, chunk=8, fuel=FUEL,
                       stream=True)


def _submit_streamed(srv):
    for i in range(3):
        srv.submit(_pp("getpid"), regs={19: 4 + i}, tenant="a")
        srv.submit(_pp("storm"), regs={19: 6, 20: 2, 21: 8}, tenant="b")


def test_child_phases_nest_under_their_parents():
    srv = _streamed_server()
    _submit_streamed(srv)
    _drain(srv)
    m = srv.metrics()
    phases = m["phases"]
    children = [n for n in phases if "/" in n]
    for name in ("admission/initial_state", "admission/image_row",
                 "admission/scatter", "dispatch/enqueue",
                 "dispatch/device_wait", "harvest/readback", "harvest/c3",
                 "harvest/unstack", "harvest/publish", "stream_flush/copy",
                 "stream_flush/reassemble"):
        assert name in children, name
    for name in children:
        assert name in PHASES
        parent = name.split("/")[0]
        assert set(phases[name]) == set(phases[parent])
        assert phases[name]["count"] >= 1
        assert phases[name]["total_s"] <= phases[parent]["total_s"]
    for parent in ("admission", "dispatch", "harvest", "stream_flush"):
        kids = sum(phases[n]["total_s"] for n in children
                   if n.startswith(parent + "/"))
        assert kids <= phases[parent]["total_s"]
    # children are never counted twice: coverage keeps its meaning
    assert 0.75 <= m["phase_coverage"] <= 1.05, m["phase_coverage"]
    top = sum(p["total_s"] for n, p in phases.items() if "/" not in n)
    assert m["phase_coverage"] == pytest.approx(
        top / m["generation"]["total_s"])


def test_stream_flush_children_explain_it():
    """On a streamed server whose flushes carry the records of many lanes,
    the cold half's landing on the host and its per-lane reassembly nest
    inside ``stream_flush`` and explain at least 90% of it."""
    cfg = HookConfig(obs_enabled=True, trace_enabled=True, trace_cap=16)
    srv = FleetServer(pool=128, cfg=cfg, gen_steps=48, chunk=8, fuel=FUEL,
                      stream=True)
    for _ in range(128):
        srv.submit(_pp("storm"), regs={19: 6, 20: 8, 21: 0}, tenant="t")
    _drain(srv)
    phases = srv.metrics()["phases"]
    flush = phases["stream_flush"]
    kids = [phases["stream_flush/copy"], phases["stream_flush/reassemble"]]
    assert all(k["count"] == flush["count"] for k in kids)
    assert sum(k["total_s"] for k in kids) >= 0.9 * flush["total_s"]


def test_nested_inflight_exports_and_watermarks_stay_monotone():
    hub = ObsHub()
    with hub.phase("harvest"):
        with hub.phase("harvest/unstack"):
            d = hub.profiler.export()
            wm = hub.watermark()
            # both open timers are credited, the child and its parent
            for name in ("harvest", "harvest/unstack"):
                assert d["counts"][name] == 1
                assert wm["profile"]["counts"][name] == 1
                assert hub.profiler.counts.get(name) is None
        mid = hub.profiler.export()
        assert mid["counts"] == {"harvest": 1, "harvest/unstack": 1}
        assert mid["totals"]["harvest/unstack"] >= \
            d["totals"]["harvest/unstack"]
        assert mid["totals"]["harvest"] >= d["totals"]["harvest"]
    final = hub.profiler.export()
    for name in ("harvest", "harvest/unstack"):
        assert final["counts"][name] == 1
        assert final["totals"][name] >= mid["totals"][name]
    assert final["totals"]["harvest/unstack"] <= final["totals"]["harvest"]
    # a server recovered from the export taken inside the child, floored
    # at the watermark, never sits below what was read inside it
    rec = ObsHub()
    rec.restore({"profiler": d})
    rec.apply_watermark(wm)
    for name in ("harvest", "harvest/unstack"):
        assert rec.profiler.counts[name] == 1
        assert rec.profiler.totals[name] >= wm["profile"]["totals"][name]


def test_timers_build_no_annotation_without_a_profiler_trace():
    hub = ObsHub()
    with hub.phase("dispatch") as t:
        assert t._annot is None
    with obs_step_annotation(3, rung=8) as s:
        assert s is obs_phase(None, "harvest")    # the shared no-op


def test_streamed_observed_run_is_bit_identical_to_unobserved():
    def run(obs):
        srv = _streamed_server(obs)
        _submit_streamed(srv)
        return sorted((_state_key(r),
                       tuple(dataclasses.astuple(t) for t in r.trace))
                      for r in _drain(srv))

    assert run(False) == run(True)


def test_profiler_trace_holds_the_fleet_spans(tmp_path):
    """Phase and child spans, and the generation step annotation, are
    written into a ``jax.profiler`` trace beside the device's events."""
    import glob

    import jax
    from jax.profiler import ProfileData
    srv = _streamed_server()
    _submit_streamed(srv)
    _drain(srv)                                  # compiles outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0                 # as the chip benchmark
    opts.host_tracer_level = 1                   # traces its served cell
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _submit_streamed(srv)
        _drain(srv)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    for name in ("fleet.admission", "fleet.admission/initial_state",
                 "fleet.dispatch/device_wait", "fleet.harvest/unstack",
                 "fleet.stream_flush/copy", "fleet.stream_flush/reassemble",
                 "fleet.generation"):
        assert name in names, name


# -- lifecycle spans + resume-wait split --------------------------------------

def test_spans_and_resume_waits_split_from_admission_waits():
    """Budget exhaustion parks the noisy tenant's lanes mid-flight; the
    re-admissions must land in the resume ledger (satellite fix: they
    used to be invisible — ``_wait_s`` only recorded first admission)
    and as preempt->resume span events, with per-tenant latency
    histograms closing every span."""
    sched = PolicyScheduler(budgets={"noisy": TenantBudget(max_svc=8)})
    srv = FleetServer(pool=2, gen_steps=48, chunk=8, fuel=FUEL, trace=True,
                      cfg=HookConfig(obs_enabled=True, trace_enabled=True),
                      scheduler=sched)
    rids = [srv.submit(_pp("storm"), regs={19: 30, 20: 2, 21: 10},
                       tenant="noisy") for _ in range(3)]
    results = {r.rid: r for r in _drain(srv, 20000)}
    assert set(results) == set(rids)
    st = srv.stats()
    assert st["budget_exhaustions"] >= 1
    assert st["resume_waits"] >= 1, "park->resume cycles not recorded"
    assert st["resume_wait_gens_max"] >= 1
    # the two ledgers are distinct: first admissions never pay a resume
    assert st["admission_waits"] == len(rids)

    m = srv.metrics()
    ev = m["spans"]["events"]
    assert ev["submit"] == 3 and ev["complete"] == 3
    assert ev.get("preempt", 0) >= 1 and ev.get("resume", 0) >= 1
    assert m["spans"]["open"] == 0
    lat = m["spans"]["latency_by_tenant"]["noisy"]
    assert lat["count"] == 3 and lat["min"] > 0
    # resume-wait histogram observed once per re-admission
    h = m["histograms"]["server_resume_wait_seconds"]
    assert h["_"]["count"] == st["resume_waits"]
    # scheduler decisions surfaced as typed counters
    assert m["counters"]["sched_decisions_total"][
        '{decision="budget_exhausted"}'] >= 1


def test_c3_readmission_span_event():
    srv = FleetServer(pool=1, gen_steps=48, fuel=FUEL, trace=True,
                      cfg=HookConfig(obs_enabled=True, trace_enabled=True))
    srv.submit(prepare(programs.mixed_ops(6, 64), Mechanism.ASC,
                       virtualize=True), tenant="t")
    _drain(srv, 20000)
    st = srv.stats()
    m = srv.metrics()
    if st["c3_readmissions"]:       # mixed_ops exercises the C3 path
        assert m["spans"]["events"].get("c3_readmit", 0) >= 1
    assert m["spans"]["open"] == 0


def test_span_idempotent_after_completion():
    hub = ObsHub()
    t = now()
    hub.spans.submit("7", "t", t)
    hub.spans.event("7", "admit", "t", t + 0.01)
    hub.spans.event("7", "complete", "t", t + 0.02)
    before = hub.spans.summary()
    # at-least-once publication: duplicate completes must not double-count
    hub.spans.event("7", "complete", "t", t + 0.03)
    hub.spans.event("7", "admit", "t", t + 0.04)
    assert hub.spans.summary() == before
    assert hub.spans.open_count == 0 and hub.spans.completed_count == 1


# -- ledger gauges ------------------------------------------------------------

def test_ledger_gauges_surface_server_state(tmp_path):
    srv = FleetServer(pool=2, gen_steps=48, fuel=FUEL,
                      cfg=HookConfig(obs_enabled=True,
                                     snapshot_interval=3,
                                     journal_fsync=False),
                      scheduler=PolicyScheduler(),
                      durability=DurabilityManager(tmp_path / "d"))
    srv.submit(BUILDERS["obs-getpid"], mechanism=Mechanism.ASC,
               virtualize=True, fuel=FUEL, tenant="t")
    _drain(srv)
    g = srv.metrics()["gauges"]
    st = srv.stats()
    assert g["server_pool_lanes"]["_"] == 2
    assert g["server_completed"]["_"] == st["completed"] == 1
    assert g["server_generation"]["_"] == srv.generation
    assert g["server_dispatched_steps"]["_"] == st["dispatched_steps"]
    assert g["server_executed_steps"]["_"] == st["executed_steps"]
    assert g["server_occupancy"]["_"] == pytest.approx(st["occupancy"],
                                                       abs=1e-3)
    assert g["server_bucket_width"]["_"] >= 1
    assert g["server_queue_depth"]["_"] == 0
    assert g["sched_quarantine_depth"]["_"] == 0
    assert g["journal_bytes"]["_"] > 0
    assert g["journal_records"]["_"] == st["journal_records"]
    # journal/snapshot phases were timed
    phases = srv.metrics()["phases"]
    assert phases["journal_append"]["count"] >= srv.generation
    assert phases["snapshot_write"]["count"] >= 1


# -- chaos counters -----------------------------------------------------------

def test_chaos_injections_and_resolutions_counted():
    from repro.serve.chaos import ChaosMonkey
    srv = FleetServer(pool=1, gen_steps=48, fuel=FUEL,
                      cfg=HookConfig(obs_enabled=True, chaos_max_retries=2),
                      chaos=ChaosMonkey(plan={1: ["dispatch"]}))
    srv.submit(_pp("getpid"), regs={19: 4}, tenant="t")
    _drain(srv, 20000)
    m = srv.metrics()
    assert m["counters"]["chaos_injections_total"][
        '{kind="dispatch"}'] == 1
    assert m["counters"]["chaos_resolutions_total"][
        '{outcome="retried"}'] == 1
    assert srv._chaos.unresolved() == []
    # the retry backoff sleep is a priced phase
    assert m["phases"]["retry_backoff"]["count"] >= 1


# -- sinks --------------------------------------------------------------------

def test_memory_jsonl_and_prom_sinks(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a_total", "").inc(5)
    mem = MemorySink(cap=2)
    for i in range(4):
        mem.write(reg, now())
    assert len(mem.snapshots) == 2    # ring keeps the newest

    jpath = tmp_path / "m.jsonl"
    js = make_sink(f"jsonl:{jpath}")
    js.write(reg, now())
    js.write(reg, now())
    lines = jpath.read_text().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0])["counters"]["a_total"]["_"] == 5

    ppath = tmp_path / "m.prom"
    ps = make_sink(f"prom:{ppath}")
    ps.write(reg, now())
    assert "a_total 5" in ppath.read_text()
    ps.write(reg, now())              # atomic rewrite, not append
    assert ppath.read_text().count("a_total 5") == 1


def test_server_writes_sink_at_interval(tmp_path):
    jpath = tmp_path / "srv.jsonl"
    srv = FleetServer(pool=1, gen_steps=48, fuel=FUEL,
                      cfg=HookConfig(obs_enabled=True,
                                     obs_sink=f"jsonl:{jpath}",
                                     obs_snapshot_interval_s=0.0))
    srv.submit(_pp("getpid"), regs={19: 4})
    _drain(srv)
    assert not jpath.exists()         # interval 0 = never due
    srv._obs.maybe_snapshot(force=True)
    assert jpath.exists()
    assert srv.metrics()["sink_writes"] == 1


# -- kill-and-recover: monotone + span-complete -------------------------------

def _mk_durable(d, obs=True):
    cfg = HookConfig(trace_enabled=True, compact_enabled=True,
                     snapshot_interval=3, journal_fsync=False,
                     obs_enabled=obs)
    return FleetServer(4, cfg=cfg, gen_steps=48, fuel=FUEL,
                       scheduler=PolicyScheduler(
                           budgets={"b": TenantBudget(max_svc=40)}),
                       durability=DurabilityManager(d))


def _feed(srv):
    for _ in range(3):
        srv.submit(programs.getpid_loop, mechanism=Mechanism.ASC,
                   virtualize=True, fuel=FUEL, tenant="a", priority=1)
        srv.submit(BUILDERS["obs-mixed"], mechanism=Mechanism.ASC,
                   virtualize=True, fuel=FUEL, tenant="b")


@pytest.mark.parametrize("kill_gen", [2, 5, 7])
def test_recovery_is_monotone_and_span_complete(tmp_path, kill_gen):
    """Kill after ``kill_gen`` generations (journal-only, at the
    snapshot boundary, and mid-window past it).  The recovered server's
    counters, phase counts and generation count must never sit below
    what a ``metrics()`` scraper read from the dead server between
    steps, and every span it was tracking must still complete."""
    vic = _mk_durable(tmp_path / "vic")
    _feed(vic)
    for _ in range(kill_gen):
        vic.step()
    pre_counters = vic._obs.registry.counter_watermark()
    pre_phase_counts = dict(vic._obs.profiler.counts)
    pre_gen_count = vic._obs.profiler.gen_count
    pre_span_events = dict(vic._obs.spans.summary()["events"])
    del vic                                       # the crash

    srv, replayed = FleetServer.recover(tmp_path / "vic")
    assert srv._obs is not None, "obs_enabled lost across recovery"
    hub = srv._obs
    assert hub.profiler.gen_count >= pre_gen_count
    for name, v in pre_phase_counts.items():
        assert hub.profiler.counts.get(name, 0) >= v, name
    post_counters = hub.registry.counter_watermark()
    for series, v in pre_counters.items():
        assert post_counters.get(series, 0) >= v, series
    post_events = hub.spans.summary()["events"]
    for ev, v in pre_span_events.items():
        assert post_events.get(ev, 0) >= v, ev

    _drain(srv, 20000)
    m = srv.metrics()
    assert m["spans"]["open"] == 0, "a span never completed"
    assert m["spans"]["completed"] >= 6
    assert m["counters"]["requests_completed_total"]['{tenant="a"}'] >= 3
    assert m["counters"]["requests_completed_total"]['{tenant="b"}'] >= 3


def test_unobserved_durable_server_recovers_unobserved(tmp_path):
    vic = _mk_durable(tmp_path / "vic", obs=False)
    _feed(vic)
    for _ in range(4):
        vic.step()
    del vic
    srv, _ = FleetServer.recover(tmp_path / "vic")
    assert srv._obs is None
    _drain(srv, 20000)
    assert srv.metrics() == {}
