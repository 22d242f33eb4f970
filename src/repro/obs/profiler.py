"""Phase profiler for the FleetServer generation loop (repro.obs).

Answers "where does a generation's wall-clock go" the way ASC-Hook's
cycle-breakdown tables answer "where do a hook's cycles go": every
stage of ``FleetServer.step()`` runs inside a named phase —

    sched_pass      policy admission ordering / preemption / eviction
    rebucket        compaction permute + ladder re-dispatch prep
    admission       pending-queue scatter into free lanes
    dispatch        XLA dispatch of the masked generation step
    device_sync     blocking on device completion (obs-only split)
    harvest         device->host readback, publish, C3 diagnose
    stream_flush    cold-half trace drain into the TraceStream
    journal_append  write-ahead journal group commit
    snapshot_write  full-fleet snapshot
    rollback_verify chaos-mode replay-verify at snapshot boundaries
    retry_backoff   chaos retry sleeps
    obs_snapshot    sink snapshot writes (self-observation, priced too)

A phase may open named child phases, written ``parent/child``, inside
itself: they split admission, dispatch, harvest and stream_flush by
cause (eager initial states vs the scatter, enqueue vs the wait for the
chip, readback vs per-lane unstacking vs publishing, the cold half's
landing on the host vs its per-lane reassembly).  Children report the
same fields as any phase; coverage counts top-level phases only, so a
child's time is never counted twice.

Every timer also opens a ``jax.profiler.TraceAnnotation`` named
``fleet.<phase>`` while a profiler trace is active, so the same spans
sit on the device trace's clock; without an active trace no annotation
(and no name for one) is built.  The obs clock stays the source of
every total.

Timings come from :func:`repro.obs.metrics.now` (monotonic) and land in
one labelled histogram (``server_phase_seconds{phase=...}``) plus a
plain totals dict, so ``breakdown()`` can report both percentiles and
the coverage ratio — the share of measured generation time the phases
explain, which ``benchmarks/obs_overhead.py`` requires to be >= 90%.

The timer is a plain class (not a generator contextmanager) to keep
per-phase overhead at two clock reads, one trace-state check and a few
dict and list ops.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.obs.metrics import Histogram, MetricsRegistry, now

PHASES = (
    "sched_pass", "rebucket", "admission", "dispatch", "device_sync",
    "harvest", "stream_flush", "journal_append", "snapshot_write",
    "rollback_verify", "retry_backoff", "obs_snapshot",
    # children: eager per-request initial states, image-table rows, the
    # padded admission/restore scatter
    "admission/initial_state", "admission/image_row", "admission/scatter",
    # span and flip enqueues vs the blocking wait for the sub-span
    "dispatch/enqueue", "dispatch/device_wait",
    # host transfers, C3 diagnosis and recycling, per-lane unstack + halt
    # patch, stream pop / histograms / charges / FleetResult
    "harvest/readback", "harvest/c3", "harvest/unstack", "harvest/publish",
    # the device->host landing of a flipped cold-half block vs its
    # per-lane reassembly into the stream
    "stream_flush/copy", "stream_flush/reassemble",
)
TRACE_PREFIX = "fleet."


class _PhaseTimer:
    """``with prof.phase("harvest"):`` — records on exit, even on error."""

    __slots__ = ("_prof", "_name", "_t0", "_annot")

    def __init__(self, prof: "PhaseProfiler", name: str):
        self._prof = prof
        self._name = name

    def __enter__(self):
        self._annot = None
        if TraceAnnotation.is_enabled():      # a profiler trace is active
            self._annot = TraceAnnotation(TRACE_PREFIX + self._name)
            self._annot.__enter__()
        self._t0 = now()
        self._prof._inflight.append((self._name, self._t0))
        return self

    def __exit__(self, *exc):
        dt = now() - self._t0
        self._prof._inflight.pop()
        self._prof.record(self._name, dt)
        if self._annot is not None:
            self._annot.__exit__(*exc)
        return False


class _NullTimer:
    """Shared no-op timer for the disabled path (zero allocation)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_TIMER = _NullTimer()


def step_annotation(step_num: int, **meta):
    """``jax.profiler.StepTraceAnnotation("fleet.generation")`` around one
    generation while a profiler trace is active, else the shared no-op."""
    if not TraceAnnotation.is_enabled():
        return NULL_TIMER
    return StepTraceAnnotation(TRACE_PREFIX + "generation",
                               step_num=step_num, **meta)


class PhaseProfiler:
    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self._hist: Histogram = registry.histogram(
            "server_phase_seconds", "wall-clock per generation-loop phase")
        self._gen: Histogram = registry.histogram(
            "server_generation_seconds", "wall-clock per generation")
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.gen_total = 0.0
        self.gen_count = 0
        # the phase timers currently open, outermost first, with their
        # start times: exports taken from *inside* a phase (journal
        # watermarks, snapshot writes) credit each of them with its
        # elapsed-so-far time so counts stay exactly monotone across a
        # crash-recovery cut
        self._inflight: List[Tuple[str, float]] = []

    # -- recording ------------------------------------------------------
    def phase(self, name: str) -> _PhaseTimer:
        return _PhaseTimer(self, name)

    def record(self, name: str, dt: float) -> None:
        self._hist.observe(dt, phase=name)
        self.totals[name] = self.totals.get(name, 0.0) + dt
        self.counts[name] = self.counts.get(name, 0) + 1

    def record_generation(self, dt: float) -> None:
        self._gen.observe(dt)
        self.gen_total += dt
        self.gen_count += 1

    # -- views ----------------------------------------------------------
    def breakdown(self) -> dict:
        """Per-phase totals/percentiles + share of generation time."""
        phases = {}
        for name in sorted(self.totals):
            s = self._hist.summary(phase=name)
            phases[name] = {
                "count": self.counts[name],
                "total_s": self.totals[name],
                "mean_ms": 1e3 * self.totals[name] / max(1, self.counts[name]),
                "p50_ms": 1e3 * s["p50"],
                "p95_ms": 1e3 * s["p95"],
                "p99_ms": 1e3 * s["p99"],
                "share": (self.totals[name] / self.gen_total
                          if self.gen_total else 0.0),
            }
        # children sit inside their parent: top-level phases only
        covered = sum(v for k, v in self.totals.items() if "/" not in k)
        return {
            "phases": phases,
            "generation": {"count": self.gen_count, "total_s": self.gen_total,
                           **{k: 1e3 * v for k, v in
                              (("p50_ms", self._gen.summary()["p50"]),
                               ("p95_ms", self._gen.summary()["p95"]),
                               ("p99_ms", self._gen.summary()["p99"]))}},
            "coverage": (covered / self.gen_total) if self.gen_total else 0.0,
        }

    # -- durability -----------------------------------------------------
    # Histogram state lives in the registry (snapshotted there); only the
    # plain totals need explicit export.
    def export(self) -> dict:
        d = {"totals": dict(self.totals), "counts": dict(self.counts),
             "gen_total": self.gen_total, "gen_count": self.gen_count}
        t = now()
        for name, t0 in self._inflight:
            d["counts"][name] = d["counts"].get(name, 0) + 1
            d["totals"][name] = d["totals"].get(name, 0.0) + (t - t0)
        return d

    def restore(self, d: Optional[dict]) -> None:
        if not d:
            return
        for k, v in d["totals"].items():
            self.totals[k] = self.totals.get(k, 0.0) + v
        for k, v in d["counts"].items():
            self.counts[k] = self.counts.get(k, 0) + v
        self.gen_total += d["gen_total"]
        self.gen_count += d["gen_count"]

    def raise_to(self, d: Optional[dict]) -> None:
        """Floor every total/count at a journaled watermark (elementwise
        max) — recovery's monotonicity backstop for timings the crashed
        server recorded after its last snapshot export."""
        if not d:
            return
        for k, v in d["totals"].items():
            self.totals[k] = max(self.totals.get(k, 0.0), v)
        for k, v in d["counts"].items():
            self.counts[k] = max(self.counts.get(k, 0), v)
        self.gen_total = max(self.gen_total, d["gen_total"])
        self.gen_count = max(self.gen_count, d["gen_count"])
