"""The long-running I/O batch (``io_server.batch``) on the CPU: the cell at
a size a test holds is correct, its control and every planted fault are
not; the mid-flight reference for the four I/O loops accepts the plain
reference's own run cut at every syscall and rejects each observable
moved by one unit; the roofline's byte count on a made-up window.

All cell runs share one process, so the programs compile once for the
file.
"""
import time

import numpy as np
import pytest

import _chipbench_path  # noqa: F401  (puts the benchmark on sys.path)

import harness  # noqa: E402
import io_roofline  # noqa: E402
import reference as R  # noqa: E402
import reference_io as RI  # noqa: E402
import roofline  # noqa: E402
from test_chipbench_cells import frozen, half_batch  # noqa: E402

CELL = "io_server.batch"
SMALL = {"pool": 16, "compact_min_bucket": 16,
         "scalar_sample_per_workload": 1}
SEED = 2**33 + 15
WORKLOADS = sorted(R.NBYTES)


def run(*, control=False):
    return harness.run_cell(
        workload=CELL, seed=SEED, seconds=0.5, trace=False,
        t_start=time.perf_counter(), device_kind="TPU v5 lite",
        control=control, resize=SMALL)


def failed_checks(result):
    return [k for k, c in result["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]


def test_lanes_split_evenly_over_the_workloads_per_seed():
    kind = harness.load_module("traffic", "closed_io_batch.py")
    _, config, mix = harness.cell_parts(harness.benchmark(), CELL)
    a = kind.generate(mix, config, SEED, config["pool"])
    assert a == kind.generate(mix, config, SEED, config["pool"])
    assert a != kind.generate(mix, config, SEED + 1, config["pool"])
    per = config["pool"] // len(config["workloads"])
    assert {w: sum(x[1] == w for x in a) for w in WORKLOADS} == \
        dict.fromkeys(WORKLOADS, per)
    assert {x[0] for x in a} == {"ASC"}
    assert all(2**40 <= x[2] < 2**41 for x in a)


# -- faults planted in the ring's cold halves --------------------------------------

def _once_on_a_cold_half(change):
    """``flip_trace`` whose first cold half that holds a record is changed
    by ``change(cold_lane, counts, lane)`` before the host sees it."""
    def fault(real):
        done = []

        def flip(trace):
            trace, cold, counts, bases = real(trace)
            lanes = np.flatnonzero(counts > bases)
            if done or not len(lanes):
                return trace, cold, counts, bases
            done.append(1)
            cold, counts = np.array(cold), np.array(counts)
            change(cold[lanes[0]], counts, lanes[0])
            return trace, cold, counts, bases
        return flip
    return fault


def _bump_ret(half, counts, i):
    half[0, 6] += 1                   # REC_RET of the half's first record


def _drop_first(half, counts, i):
    half[:-1] = half[1:].copy()
    counts[i] -= 1


changed_ret = _once_on_a_cold_half(_bump_ret)
dropped_record = _once_on_a_cold_half(_drop_first)


# -- the cell ----------------------------------------------------------------------

def test_cell_is_correct():
    r = run()
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == SMALL["pool"]
    assert {"guest_steps_per_s", "setup_s"} <= set(r["metrics"])
    assert r["diagnostics"]["compiles_in_window"] == 0
    assert r["checks"]["scalar_sampled"]["value"] == len(WORKLOADS)
    w = r["diagnostics"]["counts"]["window"]
    assert sorted(w["lanes"]) == WORKLOADS
    # every record the lanes made in the window reached the stream
    assert w["stream"]["records_seen"] == w["ring_records"] > 0
    assert w["payload_bytes"] > 0
    # stream reads and sink writes move as the reference says
    io_bw = w["lanes"]["io_bandwidth_param"]
    assert io_bw["in_off"] + io_bw["out_count"] == io_bw["payload_bytes"]
    phases = r["diagnostics"]["window_phases_s"]
    assert {"stream_flush/copy", "stream_flush/reassemble"} <= set(phases)


def test_control_is_not_correct():
    r = run(control=True)
    assert not r["correct"]
    assert r["checks"]["reference_mismatch"]["value"] == SMALL["pool"]


@pytest.mark.parametrize(
    "target, fault",
    [("run_fleet_span", frozen), ("run_fleet_span", half_batch),
     ("flip_trace", changed_ret), ("flip_trace", dropped_record)],
    ids=["frozen", "half_batch", "changed_ret", "dropped_record"])
def test_fault_is_not_correct(target, fault, monkeypatch):
    from repro.core import fleet as F
    monkeypatch.setattr(F, target, fault(getattr(F, target)))
    r = run()
    assert not r["correct"], r["checks"]
    assert failed_checks(r)


# -- the mid-flight reference --------------------------------------------------------

X19 = 1000


def _cuts(workload, monkeypatch, iterations=2):
    """The plain reference's own run cut after each of its syscalls in the
    first ``iterations`` iterations: ``(records, obs, x19 values)`` per
    cut, the x19 values being every one the run shows there."""
    snaps = []
    real = R._Guest.call

    def call(self, name, args, ret):
        out = real(self, name, args, ret)
        snaps.append({"records": list(self.records), "in_off": self.in_off,
                      "out_count": self.out_count, "out_sum": self.out_sum,
                      "emul_served": self.emul, "syscalls": self.syscalls,
                      "getpids": self.getpids})
        return out

    monkeypatch.setattr(R._Guest, "call", call)
    g, extra = R._run(workload, "ASC", iterations)
    per_iter = RI.loop(workload, "ASC").calls_in[-1]
    for s, snap in enumerate(snaps[:iterations * per_iter], start=1):
        it, pos = divmod(s - 1, per_iter)
        counter, hook_count = R.hooks("ASC", snap["syscalls"],
                                      snap["getpids"])
        obs = {k: snap[k] for k in ("in_off", "out_count", "out_sum",
                                    "emul_served")}
        obs.update(halted=R.RUNNING, enosys_count=0, counter=counter,
                   hook_count=hook_count,
                   records_made=len(snap["records"]),
                   file_bytes=RI.loop(workload, "ASC").state(s)[
                       "file_bytes"])
        # x19 drops once the iteration's last syscall is issued
        x19s = [X19 - it] + ([X19 - it - 1] if pos == per_iter - 1 else [])
        records = snap["records"]
        if records[-1][0] == R.NR["rt_sigreturn"]:
            # the call is recorded a step before its return from SIGSYS
            yield records[:-1], dict(obs, records_made=len(records) - 1), \
                [X19 - it]
        yield records, obs, x19s
    if workload == "file_churn_param":
        assert obs["file_bytes"] == extra["files"]["churn.da"]


def _raw(records):
    rows = np.zeros((len(records), 8), np.int64)
    rows[:, RI.ROW_COLS] = records
    rows[:, 0] = np.arange(len(records))       # step and pc are not read
    return rows


@pytest.mark.parametrize("workload", WORKLOADS)
def test_closed_form_is_the_reference_run(workload):
    lp = RI.loop(workload, "ASC")
    g, _ = R._run(workload, "ASC", 3)
    n = 3 * len(lp.rows)
    for k in range(n + 1):
        assert lp.expected_rows(k).tolist() == [list(r) for r in
                                                g.records[:k]]
    assert lp.syscalls(n) == 3 * lp.calls_in[-1]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_reference_accepts_its_own_run_at_every_syscall(workload,
                                                        monkeypatch):
    n = 0
    for records, obs, x19s in _cuts(workload, monkeypatch):
        for x19 in x19s:
            assert RI.midflight(workload, "ASC", X19, dict(obs, x19=x19),
                                _raw(records)) == []
        # the hook of the next syscall has counted it
        nxt = dict(obs, x19=x19s[0], counter=obs["counter"] + 1)
        assert RI.midflight(workload, "ASC", X19, nxt, _raw(records)) == []
        n += 1
    lp = RI.loop(workload, "ASC")
    assert n == 2 * len(lp.rows)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_reference_rejects_each_observable_moved_by_one(workload,
                                                        monkeypatch):
    def bad(obs, rows):
        return RI.midflight(workload, "ASC", X19, obs, rows)

    for records, obs, x19s in _cuts(workload, monkeypatch):
        rows = _raw(records)
        obs = dict(obs, x19=x19s[0])
        for f in ("in_off", "out_count", "out_sum", "emul_served",
                  "enosys_count", "file_bytes", "halted", "hook_count",
                  "records_made"):
            for d in (-1, 1):
                assert f in bad(dict(obs, **{f: obs[f] + d}), rows), (f, d)
        assert "counter" in bad(dict(obs, counter=obs["counter"] - 1), rows)
        assert "counter" in bad(dict(obs, counter=obs["counter"] + 2), rows)
        assert "x19" in bad(dict(obs, x19=min(x19s) - 1), rows)
        assert "x19" in bad(dict(obs, x19=max(x19s) + 1), rows)
        for col in range(2, 8):
            moved = rows.copy()
            moved[-1, col] += 1
            assert "records" in bad(obs, moved), col
        # the last record lost: fewer rows than the lane made
        assert bad(obs, rows[:-1])


@pytest.mark.parametrize("workload", ["sqlite_param", "getpid_loop_param"])
def test_no_midflight_reference_but_for_the_io_loops(workload):
    with pytest.raises(KeyError):
        RI.loop(workload, "ASC")


# -- the roofline's bytes ------------------------------------------------------------

def test_io_roofline_on_a_made_up_count():
    shapes = {"regs": (31,), "pc": (), "mem": (32768,)}
    per_step = roofline.bytes_per_lane_step(shapes)       # 2 * 8 * 32 + 4
    window = {"payload_bytes": 3000, "ring_records": 10}
    assert io_roofline.data_bytes(3000, 10) == 3000 + 640
    # the window's 3,640 data bytes over 400 lane-steps, scaled to 100
    got = io_roofline.io_span_bytes(shapes, 100, window, 400)
    assert got == pytest.approx(100 * per_step + 3640 / 4)
    mod = harness.load_module("metrics", "io_span_roofline.py")
    ctx = {"traced": {"span_s": 1e-6, "delta": {"executed_steps": 100}},
           "window": {"executed_steps": 400}, "counts": {"window": window},
           "peaks": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}},
           "device_kind": "TPU v5 lite", "lane_shapes": shapes}
    assert mod.reduce(ctx) == pytest.approx(100 * got / 819e9 / 1e-6)
    # the parent's program, or an untraced run: nothing to read
    assert mod.reduce(dict(ctx, traced=None)) is None
    assert mod.reduce(dict(ctx, counts={})) is None


@pytest.mark.parametrize("workload, per_iteration", [
    ("read_loop_param", 1024), ("io_bandwidth_param", 2 * 4096),
    ("mixed_ops_param", 0), ("file_churn_param", 2 * 512)])
def test_payload_bytes_per_iteration(workload, per_iteration):
    lp = RI.loop(workload, "ASC")
    r = len(lp.rows)
    assert [lp.payload_bytes(i * r) for i in range(4)] == \
        [i * per_iteration for i in range(4)]
