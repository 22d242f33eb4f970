"""CPU tests of the per-layer metrics that read the serving loop's child
spans from the window's obs phase totals."""
import pytest

import _chipbench_path as P  # noqa: F401  (puts the benchmark on sys.path)

import harness  # noqa: E402

NEW = ("admission_state_ms_per_req.served",
       "admission_scatter_ms_per_req.served",
       "harvest_unstack_ms_per_req.served", "device_wait_ms_per_gen.served",
       "host_self_ms_per_req.served")


def _reduce(name, ctx):
    return harness.load_module("metrics", name + ".py").reduce(ctx)


def _ctx(phases, admitted=40, completed=50, generations=200):
    return {"window": {"admission_waits": admitted, "completed": completed,
                      "generations": generations, "phases": phases}}


PHASES = {"sched_pass": 0.0, "rebucket": 0.5, "admission": 2.0,
          "admission/initial_state": 1.2, "admission/image_row": 0.1,
          "admission/scatter": 0.6, "dispatch": 3.0,
          "dispatch/enqueue": 0.4, "dispatch/device_wait": 2.5,
          "device_sync": 0.5, "harvest": 1.5, "harvest/readback": 0.3,
          "harvest/c3": 0.0, "harvest/unstack": 1.0,
          "harvest/publish": 0.1, "stream_flush": 0.2}


@pytest.mark.parametrize("name, want", [
    ("admission_state_ms_per_req.served", 1e3 * 1.2 / 40),
    ("admission_scatter_ms_per_req.served", 1e3 * 0.6 / 40),
    ("harvest_unstack_ms_per_req.served", 1e3 * 1.0 / 50),
    ("device_wait_ms_per_gen.served", 1e3 * (2.5 + 0.5) / 200),
    # top level: 0.5 + 2.0 + 3.0 + 0.5 + 1.5 + 0.2 = 7.7 s, less 3.0 s
    ("host_self_ms_per_req.served", 1e3 * (7.7 - 0.5 - 2.5) / 50),
])
def test_reduce_on_a_made_up_window(name, want):
    assert _reduce(name, _ctx(PHASES)) == pytest.approx(want)


def test_host_self_and_device_wait_make_up_the_top_level_phases():
    ctx = _ctx(PHASES)
    d = ctx["window"]
    host = _reduce("host_self_ms_per_req.served", ctx)
    wait = _reduce("device_wait_ms_per_gen.served", ctx)
    top = sum(v for k, v in PHASES.items() if "/" not in k)
    assert (host * d["completed"] + wait * d["generations"]) / 1e3 \
        == pytest.approx(top)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_gives_none(name):
    """A program without the child phases (the parent of this change), or
    a window with nothing admitted or published, gives no value and does
    not raise."""
    parent = {k: v for k, v in PHASES.items() if "/" not in k}
    assert _reduce(name, _ctx(parent)) is None
    assert _reduce(name, _ctx(PHASES, admitted=0, completed=0,
                              generations=0)) is None


def test_the_metrics_are_declared_for_the_served_cell():
    bench = harness.benchmark()
    traced = {m["name"]: m for m in harness.cell_metrics(
        bench, "io_monitor.served", True)}
    for name in NEW:
        m = traced[name]
        assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (
            "program_span", "serving loop", "latency_p95_ms",
            ["io_monitor.served"])
    batch = {m["name"] for m in harness.cell_metrics(
        bench, "getpid_hook.batch", True)}
    assert not batch & set(NEW)
