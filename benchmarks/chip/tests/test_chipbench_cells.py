"""Each cell run end to end on the CPU at a size a test holds, with the
harness's look for a chip skipped: sound runs come out correct, and the
control and every fault planted in the timed path come out not correct.

All runs share one process, so the programs compile once for the file.
"""
import time

import pytest

import _chipbench_path  # noqa: F401  (puts the benchmark on sys.path)

import harness  # noqa: E402

SMALL = {
    "io_monitor.served": {"pool": 16, "compact_min_bucket": 8,
                          "rate_per_s": 2.0, "drain_limit_s": 20.0,
                          "scalar_sample_random": 1},
    "getpid_hook.batch": {"pool": 8, "compact_min_bucket": 8,
                          "scalar_sample_per_mechanism": 1},
}
SECONDS = {"io_monitor.served": 3.0, "getpid_hook.batch": 1.0}
SEED = 2**32 + 99
CELLS = sorted(SMALL)


def run(cell, *, seed=SEED, control=False, resize=None):
    return harness.run_cell(
        workload=cell, seed=seed, seconds=SECONDS[cell], trace=False,
        t_start=time.perf_counter(), device_kind="TPU v5 lite",
        control=control, resize={**SMALL[cell], **(resize or {})})


def failed_checks(result):
    return [k for k, c in result["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]


# -- faults planted under the span program -------------------------------------

def frozen(real):
    """A step that returns its state unchanged."""
    def span(imgs, states, img_ids, *, trace=None, **kw):
        return states if trace is None else (states, trace)
    return span


def half_batch(real):
    """Half of the batch left out: every odd lane keeps its state (live
    lanes sit in a dense prefix of a compacted pool, so the upper half of
    its width is often empty)."""
    import jax
    import jax.numpy as jnp

    def span(imgs, states, img_ids, *, trace=None, **kw):
        old = jax.tree_util.tree_map(jnp.copy, states)
        out = real(imgs, states, img_ids, trace=trace, **kw)
        new = out if trace is None else out[0]
        w = new.pc.shape[0]
        keep = jnp.arange(w) % 2 == 0
        merged = jax.tree_util.tree_map(
            lambda n, o: jnp.where(keep.reshape((-1,) + (1,) * (n.ndim - 1)),
                                   n, o), new, old)
        return merged if trace is None else (merged, out[1])
    return span


def altered(real):
    """An answer altered where it is produced: the first running lane's
    output checksum moves by one word."""
    import jax.numpy as jnp

    def span(imgs, states, img_ids, *, trace=None, **kw):
        out = real(imgs, states, img_ids, trace=trace, **kw)
        new = out if trace is None else out[0]
        running = new.halted == 0
        i = jnp.argmax(running)
        new = new._replace(out_sum=new.out_sum.at[i].add(
            jnp.where(running[i], 8, 0)))
        return new if trace is None else (new, out[1])
    return span


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct(cell):
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert "setup_s" in r["metrics"]
    assert r["diagnostics"]["compiles_in_window"] == 0


def test_batch_counts_calls_per_mechanism():
    r = run("getpid_hook.batch")
    total = r["metrics"]["getpid_calls_per_s"]["value"]
    counts = r["diagnostics"]["counts"]
    calls = counts["loop_iterations"]
    assert sorted(calls) == ["ASC", "LD_PRELOAD", "PTRACE", "SIGNAL"]
    assert total > 0 and all(n > 0 for n in calls.values())
    ctx = {"counts": counts, "window_s": sum(calls.values()) / total}
    per = [harness.load_module("metrics", f"getpid_calls_per_s.{m}.py")
           .reduce(ctx) for m in ("asc", "signal", "ptrace", "ld_preload")]
    assert sum(per) == pytest.approx(total)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    r = run(cell, control=True)
    assert not r["correct"]
    assert "reference_mismatch" in failed_checks(r)


@pytest.mark.parametrize("fault", [frozen, half_batch, altered],
                         ids=["frozen", "half_batch", "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    from repro.core import fleet as F
    monkeypatch.setattr(F, "run_fleet_span", fault(F.run_fleet_span))
    r = run(cell, resize={"drain_limit_s": 2.0}
            if cell.endswith("served") else None)
    assert not r["correct"], r["checks"]
    assert failed_checks(r)
