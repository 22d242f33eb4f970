"""Continuous-batching server equivalence (marked ``serving``).

The property the server must never break: for ANY arrival order, pool
width and generation granularity, each request's published machine state is
bit-identical to ``run_prepared`` of that process alone — continuous
batching, in-place admission and donated buffers are scheduling, never
semantics.  Example counts default low so tier-1 stays fast; raise
``ASC_TEST_EXAMPLES`` for the heavy tier (see tests/README.md).
"""
import os

import numpy as np
import pytest
from _hyp_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core import (HookConfig, Mechanism, prepare, programs,
                        run_prepared, run_with_c3, layout as L, mem_read)
from repro.serve.fleet_server import FleetServer

pytestmark = pytest.mark.serving

FUEL = 150_000
MAX_EXAMPLES = int(os.environ.get("ASC_TEST_EXAMPLES", "5"))

_SETTINGS = dict(max_examples=MAX_EXAMPLES, deadline=None, derandomize=True)
if HAVE_HYPOTHESIS:
    from hypothesis import HealthCheck
    _SETTINGS["suppress_health_check"] = list(HealthCheck)

MECHS = [Mechanism.NONE, Mechanism.LD_PRELOAD, Mechanism.ASC,
        Mechanism.SIGNAL, Mechanism.PTRACE]

# Parameterised workloads (iteration count in x19) so every (workload,
# mechanism) cell prepares ONCE and hypothesis examples stay cheap.
_WORKLOADS = {
    "getpid": programs.getpid_loop_param,
    "read": lambda: programs.read_loop_param(256),
}

_pp_cache = {}
_ref_cache = {}


def _pp(wname, mech):
    key = (wname, mech)
    if key not in _pp_cache:
        virt = mech is not Mechanism.NONE
        _pp_cache[key] = prepare(_WORKLOADS[wname](), mech, virtualize=virt)
    return _pp_cache[key]


def _ref(wname, mech, n):
    key = (wname, mech, n)
    if key not in _ref_cache:
        _ref_cache[key] = run_prepared(_pp(wname, mech), fuel=FUEL,
                                       regs={19: n})
    return _ref_cache[key]


def _assert_state_equal(ref, got, ctx):
    for field in ref._fields:
        a, b = np.asarray(getattr(ref, field)), np.asarray(getattr(got, field))
        assert np.array_equal(a, b), f"{ctx}: field {field!r} diverged"


@settings(**_SETTINGS)
@given(data=st.data())
def test_any_arrival_order_matches_run_prepared(data):
    """programs x mechanisms x pool sizes: served state == solo state."""
    pool = data.draw(st.integers(1, 3), label="pool")
    gen_steps = data.draw(st.sampled_from([40, 96]), label="gen_steps")
    n_reqs = data.draw(st.integers(1, 6), label="n_reqs")
    reqs = [(data.draw(st.sampled_from(sorted(_WORKLOADS)), label="w"),
             data.draw(st.sampled_from(MECHS), label="m"),
             data.draw(st.integers(1, 12), label="n"))
            for _ in range(n_reqs)]

    srv = FleetServer(pool=pool, gen_steps=gen_steps, chunk=8, fuel=FUEL)
    rids = [srv.submit(_pp(w, m), regs={19: n}) for w, m, n in reqs]
    results = {r.rid: r for r in srv.run()}
    assert len(results) == len(reqs)
    assert srv.stats()["scalar_reexecutions"] == 0
    for rid, (w, m, n) in zip(rids, reqs):
        _assert_state_equal(_ref(w, m, n), results[rid].state,
                            f"pool={pool} gs={gen_steps} req=({w},{m},{n})")


@settings(**_SETTINGS)
@given(data=st.data())
def test_mid_flight_submission_matches(data):
    """Requests arriving while the pool is busy (the continuous part of
    continuous batching) publish the same states as up-front submission."""
    pool = data.draw(st.integers(1, 2), label="pool")
    first = data.draw(st.integers(4, 10), label="first")
    late = data.draw(st.integers(1, 8), label="late")
    mech = data.draw(st.sampled_from(MECHS), label="mech")

    srv = FleetServer(pool=pool, gen_steps=40, chunk=8, fuel=FUEL)
    rid0 = srv.submit(_pp("getpid", Mechanism.ASC), regs={19: first})
    results = {}
    for r in srv.step():
        results[r.rid] = r
    rid1 = srv.submit(_pp("read", mech), regs={19: late})  # mid-flight
    for r in srv.run():
        results[r.rid] = r
    _assert_state_equal(_ref("getpid", Mechanism.ASC, first),
                        results[rid0].state, "up-front request")
    _assert_state_equal(_ref("read", mech, late),
                        results[rid1].state, "mid-flight request")


def test_fuel_exhaustion_published_as_halt_fuel():
    from repro.core import HALT_FUEL
    pp = prepare(programs.getpid_loop(100_000), Mechanism.ASC, virtualize=True)
    ref = run_prepared(pp, fuel=700)
    srv = FleetServer(pool=2, gen_steps=64, fuel=700)
    rid = srv.submit(pp)
    res = {r.rid: r for r in srv.run()}
    assert int(ref.halted) == HALT_FUEL
    _assert_state_equal(ref, res[rid].state, "fuel-exhausted request")


def test_pack_fleet_admits_incrementally_through_a_table():
    """pack_fleet(table=...) routes image dedup through a fixed-capacity
    FleetImageTable: same ids/dedup as the stacking path, rows refcounted
    per lane, and the packed stack runs lanes bit-identically."""
    from repro.core import FleetImageTable, fleet, pack_fleet
    tbl = FleetImageTable(3)
    pps = [_pp("getpid", Mechanism.ASC), _pp("getpid", Mechanism.ASC),
           _pp("read", Mechanism.SIGNAL)]
    regs = [{19: 3}, {19: 5}, {19: 4}]
    _, ids, states = pack_fleet(pps, fuel=FUEL, regs=regs, table=tbl)
    assert list(ids) == [0, 0, 1]
    assert tbl.admissions == 2 and tbl.dedup_hits == 1
    assert tbl.live_rows() == 2
    out = fleet.run_fleet(tbl.images, states, ids, chunk=8)
    for i, (pp, rg) in enumerate(zip(pps, regs)):
        _assert_state_equal(run_prepared(pp, fuel=FUEL, regs=rg),
                            fleet.unstack_state(out, i), f"table-lane {i}")
    for r in ids:
        tbl.release(int(r))
    assert tbl.live_rows() == 0


def test_admission_waits_out_a_full_table():
    """More distinct live binaries than table rows: admission stalls (the
    request stays queued, nothing is lost or corrupted) until a lane
    finishes and frees its row."""
    srv = FleetServer(pool=2, gen_steps=64, fuel=FUEL, table_capacity=1)
    reqs = [("getpid", Mechanism.ASC, 4), ("read", Mechanism.SIGNAL, 3),
            ("getpid", Mechanism.ASC, 6)]
    rids = [srv.submit(_pp(w, m), regs={19: n}) for w, m, n in reqs]
    res = {r.rid: r for r in srv.run()}
    assert len(res) == 3
    for rid, (w, m, n) in zip(rids, reqs):
        _assert_state_equal(_ref(w, m, n), res[rid].state,
                            f"full-table req ({w},{m},{n})")
    assert srv.table.live_rows() == 0


def test_device_error_in_image_write_propagates(monkeypatch):
    """A device error while writing an image row (e.g. RESOURCE_EXHAUSTED,
    a RuntimeError like the table-full one) leaves ``run()`` instead of
    being read as a full table, and the row it was about to take stays
    free: once the device recovers the same request is served."""
    from repro.core import fleet as F
    srv = FleetServer(pool=2, gen_steps=64, fuel=FUEL, table_capacity=2)
    rid = srv.submit(_pp("getpid", Mechanism.ASC), regs={19: 4})
    real = F.set_image_row

    def failing(*args, **kwargs):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")

    monkeypatch.setattr(F, "set_image_row", failing)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        srv.run()
    assert srv.table.live_rows() == 0 and srv.table.admissions == 0
    monkeypatch.setattr(F, "set_image_row", real)
    res = srv.run()
    assert [r.rid for r in res] == [rid]
    _assert_state_equal(_ref("getpid", Mechanism.ASC, 4), res[0].state,
                        "request served after the device error")
    assert srv.table.live_rows() == 0


def test_image_table_full_is_its_own_error():
    from repro.core import FleetImageTable, ImageTableFull
    tbl = FleetImageTable(1)
    tbl.admit(_pp("getpid", Mechanism.ASC))
    with pytest.raises(ImageTableFull):
        tbl.admit(_pp("read", Mechanism.SIGNAL))
    assert tbl.live_rows() == 1


def test_image_table_dedups_and_recycles_rows():
    srv = FleetServer(pool=2, gen_steps=64, fuel=FUEL, table_capacity=3)
    pp = _pp("getpid", Mechanism.ASC)
    for n in (3, 4, 5, 6):
        srv.submit(pp, regs={19: n})
    srv.run()
    assert srv.table.admissions == 1          # one binary, one row write
    assert srv.table.dedup_hits == 3
    assert srv.table.live_rows() == 0         # all released after harvest
    # capacity bounds concurrent *distinct* binaries, not total requests
    for n in (2, 3):
        srv.submit(_pp("read", Mechanism.SIGNAL), regs={19: n})
    out = srv.run()
    assert len(out) == 2 and srv.table.admissions == 2


# -- fleet-native C3 (the acceptance workload) --------------------------------

def test_c3_workload_completes_with_zero_scalar_reexecutions():
    """R3-fault sites under the server: the trap -> pin -> re-admit cycle
    stays in-fleet and the event list matches run_with_c3's exactly."""
    cfg_ref = HookConfig()
    st_ref, _, ev_ref, runs_ref = run_with_c3(
        lambda: programs.indirect_svc(3), cfg=cfg_ref, virtualize=True,
        fuel=FUEL)
    assert runs_ref == 2 and len(ev_ref) == 1  # the Figure-4 story

    srv = FleetServer(pool=2, gen_steps=64, fuel=FUEL)
    rid = srv.submit(lambda: programs.indirect_svc(3), virtualize=True)
    # a bystander lane: recycling one lane must not disturb the others
    other = prepare(programs.getpid_loop(10), Mechanism.ASC, virtualize=True)
    rid_other = srv.submit(other)
    res = {r.rid: r for r in srv.run()}

    r = res[rid]
    assert r.events == ev_ref
    assert r.attempts == runs_ref
    _assert_state_equal(st_ref, r.state, "C3 request")
    assert mem_read(r.state, L.SCRATCH) == L.VIRT_PID  # transparency held
    stats = srv.stats()
    assert stats["scalar_reexecutions"] == 0
    assert stats["c3_readmissions"] == 1
    _assert_state_equal(run_prepared(other, fuel=FUEL),
                        res[rid_other].state, "bystander lane")


def test_c3_disabled_publishes_the_fault():
    cfg = HookConfig(enable_c3=False)
    pp = prepare(programs.indirect_svc(1), Mechanism.ASC, cfg=cfg)
    ref = run_prepared(pp, fuel=FUEL)
    srv = FleetServer(pool=1, gen_steps=64, fuel=FUEL)
    rid = srv.submit(pp)
    r = srv.run()[0]
    assert rid == r.rid and not r.events
    _assert_state_equal(ref, r.state, "C3-disabled fault")


def test_c3_table_full_publishes_fault_instead_of_corrupting():
    """Two lanes sharing one faulting binary in a capacity-1 table: the
    re-prepared image transiently needs a spare row.  The first harvested
    lane must degrade to publishing its fault (never corrupt the server);
    releasing its shared row then lets the second lane recycle."""
    from repro.core import HALT_EXIT, HALT_SEGV
    srv = FleetServer(pool=2, gen_steps=64, fuel=FUEL, table_capacity=1)
    cfg = HookConfig()
    rids = [srv.submit(lambda: programs.indirect_svc(1), cfg=cfg,
                       virtualize=True) for _ in range(2)]
    res = {r.rid: r for r in srv.run()}
    assert len(res) == 2
    halts = sorted(int(np.asarray(res[r].state.halted)) for r in rids)
    assert halts == [HALT_EXIT, HALT_SEGV]
    assert srv.stats()["c3_readmissions"] == 1
    assert srv.table.live_rows() == 0


def test_submit_rejects_conflicting_mechanism_for_prepared():
    srv = FleetServer(pool=1, gen_steps=64, fuel=FUEL)
    pp = _pp("getpid", Mechanism.ASC)
    with pytest.raises(ValueError):
        srv.submit(pp, mechanism=Mechanism.SIGNAL)


def test_c3_pins_shared_via_server_cfg():
    """A server-level config shares learned pins across requests, exactly
    like run_with_c3 with a shared HookConfig."""
    cfg = HookConfig()
    srv = FleetServer(pool=1, gen_steps=64, fuel=FUEL)
    rid1 = srv.submit(lambda: programs.indirect_svc(1), cfg=cfg,
                      virtualize=True)
    res1 = {r.rid: r for r in srv.run()}
    assert len(res1[rid1].events) == 1
    rid2 = srv.submit(lambda: programs.indirect_svc(5), cfg=cfg,
                      virtualize=True)
    res2 = {r.rid: r for r in srv.run()}
    assert res2[rid2].events == [] and res2[rid2].attempts == 1
