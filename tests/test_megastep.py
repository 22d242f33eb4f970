"""Pallas megastep engine suite (marked ``megastep``).

The engine contract: ``pallas == xla == scalar``, bit-exact.  All three
executors are generated from the one op-spec table
(:mod:`repro.core.opspec`), and the megastep kernel literally runs the
fleet's spec-generated step body on values held in kernel refs — so any
divergence is a real bug in the kernel plumbing (specs, aliasing,
blocking), never a semantic re-implementation drift.  The suite pins
that across mechanism x workload x chunk x compaction on/off, with
traced carries (rings, histograms, verdict counters) included, running
interpret-mode on forced-host devices (CPU never needs an accelerator).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core import (HookConfig, Mechanism, fleet, pack_fleet, prepare,
                        programs, run_fleet_prepared, run_prepared,
                        unstack_state)
from repro.kernels.megastep import ops as mops
from repro.kernels.megastep.kernel import default_interpret, megastep_chunk
from repro.kernels.megastep.ref import megastep_chunk_ref

pytestmark = pytest.mark.megastep

FUEL = 120_000
MAX_EXAMPLES = int(os.environ.get("ASC_TEST_EXAMPLES", "5"))

_SETTINGS = dict(max_examples=MAX_EXAMPLES, deadline=None, derandomize=True)
if HAVE_HYPOTHESIS:
    from hypothesis import HealthCheck
    _SETTINGS["suppress_health_check"] = list(HealthCheck)

MECHS = [Mechanism.NONE, Mechanism.LD_PRELOAD, Mechanism.ASC,
         Mechanism.SIGNAL, Mechanism.PTRACE]

_WORKLOADS = {
    "getpid": programs.getpid_loop_param,
    "read": lambda: programs.read_loop_param(256),
}

_pp_cache = {}


def _pp(wname, mech):
    key = (wname, mech)
    if key not in _pp_cache:
        virt = mech is not Mechanism.NONE
        _pp_cache[key] = prepare(_WORKLOADS[wname](), mech, virtualize=virt)
    return _pp_cache[key]


def _assert_tree_equal(ref, got, ctx):
    for field in ref._fields:
        a, b = np.asarray(getattr(ref, field)), np.asarray(getattr(got, field))
        assert np.array_equal(a, b), f"{ctx}: field {field!r} diverged"


def _mixed_fleet(short=3, long=40):
    pps, regs = [], []
    for mech in MECHS:
        for wname in _WORKLOADS:
            for n in (short, long):
                pps.append(_pp(wname, mech))
                regs.append({19: n})
    return pps, regs


# -- interpret-mode fallback --------------------------------------------------

def test_interpret_defaults_on_host_devices():
    """Tier-1 runs on CPU: the kernel must default to interpret mode there
    (and only compile natively on accelerator Pallas backends)."""
    if jax.default_backend() == "cpu":
        assert default_interpret() is True
    else:
        assert default_interpret() is False


# -- chunk-level kernel vs XLA oracle ----------------------------------------

def test_chunk_kernel_matches_ref():
    """One fused chunk == the fleet engine's own chunk scan, untraced and
    traced, including a lane-blocked grid and forced interpret mode."""
    pps, regs = _mixed_fleet()
    imgs, ids_np, states = pack_fleet(pps, fuel=FUEL, regs=regs)
    ids = jnp.asarray(ids_np, jnp.int32)
    ref = megastep_chunk_ref(imgs, ids, states, chunk=8)
    for block in (None, 4):
        got = mops.megastep(imgs, ids, states, chunk=8, block=block,
                            interpret=True)
        _assert_tree_equal(ref, got, f"untraced chunk, block={block}")

    tr = fleet.make_empty_trace(len(pps), 16)
    ref_s, ref_t = megastep_chunk_ref(imgs, ids, states, tr, chunk=8)
    got_s, got_t = mops.megastep(imgs, ids, states,
                                 fleet.make_empty_trace(len(pps), 16),
                                 chunk=8, interpret=True)
    _assert_tree_equal(ref_s, got_s, "traced chunk states")
    _assert_tree_equal(ref_t, got_t, "traced chunk trace carry")


def test_chunk_kernel_rejects_bad_block():
    pps, regs = _mixed_fleet()
    imgs, ids_np, states = pack_fleet(pps, fuel=FUEL, regs=regs)
    ids = jnp.asarray(ids_np, jnp.int32)
    with pytest.raises(ValueError, match="block"):
        megastep_chunk(imgs, ids, states, chunk=4, block=3)


# -- whole-run engine parity (the tentpole property) --------------------------

@settings(**_SETTINGS)
@given(mech=st.sampled_from(MECHS),
       wname=st.sampled_from(sorted(_WORKLOADS)),
       chunk=st.sampled_from([1, 5, 8]),
       compact=st.booleans(),
       n=st.integers(min_value=1, max_value=40))
def test_engine_parity_property(mech, wname, chunk, compact, n):
    """pallas == xla == scalar, bit-exact, for any mechanism x
    workload x chunk x compaction, untraced."""
    pp = _pp(wname, mech)
    pps = [pp] * 4
    regs = [{19: n}, {19: 1}, {19: max(1, n // 2)}, {19: n}]
    out_x = run_fleet_prepared(pps, fuel=FUEL, regs=regs, chunk=chunk,
                               compact=compact, engine="xla")
    out_p = run_fleet_prepared(pps, fuel=FUEL, regs=regs, chunk=chunk,
                               compact=compact, engine="pallas")
    ctx = f"{mech} {wname} chunk={chunk} compact={compact} n={n}"
    _assert_tree_equal(out_x, out_p, ctx)
    scalar = run_prepared(pp, fuel=FUEL, regs=regs[0])
    _assert_tree_equal(scalar, unstack_state(out_p, 0), f"{ctx} scalar")


@settings(**_SETTINGS)
@given(mech=st.sampled_from(MECHS),
       wname=st.sampled_from(sorted(_WORKLOADS)),
       chunk=st.sampled_from([1, 5, 8]),
       compact=st.booleans(),
       n=st.integers(min_value=1, max_value=40))
def test_engine_parity_traced_property(mech, wname, chunk, compact, n):
    """The traced carry — rings, histograms, verdict counters — is
    engine-invariant too, and the machine states stay bit-identical
    to the untraced run under the all-ALLOW default policy."""
    pp = _pp(wname, mech)
    pps = [pp] * 3
    regs = [{19: n}, {19: 1}, {19: max(1, n // 2)}]
    sx, tx = run_fleet_prepared(pps, fuel=FUEL, regs=regs, chunk=chunk,
                                compact=compact, trace=True,
                                engine="xla")
    sp, tp = run_fleet_prepared(pps, fuel=FUEL, regs=regs, chunk=chunk,
                                compact=compact, trace=True,
                                engine="pallas")
    ctx = f"{mech} {wname} chunk={chunk} compact={compact} n={n}"
    _assert_tree_equal(sx, sp, ctx + " states")
    _assert_tree_equal(tx, tp, ctx + " trace carry")
    plain = run_fleet_prepared(pps, fuel=FUEL, regs=regs, chunk=chunk,
                               compact=compact, engine="pallas")
    _assert_tree_equal(plain, sp, ctx + " traced-vs-untraced")


# -- span driver: generation-chained equivalence ------------------------------

def test_span_chaining_matches_unbounded_run():
    """Driving the fleet through bounded pallas spans (the serving path:
    no HALT_FUEL patch until harvest) reaches exactly the xla engine's
    run-to-halt state."""
    pps, regs = _mixed_fleet()
    imgs, ids_np, states = pack_fleet(pps, fuel=FUEL, regs=regs)
    ref = fleet.run_fleet(imgs, pack_fleet(pps, fuel=FUEL, regs=regs)[2],
                          ids_np, chunk=8, engine="xla")
    cur = states
    for _ in range(64):
        cur = fleet.run_fleet_span(imgs, cur, ids_np, steps=64, chunk=8,
                                   engine="pallas")
        halted = np.asarray(cur.halted)
        icount = np.asarray(cur.icount)
        fuel = np.asarray(cur.fuel)
        if not ((halted == fleet.RUNNING) & (icount < fuel)).any():
            break
    cur = cur._replace(halted=jnp.asarray(
        fleet.finish_halt_codes(np.asarray(cur.halted),
                                np.asarray(cur.icount),
                                np.asarray(cur.fuel))))
    _assert_tree_equal(ref, cur, "span-chained pallas vs unbounded xla")


# -- engine selection plumbing ------------------------------------------------

def test_engine_validation():
    pps, regs = _mixed_fleet()
    with pytest.raises(ValueError, match="unknown fleet engine"):
        run_fleet_prepared(pps[:2], fuel=1000, engine="cuda")
    with pytest.raises(ValueError, match="shard"):
        run_fleet_prepared(pps[:2], fuel=1000, engine="pallas", shard=True)


def test_pallas_refused_on_a_tpu_backend(monkeypatch):
    """The kernel's int64 blocks do not lower for a TPU: the engine is
    refused up front (a server fails at construction, not inside a
    generation), and no kernel is interpreted off the CPU backend."""
    from repro.serve.fleet_server import FleetServer
    assert default_interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="Mosaic"):
        fleet._check_engine("pallas")
    with pytest.raises(ValueError, match="ROADMAP A2"):
        FleetServer(pool=2, engine="pallas")
    assert fleet._check_engine("xla") == "xla"
    assert not default_interpret()


def test_hookcfg_engine_roundtrip(tmp_path):
    cfg = HookConfig(fleet_engine="pallas")
    path = tmp_path / "hook.json"
    cfg.save(path)
    got = HookConfig.load(path)
    assert got.fleet_engine == "pallas"
    assert HookConfig().fleet_engine == "xla"  # default stays the xla engine


def test_config_engine_drives_prepared_run():
    """``HookConfig.fleet_engine`` is honoured by run_fleet_prepared and
    produces bit-identical results to the explicit xla call."""
    cfg = HookConfig(fleet_engine="pallas")
    pps = [prepare(_WORKLOADS["getpid"](), Mechanism.ASC, cfg=cfg)] * 2
    regs = [{19: 5}, {19: 9}]
    out_cfg = run_fleet_prepared(pps, fuel=FUEL, regs=regs)
    out_xla = run_fleet_prepared(pps, fuel=FUEL, regs=regs, engine="xla")
    _assert_tree_equal(out_xla, out_cfg, "config-driven engine")


def test_fleet_server_engine_parity():
    """A pallas-engined server publishes bit-identical results (states,
    decoded traces, histograms) to the xla-engined one."""
    from repro.serve.fleet_server import FleetServer

    def go(engine):
        srv = FleetServer(pool=4, engine=engine, trace=True)
        srv.submit(lambda: programs.getpid_loop(6), mechanism=Mechanism.ASC,
                   fuel=FUEL)
        srv.submit(lambda: programs.mixed_ops(2, 64),
                   mechanism=Mechanism.SIGNAL, fuel=FUEL)
        return sorted(srv.run(), key=lambda r: r.rid)

    res_p, res_x = go("pallas"), go("xla")
    assert len(res_p) == len(res_x) == 2
    for x, p in zip(res_x, res_p):
        _assert_tree_equal(x.state, p.state, f"rid {x.rid}")
        assert [r.__dict__ for r in x.trace] == [r.__dict__ for r in p.trace]
        assert x.histogram == p.histogram
