"""Fast fleet-engine smoke tests (marked ``smoke``): seconds, not minutes.

Run just these with ``pytest -m smoke`` for a quick signal; the exhaustive
bit-parity sweep lives in test_fleet_parity.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HALT_EXIT, HookConfig, Mechanism, fleet,
                        hook_invocations, layout as L, machine as M,
                        mem_read_block, prepare, programs,
                        run_fleet_prepared, unstack_state)

pytestmark = pytest.mark.smoke


@pytest.fixture(scope="module")
def tiny_fleet():
    pps = [prepare(programs.getpid_loop(5), Mechanism.ASC, virtualize=True),
           prepare(programs.getpid_loop(8), Mechanism.SIGNAL, virtualize=True)]
    out = run_fleet_prepared(pps, fuel=100_000, chunk=4)
    return pps, out


def test_fleet_runs_to_exit(tiny_fleet):
    _, out = tiny_fleet
    assert np.asarray(out.halted).tolist() == [HALT_EXIT, HALT_EXIT]
    assert np.asarray(out.exit_code).tolist() == [0, 0]


def test_fleet_counters_one_readback(tiny_fleet):
    """Per-lane hook counts come back in one transfer and match the lanes'
    getpid iteration counts (+1: the final exit syscall is hooked too)."""
    _, out = tiny_fleet
    counts = fleet.fleet_counters(out)
    assert counts.tolist() == [6, 9]
    # batched hook_invocations aggregates the fleet
    assert hook_invocations(out) == 15


def test_fleet_summary_rows(tiny_fleet):
    _, out = tiny_fleet
    rows = fleet.fleet_summary(out)
    assert len(rows) == 2
    assert rows[0]["halted"] == HALT_EXIT
    assert rows[0]["hooks"] == 6
    assert all(r["icount"] > 0 and r["cycles"] > 0 for r in rows)


def test_mem_read_block_matches_mem_read(tiny_fleet):
    _, out = tiny_fleet
    lane = unstack_state(out, 0)
    block = mem_read_block(lane, L.MAILBOX, 4)
    assert block.shape == (4,)
    for j in range(4):
        assert int(block[j]) == M.mem_read(lane, L.MAILBOX + 8 * j)


def test_hookcfg_fleet_chunk_roundtrip(tmp_path):
    cfg = HookConfig(fleet_chunk=32)
    p = tmp_path / "hook.json"
    cfg.save(p)
    assert HookConfig.load(p).fleet_chunk == 32
    assert HookConfig().fleet_chunk == 8


def test_run_fleet_rejects_bad_chunk(tiny_fleet):
    pps, _ = tiny_fleet
    from repro.core import pack_fleet
    imgs, ids, states = pack_fleet(pps)
    with pytest.raises(ValueError):
        fleet.run_fleet(imgs, states, ids, chunk=0)


def test_scalar_step_is_vmappable():
    """The scalar ``machine.step`` itself vmaps cleanly (one batched step
    equals per-lane scalar steps) — the fleet engine is the fast path, but
    vmap composability is part of the contract."""
    pps = [prepare(programs.getpid_loop(3), Mechanism.NONE),
           prepare(programs.caller_x8(2), Mechanism.NONE)]
    from repro.core import initial_state, stack_images, stack_states
    imgs = stack_images([pp.decoded for pp in pps])
    states = stack_states([initial_state(pp) for pp in pps])
    batched = jax.vmap(M.step)(imgs, states)
    for i, pp in enumerate(pps):
        ref = M.step(pp.decoded, initial_state(pp))
        lane = unstack_state(batched, i)
        for f in ref._fields:
            assert np.array_equal(np.asarray(getattr(ref, f)),
                                  np.asarray(getattr(lane, f))), f


def test_lane_sharding_helpers_noop_on_one_device():
    """The lane-partitioning path is exercised end to end; on one device it
    must be a transparent no-op."""
    from repro.core import pack_fleet
    from repro.parallel.sharding import fleet_mesh, lane_sharding, shard_fleet
    pps = [prepare(programs.getpid_loop(3), Mechanism.NONE) for _ in range(2)]
    imgs, ids, states = pack_fleet(pps)
    mesh = fleet_mesh()
    assert lane_sharding(mesh).spec[0] == "lanes"
    imgs2, ids2, states2 = shard_fleet(imgs, jnp.asarray(ids), states)
    out = fleet.run_fleet(imgs2, states2, ids2, chunk=4)
    assert np.asarray(out.halted).tolist() == [HALT_EXIT, HALT_EXIT]


def test_run_fleet_shard_path():
    """run_fleet(shard=True) goes through the partitioning helper."""
    pps = [prepare(programs.getpid_loop(2), Mechanism.NONE) for _ in range(2)]
    out = run_fleet_prepared(pps, fuel=50_000, shard=True)
    assert np.asarray(out.halted).tolist() == [HALT_EXIT, HALT_EXIT]


def test_span_parts_carry_named_scopes():
    """The traced span's parts are named scopes in its lowered text, so a
    device trace can charge each operation to one of them."""
    import re
    n = 8
    imgs = jax.eval_shape(lambda: fleet.FleetImages(
        packed=jnp.zeros((4, L.CODE_WORDS), jnp.int64),
        imm=jnp.zeros((4, L.CODE_WORDS), jnp.int64)))
    ids = jax.ShapeDtypeStruct((n,), jnp.int32)
    states = jax.eval_shape(lambda: fleet.make_halted_states(n))
    trace = jax.eval_shape(lambda: fleet.make_empty_trace(n, 64))
    text = fleet._jitted_span_traced(8, 16).lower(
        imgs, ids, states, trace).as_text(debug_info=True)
    locs = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in ("fetch", "regs", "mem", "alu", "syscall", "emul",
                  "io_mover", "trace_ring"):
        assert any(re.search(rf"(^|/){scope}/", s) for s in locs), scope
