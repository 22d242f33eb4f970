"""Collective interception layer (the paper's technique, adapted to SPMD)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.hooks import (CastCompressHandler, RSAGHandler, TraceHandler,
                         census_fn, completeness_report,
                         hlo_collective_census, hook_collectives, hooking,
                         scan_jaxpr, virtualize)

N_DEV = jax.device_count()
pytestmark = pytest.mark.skipif(N_DEV < 1, reason="needs a device")


from repro.launch.mesh import make_mesh as _compat_mesh, shard_map_fn

_shard_map = shard_map_fn()


def make_mesh():
    return _compat_mesh((N_DEV,), ("data",))


def dp_step(x):
    """A DDP-style step: local compute + gradient psum + scan with psums."""
    g = x * 2.0
    g = jax.lax.psum(g, "data")

    def body(c, t):
        return c + jax.lax.psum(t, "data"), ()

    c, _ = jax.lax.scan(body, g, jnp.ones((3,) + g.shape, g.dtype))
    return c


def make_sm():
    mesh = make_mesh()
    return _shard_map(dp_step, mesh=mesh, in_specs=P(None, None),
                         out_specs=P(None, None))


X = jnp.arange(16.0 * 256, dtype=jnp.float32).reshape(16, 256)


# -- static census (Table 1/2 analogue) --------------------------------------

def test_census_finds_nested_sites():
    c = census_fn(make_sm(), X)
    assert c["total_sites"] == 2
    assert c["by_primitive"] == {"psum_invariant": 2}
    # scan site is weighted by its trip count (3) in per-step bytes
    assert c["payload_bytes_per_step"] == X.size * 4 * (1 + 3)
    paths = [s.path for s in c["sites"]]
    assert any("scan/" in p for p in paths), paths


def test_census_loop_trip_counts():
    c = census_fn(make_sm(), X)
    trips = {s.path: s.loop_trip for s in c["sites"]}
    assert set(trips.values()) == {1, 3}


# -- interception (the trampoline) --------------------------------------------

def test_trace_handler_is_transparent():
    sm = make_sm()
    th = TraceHandler()
    y0 = sm(X)
    y1 = hook_collectives(sm, {"psum": th})(X)
    assert th.count == 2  # both sites, incl. inside the scan body
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


def _canon_hlo(lowered) -> str:
    """HLO text with source locations stripped (hook wrappers shift line
    numbers; the computation itself is what must match)."""
    import re
    txt = re.sub(r", metadata=\{[^}]*\}", "", lowered.as_text())
    txt = re.sub(r"module @\S+", "module @M", txt)
    txt = re.sub(r"@jit_\w+", "@jit_F", txt)
    keep, skipping = [], False
    for line in txt.splitlines():
        if line.strip() in ("FileNames", "FunctionNames", "FileLocations",
                            "StackFrames"):
            skipping = True
            continue
        if skipping:
            if line.strip() == "":
                skipping = False
            continue
        keep.append(line)
    return "\n".join(keep)


def test_transparent_hook_compiles_to_identical_hlo():
    """The paper's transparency property at the artifact level: a pure
    pass-through hook must yield a bit-identical compiled program, not just
    equal values.  (This invariant used to live in the
    collective_hook_overhead benchmark; it is enforced here so a handler
    regression cannot ship silently.)"""
    mesh = make_mesh()
    sm = _shard_map(lambda x: jax.lax.psum(x * 2.0, "data"), mesh=mesh,
                    in_specs=P(None, None), out_specs=P(None, None))
    x = jnp.arange(64.0).reshape(8, 8)
    base = _canon_hlo(jax.jit(sm).lower(x))
    th = TraceHandler()
    hooked = _canon_hlo(jax.jit(hook_collectives(sm, {"psum": th})).lower(x))
    assert th.count >= 1  # the hook actually ran at trace time
    assert hooked == base


def test_hook_works_under_jit_and_grad():
    sm = make_sm()
    th = TraceHandler()

    def loss(x):
        return jnp.sum(hook_collectives(sm, {"psum": th})(x))

    g = jax.jit(jax.grad(loss))(X)
    assert g.shape == X.shape
    assert jnp.all(jnp.isfinite(g))
    assert th.count >= 2


def test_no_recursive_interception():
    """Handlers may themselves use collectives (dlmopen-namespace analogue)."""
    calls = []

    def handler(name, args, params, do_original):
        calls.append(name)
        # this psum must NOT re-enter the handler
        extra = jax.lax.psum(args[0] * 0.0, "data")
        return do_original(args[0] + extra)

    y0 = make_sm()(X)
    y1 = hook_collectives(make_sm(), {"psum": handler})(X)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1))
    assert len(calls) == 2


def test_transparency_check_rejects_bad_handler():
    def bad(name, args, params, do_original):
        return args[0][:4]  # wrong shape

    with pytest.raises(TypeError, match="transparency"):
        hook_collectives(make_sm(), {"psum": bad})(X)


def test_hooks_compose_with_stack():
    th_outer, th_inner = TraceHandler(), TraceHandler()
    with hooking({"psum": th_outer}):
        with hooking({"psum": th_inner}):  # innermost wins
            make_sm()(X)
    assert th_inner.count == 2 and th_outer.count == 0


def test_virtualize_skips_collective():
    # a fabricated result is device-varying as far as shard_map's replication
    # checker knows, so the harness disables check_vma (the virtualised value
    # is the benchmark's concern, not the type system's)
    mesh = make_mesh()
    sm = _shard_map(dp_step, mesh=mesh, in_specs=P(None, None),
                    out_specs=P(None, None), check_vma=False)
    vh = virtualize(lambda args: args[0] * 0.0)
    y = hook_collectives(sm, {"psum": vh})(X)
    assert bool(jnp.all(y == 0))


# -- shipped feature handlers --------------------------------------------------

def test_cast_compress_halves_wire_bytes():
    ch = CastCompressHandler(min_bytes=1024)
    y0 = make_sm()(X)
    y1 = hook_collectives(make_sm(), {"psum": ch})(X)
    assert ch.compressed_sites == 2
    err = jnp.max(jnp.abs(y1 - y0) / (jnp.abs(y0) + 1e-9))
    assert float(err) < 0.02  # bf16 wire error


def test_rsag_schedule_rewrite_is_exact():
    rh = RSAGHandler(axis_size=N_DEV)
    y0 = make_sm()(X)
    y1 = hook_collectives(make_sm(), {"psum": rh})(X)
    assert rh.rewritten == 2
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=1e-6)


# -- completeness (C1/C2/C3 analogue) -----------------------------------------

def test_hlo_census_counts_collectives():
    mesh = make_mesh()
    sm = _shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                       in_specs=P("data", None), out_specs=P(None, None))
    x = jnp.ones((N_DEV * 2, 8))
    txt = jax.jit(sm).lower(x).compile().as_text()
    counts = hlo_collective_census(txt)
    # even on 1 device XLA emits the (degenerate) all-reduce op
    assert counts.get("all-reduce", 0) >= 1


def test_completeness_report_structure():
    c = census_fn(make_sm(), X)
    txt = jax.jit(make_sm()).lower(X).compile().as_text()
    rep = completeness_report(c, txt)
    assert rep.jaxpr_counts.get("all-reduce") == 2
    assert isinstance(rep.fully_hooked, bool)
