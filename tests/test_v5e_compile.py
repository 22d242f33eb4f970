"""Compile the served main path for a TPU v5e chip, without the chip.

The TPU compiler compiles for a described ``v5e:2x2`` topology here, so
these tests catch what only the chip's compiler refuses (layouts, tiling,
programs that do not fit 16 GB) at no chip time.  Shapes are those of
``chip_smoke.py``: a 4096-lane pool, a 32-row image table, the streamed
trace carry.  Nothing runs; only ``memory_analysis()`` is read.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fleet as F
from repro.core import layout as L
from repro.core.hookcfg import HookConfig

POOL = 4096
TABLE_ROWS = 32
CHUNK = HookConfig().fleet_chunk
TRACE_CAP = HookConfig().trace_cap
HBM_BYTES = 16 * 10**9
BUDGET = 0.75 * HBM_BYTES    # leave a quarter for the server's other buffers


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    # a compile for a described chip cannot be read back without one
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _pool(sharding, n=POOL):
    """(images, img_ids, states, trace) shapes of an n-lane pool."""
    imgs = jax.eval_shape(lambda: F.FleetImages(
        packed=jnp.zeros((TABLE_ROWS, L.CODE_WORDS), jnp.int64),
        imm=jnp.zeros((TABLE_ROWS, L.CODE_WORDS), jnp.int64)))
    ids = jax.ShapeDtypeStruct((n,), jnp.int32)
    states = jax.eval_shape(lambda: F.make_halted_states(n))
    trace = jax.eval_shape(lambda: F.make_empty_trace(n, TRACE_CAP))
    return _on(sharding, (imgs, ids, states, trace))


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used <= BUDGET, (mem.argument_size_in_bytes,
                            mem.temp_size_in_bytes)
    return mem


def test_span_compiles_and_fits(one_chip):
    """The untraced generation (``serve_gen_steps`` steps per dispatch)."""
    imgs, ids, states, _ = _pool(one_chip)
    span = HookConfig().serve_gen_steps // CHUNK
    mem = _fits(F._jitted_span(CHUNK, span).lower(imgs, ids, states)
                .compile())
    assert mem.argument_size_in_bytes > POOL * L.MEM_WORDS * 8


def test_traced_span_compiles_and_fits(one_chip):
    """The streamed sub-span the smoke dispatches (trace_cap steps)."""
    imgs, ids, states, trace = _pool(one_chip)
    span = F.stream_interval(TRACE_CAP, CHUNK) // CHUNK
    _fits(F._jitted_span_traced(CHUNK, span).lower(imgs, ids, states, trace)
          .compile())


def test_admission_scatter_compiles_and_fits(one_chip):
    """The traced admission, padded to pool width as the server pads it."""
    _, _, states, trace = _pool(one_chip)
    k = POOL
    vec = lambda dt: jax.ShapeDtypeStruct((k,), dt, sharding=one_chip)
    args = (states, trace, vec(jnp.int64),
            jax.ShapeDtypeStruct((k, 31), jnp.int64, sharding=one_chip),
            *(vec(jnp.int64) for _ in range(6)),
            jax.ShapeDtypeStruct((k, F.N_POLICY_SLOTS), jnp.int32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((k, F.N_POLICY_SLOTS), jnp.int64,
                                 sharding=one_chip))
    _fits(F._jitted_admit_traced.lower(*args).compile())


def test_compaction_permute_compiles_and_fits(one_chip):
    """The shrink from the full pool to the next rung."""
    _, _, states, trace = _pool(one_chip)
    half = jax.ShapeDtypeStruct((POOL // 2,), jnp.int64, sharding=one_chip)
    mem = _fits(F._jitted_permute_split.lower((states, trace), half, half)
                .compile())
    # a gather: both halves are new buffers, none aliases the source
    assert mem.output_size_in_bytes >= np.int64(POOL) * L.MEM_WORDS * 8
