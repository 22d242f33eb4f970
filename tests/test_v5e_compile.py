"""Compile the served main path for a TPU v5e chip, without the chip.

The TPU compiler compiles for a described ``v5e:2x2`` topology here, so
these tests catch what only the chip's compiler refuses (layouts, tiling,
programs that do not fit 16 GB) at no chip time.  Shapes are those of
``chip_smoke.py``: a 4096-lane pool, a 32-row image table, the streamed
trace carry.  Nothing runs; only ``memory_analysis()`` and the compiled
HLO text are read.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fleet as F
from repro.core import layout as L
from repro.core.hookcfg import HookConfig
from repro.emul import engine as E

POOL = 4096
TABLE_ROWS = 32
CHUNK = HookConfig().fleet_chunk
TRACE_CAP = HookConfig().trace_cap
HBM_BYTES = 16 * 10**9
BUDGET = 0.75 * HBM_BYTES    # leave a quarter for the server's other buffers

# temp_size_in_bytes of these two spans when each step still made its own
# flat view of the [POOL, MEM_WORDS] plane (four whole-plane relayouts per
# step); read from this file's compiles on the code of that time.  The flat
# carry must not need more.
TEMPS_WITH_PER_STEP_VIEWS = {"span": 3_417_509_888,
                             "traced_span": 3_524_913_664}


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


@pytest.fixture(scope="module")
def compiled_spans(one_chip):
    """The two span programs, compiled once for every test below."""
    imgs, ids, states, trace = _pool(one_chip)
    span = HookConfig().serve_gen_steps // CHUNK
    sub = F.stream_interval(TRACE_CAP, CHUNK) // CHUNK
    return {
        "span": F._jitted_span(CHUNK, span).lower(imgs, ids, states)
        .compile(),
        "traced_span": F._jitted_span_traced(CHUNK, sub)
        .lower(imgs, ids, states, trace).compile(),
    }


def test_span_compiles_and_fits(compiled_spans):
    """The untraced generation (``serve_gen_steps`` steps per dispatch)."""
    mem = _fits(compiled_spans["span"])
    assert mem.argument_size_in_bytes > POOL * L.MEM_WORDS * 8


def test_traced_span_compiles_and_fits(compiled_spans):
    """The streamed sub-span the smoke dispatches (trace_cap steps)."""
    _fits(compiled_spans["traced_span"])


# -- the step body of the compiled span ---------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{\s*$")


def _computations(hlo: str):
    """HLO text -> ({computation name: instruction lines}, entry name)."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = m.group(1)
                comps[cur] = []
                if line.startswith("ENTRY "):
                    entry = cur
        elif line.startswith("}"):
            cur = None
        else:
            comps[cur].append(line)
    return comps, entry


def _instruction(line: str):
    """``(opcode, result shape, operands and attributes)`` of one HLO
    instruction line, or None.  A tuple shape is returned whole."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if not s.startswith("%") or " = " not in s:
        return None
    rest = s.split(" = ", 1)[1]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 2:]
    else:
        shape, _, rest = rest.partition(" ")
    return rest.split("(", 1)[0], shape, rest


def _while_bodies(comps, name):
    return [re.search(r"body=%([^,\s]+)", rest).group(1)
            for op, _, rest in filter(None, map(_instruction, comps[name]))
            if op == "while"]


def _step_body(hlo: str):
    """Instructions of the step: the body of the chunk scan's ``while``
    inside the span's ``while``, without the computations it calls
    (conditional branches, the io and data loops, fusion bodies)."""
    comps, entry = _computations(hlo)
    (span_body,) = _while_bodies(comps, entry)
    (step,) = _while_bodies(comps, span_body)
    return list(filter(None, map(_instruction, comps[step])))


def _elements(shape: str):
    m = re.match(r"^[a-z0-9]+\[([\d,]*)\]", shape)
    return int(np.prod([int(d) for d in m.group(1).split(",")])) \
        if m and m.group(1) else None


@pytest.mark.parametrize("span", ["span", "traced_span"])
def test_span_step_body_moves_no_whole_plane(compiled_spans, span):
    """Every step addresses the guest-memory plane where it lies: the step
    body holds no reshape or copy of a whole plane (on a TPU each is a
    relayout of the whole plane, 512 MiB per 32-bit half at this width)."""
    body = _step_body(compiled_spans[span].as_text())
    assert any(op == "scatter" or "scatter" in rest for op, _, rest in body)
    moves = [(op, shape) for op, shape, _ in body
             if op in ("reshape", "copy")
             and _elements(shape) == POOL * L.MEM_WORDS]
    assert not moves, moves


def _called(rest: str):
    """Names of the computations one instruction calls."""
    names = re.findall(r"(?:calls|body|condition|to_apply|true_computation"
                       r"|false_computation)=%([^,\s)}]+)", rest)
    for group in re.findall(r"branch_computations=\{([^}]*)\}", rest):
        names += [g.strip().lstrip("%") for g in group.split(",")]
    return names


def _data_loop(hlo: str):
    """``{computation: [(name, opcode, shape, rest)]}`` of every computation
    the guest kernel's data-loop cond reaches (its branches, the block and
    window loops, their fusions)."""
    comps, _ = _computations(hlo)

    def named(lines):
        out = []
        for line in lines:
            ins = _instruction(line)
            if ins:
                name = line.strip().removeprefix("ROOT ").split(" = ")[0]
                out.append((name.lstrip("%"), *ins))
        return out

    (cond,) = [rest for lines in comps.values() for _, op, _, rest in
               named(lines) if op == "conditional"
               and '/data_loop/cond"' in rest]
    todo, seen = _called(cond), {}
    while todo:
        c = todo.pop()
        if c not in seen:
            seen[c] = named(comps[c])
            todo += [n for *_, rest in seen[c] for n in _called(rest)]
    return seen


def _operands(rest: str):
    depth, i = 0, rest.index("(")
    for j in range(i, len(rest)):
        depth += (rest[j] == "(") - (rest[j] == ")")
        if depth == 0:
            break
    return re.findall(r"%([^,\s)]+)", rest[i:j])


def test_data_loop_indices_track_moving_lanes(compiled_spans):
    """The traced span's data loop gathers and scatters ``[K, W_KIO]``
    windows of the moving lanes, never a ``[POOL, W_KIO]`` window of the
    whole fleet, and copies or reshapes no whole ``mem`` or ``k_ino_data``
    plane (each would be a relayout of the plane on a TPU)."""
    comps = _data_loop(compiled_spans["traced_span"].as_text())
    most = min(POOL, E.K_KIO) * E.W_KIO
    planes = (POOL * L.MEM_WORDS, POOL * L.MAX_INODES * L.FILE_WORDS)
    counts, moves = [], []
    for body in comps.values():
        shapes = {name: shape for name, _, shape, _ in body}
        for name, op, shape, rest in body:
            if op in ("gather", "scatter"):
                args = _operands(rest)
                idx = args[1] if op == "gather" else args[(len(args) - 1) // 2]
                counts.append((op, name, _elements(shapes[idx])))
            elif op in ("reshape", "copy") and _elements(shape) in planes:
                moves.append((op, name, shape))
    assert any(op == "scatter" for op, *_ in counts), counts
    assert all(n <= most < POOL * E.W_KIO for *_, n in counts), counts
    assert not moves, moves


@pytest.mark.parametrize("span", ["span", "traced_span"])
def test_span_temps_no_larger_than_per_step_views(compiled_spans, span):
    """Carrying the plane flat through the span needs no more scratch than
    the per-step flat views it replaced."""
    temps = compiled_spans[span].memory_analysis().temp_size_in_bytes
    assert temps <= TEMPS_WITH_PER_STEP_VIEWS[span], temps


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
