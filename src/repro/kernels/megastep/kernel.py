"""Pallas megastep: the fleet inner chunk loop as one fused kernel.

One ``pallas_call`` runs ``chunk`` masked steps with the decode tables,
the ``[B, MEM_WORDS]`` memory image and (when traced) the whole
ring/policy carry resident in kernel refs, and writes every carry leaf
back exactly once at the chunk boundary — the XLA engine's per-step
select-chains and masked scatters re-materialise the full carry every
``lax.scan`` iteration, and this kernel replaces those round-trips with
a single merged register/memory/trace-ring/histogram writeback.

The step body is *not* re-implemented here.  The kernel reads the refs
into values and calls the same spec-generated executor as every other
engine (:func:`repro.core.fleet._step_core`, generated from the op-spec
table :mod:`repro.core.opspec`), so pallas==xla bit-exactness holds by
construction and a new syscall family remains one spec-table row — there
is no third copy of the semantics to keep in sync.

On the CPU backend (the tier-1 test environment) the kernel runs in
interpret mode, which lowers to the same XLA ops as the reference engine.
It does not lower for a TPU yet: the carry is int64, and Mosaic refuses
64-bit lane blocks (ROADMAP A2), so the fleet refuses ``engine="pallas"``
on a TPU backend.
"""
from __future__ import annotations

from typing import Optional

import jax

jax.config.update("jax_enable_x64", True)

from jax import lax
from jax.experimental import pallas as pl

from repro.core import fleet as F
from repro.core import opspec
from repro.core.machine import MachineState

_N_STATE = len(MachineState._fields)
_N_TRACE = len(F.TraceState._fields)
_N_TBL = len(opspec.SpecTables._fields)


def default_interpret() -> bool:
    """Interpret on the CPU backend only.

    CPU has no Pallas lowering, so tier-1 (and any forced-host run via
    ``JAX_PLATFORMS=cpu``) takes the interpret path; every other backend
    lowers the kernel for real (and a TPU refuses the engine before it
    gets here — :func:`repro.core.fleet._check_engine`).
    """
    return jax.default_backend() == "cpu"


def _full_spec(shape):
    # whole-array block (e.g. the [G, CODE_WORDS] decode tables: every
    # lane block fetches through the full table via its image id)
    nd = len(shape)
    return pl.BlockSpec(shape, lambda i, _nd=nd: (0,) * _nd)


def _lane_spec(leaf, block: int):
    # lane-blocked carry leaf: ``block`` lanes, full trailing dims
    nd = len(leaf.shape) - 1
    return pl.BlockSpec((block,) + leaf.shape[1:],
                        lambda i, _nd=nd: (i,) + (0,) * _nd)


def _make_kernel(chunk: int, traced: bool):
    n_carry = _N_STATE + (_N_TRACE if traced else 0)

    def kernel(*refs):
        packed_ref, imm_ref, ids_ref = refs[:3]
        # spec columns arrive as operands: a kernel cannot capture the
        # module-level jnp constants, so the step body indexes these
        tbl = opspec.SpecTables(*(r[...] for r in
                                  refs[3:3 + _N_TBL]))
        in_refs = refs[3 + _N_TBL:3 + _N_TBL + n_carry]
        out_refs = refs[3 + _N_TBL + n_carry:]
        img = F.FleetImages(packed=packed_ref[...], imm=imm_ref[...])
        ids = ids_ref[...]
        # the word planes flat for the whole chunk, as the XLA drivers
        # carry them (repro.core.fleet.flat_planes)
        s = F.flat_planes(MachineState(*(r[...] for r in in_refs[:_N_STATE])))
        if traced:
            tr = F.TraceState(*(r[...] for r in in_refs[_N_STATE:]))

            def body(_, c):
                return F._step_core(img, ids, c[0], c[1], tbl=tbl)

            s, tr = lax.fori_loop(0, chunk, body, (s, tr))
            outs = tuple(F.lane_planes(s)) + tuple(tr)
        else:

            def body(_, ss):
                return F._step_core(img, ids, ss, None, tbl=tbl)[0]

            s = lax.fori_loop(0, chunk, body, s)
            outs = tuple(F.lane_planes(s))
        for ref, val in zip(out_refs, outs):
            ref[...] = val

    return kernel


def megastep_chunk(imgs: F.FleetImages, ids, s: MachineState,
                   tr: Optional[F.TraceState] = None, *, chunk: int,
                   block: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """``chunk`` masked fleet steps for every lane in one fused dispatch.

    Bit-identical to ``chunk`` iterations of the XLA engine's
    :func:`repro.core.fleet._step_core` (the ref oracle) — same executor
    body, same carry, merged writeback.  ``block`` lane-partitions the
    grid (must divide the lane count; default one block over the whole
    fleet, which is right for CPU interpret).  With ``tr`` the trace
    carry rides along in refs and ``(state, trace)`` is returned.

    Every carry leaf is input/output-aliased, so under a jitted driver
    the buffers update in place like the donated XLA entry points.
    """
    traced = tr is not None
    B = int(s.pc.shape[0])
    block = B if block is None else int(block)
    if block < 1 or B % block:
        raise ValueError(
            f"block must divide the lane count ({B}), got {block}")
    if interpret is None:
        interpret = default_interpret()

    carry = tuple(s) + (tuple(tr) if traced else ())
    tables = tuple(opspec.TABLES)
    n_pre = 3 + len(tables)
    in_specs = ([_full_spec(imgs.packed.shape), _full_spec(imgs.imm.shape),
                 pl.BlockSpec((block,), lambda i: (i,))]
                + [_full_spec(t.shape) for t in tables]
                + [_lane_spec(x, block) for x in carry])
    outs = pl.pallas_call(
        _make_kernel(int(chunk), traced),
        grid=(B // block,),
        in_specs=in_specs,
        out_specs=[_lane_spec(x, block) for x in carry],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in carry],
        input_output_aliases={n_pre + k: k for k in range(len(carry))},
        interpret=bool(interpret),
    )(imgs.packed, imgs.imm, ids, *tables, *carry)

    s_out = MachineState(*outs[:_N_STATE])
    if not traced:
        return s_out
    return s_out, F.TraceState(*outs[_N_STATE:])
