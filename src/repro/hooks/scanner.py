"""Static jaxpr census — the linear-scan disassembly of the adaptation.

Recursively walks a ClosedJaxpr (into pjit / scan / while / cond / remat /
shard_map / custom_* bodies) and lists every collective "site" with its
nesting path, static shapes and an estimated per-execution payload, exactly
the role Table 1/2 play in the paper: knowing how many interception sites a
"process image" (compiled step) contains, and where they live.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List

import jax
import numpy as np

COLLECTIVE_NAMES = {
    "psum", "psum_invariant", "all_gather", "all_gather_invariant",
    "reduce_scatter", "all_to_all", "ppermute", "pmax", "pmin",
    "unreduced_psum",
}


@dataclasses.dataclass
class CollectiveSite:
    primitive: str
    path: str                 # e.g. "shard_map/scan/psum_invariant[0]"
    in_shapes: tuple
    in_bytes: int
    loop_trip: int            # product of enclosing scan lengths (1 if none)
    params: Dict[str, Any]


def _payload_bytes(invars) -> int:
    tot = 0
    for v in invars:
        aval = v.aval
        if hasattr(aval, "shape") and hasattr(aval, "dtype"):
            tot += int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize
    return tot


def _scan_length(eqn) -> int:
    return int(eqn.params.get("length", 1) or 1)


def _sub_jaxprs(eqn):
    for k, v in eqn.params.items():
        if k == "branches":
            for b in v:
                yield b
        elif type(v).__name__ == "ClosedJaxpr":
            yield v
        elif type(v).__name__ == "Jaxpr":
            from jax.extend import core as jex_core
            yield jex_core.ClosedJaxpr(v, ())


def scan_jaxpr(closed_jaxpr, path: str = "", trip: int = 1) -> List[CollectiveSite]:
    sites: List[CollectiveSite] = []
    counter: Dict[str, int] = {}
    for eqn in closed_jaxpr.jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_NAMES:
            idx = counter.get(name, 0)
            counter[name] = idx + 1
            sites.append(CollectiveSite(
                primitive=name,
                path=f"{path}{name}[{idx}]",
                in_shapes=tuple(getattr(v.aval, "shape", ()) for v in eqn.invars),
                in_bytes=_payload_bytes(eqn.invars),
                loop_trip=trip,
                params={k: v for k, v in eqn.params.items()
                        if isinstance(v, (int, str, bool, tuple))},
            ))
        sub_trip = trip * (_scan_length(eqn) if name == "scan" else 1)
        for sub in _sub_jaxprs(eqn):
            sites.extend(scan_jaxpr(sub, path=f"{path}{name}/", trip=sub_trip))
    return sites


def census_fn(fn: Callable, *args, **kwargs) -> Dict[str, Any]:
    """Trace fn and summarise its collective population (Table-1 analogue)."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    sites = scan_jaxpr(jaxpr)
    by_prim: Dict[str, int] = {}
    bytes_static = 0
    bytes_dynamic = 0  # weighted by enclosing loop trip counts
    for s in sites:
        by_prim[s.primitive] = by_prim.get(s.primitive, 0) + 1
        bytes_static += s.in_bytes
        bytes_dynamic += s.in_bytes * s.loop_trip
    return {
        "total_sites": len(sites),
        "by_primitive": by_prim,
        "payload_bytes_static": bytes_static,
        "payload_bytes_per_step": bytes_dynamic,
        "sites": sites,
    }
