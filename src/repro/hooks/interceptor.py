"""ASC-Hook for SPMD programs: transparent collective interception.

The paper's mechanism, re-thought for the TPU pod (DESIGN.md §2.2): the
"privileged boundary" of a compiled training step is its **collectives**.
This module intercepts them *at trace time* by rebinding the collective
primitives while a hook context is active — the moral equivalent of
ASC-Hook's load-time binary rewrite: user code (including libraries, scan
bodies, shard_map bodies) is not modified, every site is routed through a
per-primitive trampoline, and the original operation can be re-executed
from inside the hook (the displaced-instruction re-execution).

Faithfulness properties carried over from the paper:

* **transparency** — the trampoline validates that handler outputs have
  exactly the avals the original op would have produced; a pure pass-through
  handler yields bit-identical programs (tested);
* **no recursive interception** — handlers run inside a re-entrancy guard,
  the analogue of loading the hook library with ``dlmopen`` into a separate
  namespace (§3.4): collectives issued *by the handler* bind natively;
* **completeness accounting** — the static jaxpr census (scanner.py) plus the
  compiled-HLO census (completeness.py) expose exactly which collectives the
  trace-time hook cannot see (partitioner-inserted ones — the paper's
  indirect-jump case) so nothing is silently missed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax._src.lax import parallel as _lp

# The syscall table of this world: every collective primitive a traced
# program can bind, shard_map's invariant spellings included.
_PRIM_ATTRS = {
    "psum": "psum_p",
    "psum_invariant": "psum_invariant_p",
    "all_gather": "all_gather_p",
    "all_gather_invariant": "all_gather_invariant_p",
    "reduce_scatter": "reduce_scatter_p",
    "all_to_all": "all_to_all_p",
    "ppermute": "ppermute_p",
    "pmax": "pmax_p",
    "pmin": "pmin_p",
}
COLLECTIVE_PRIMS = {name: getattr(_lp, attr)
                    for name, attr in _PRIM_ATTRS.items()}

# Handler signature: (prim_name, args, params, do_original) -> outputs
# where do_original(*new_args, **param_overrides) re-executes the original
# primitive (the displaced instruction).
Handler = Callable[..., Any]


class _State(threading.local):
    def __init__(self):
        self.stack: List[Dict[str, Handler]] = []
        self.in_handler = False
        self.log: List[Tuple[str, Tuple[Any, ...]]] = []


_STATE = _State()
_INSTALLED = False
_ORIG_BINDS: Dict[str, Callable] = {}


def _current_handler(name: str) -> Optional[Handler]:
    if _STATE.in_handler or not _STATE.stack:
        return None
    # aliases: psum_invariant / all_gather_invariant are how lax.psum /
    # lax.all_gather trace inside shard_map
    table = _STATE.stack[-1]
    if name in table:
        return table[name]
    base = {"psum_invariant": "psum",
            "all_gather_invariant": "all_gather"}.get(name)
    return table.get(base) if base else None


def _flat_avals(vals) -> Tuple:
    # compare (shape, dtype) only: varying-manual-axes / weak-type metadata
    # differ legitimately between tracer avals and abstract_eval results
    out = []
    for v in vals:
        a = jax.api_util.shaped_abstractify(v)
        out.append((tuple(a.shape), jnp.dtype(a.dtype).name))
    return tuple(out)


def _make_bind(prim, orig_bind):
    def bind(*args, **params):
        handler = _current_handler(prim.name)
        if handler is None:
            return orig_bind(*args, **params)

        def do_original(*new_args, **overrides):
            return orig_bind(*(new_args or args), **{**params, **overrides})

        _STATE.in_handler = True
        try:
            out = handler(prim.name, args, dict(params), do_original)
        finally:
            _STATE.in_handler = False

        # normalise arity: a handler may return a bare array for a
        # one-output multiple-results primitive (psum_p is multi-result,
        # psum_invariant is not — handlers should not have to care)
        if prim.multiple_results and not isinstance(out, (tuple, list)):
            out = (out,)
        outs = out if prim.multiple_results else (out,)
        ref = _abstract_out(prim, args, params)
        got = _flat_avals(outs)
        if ref is not None and got != ref:
            raise TypeError(
                f"hook handler for {prim.name} broke transparency: "
                f"expected avals {ref}, got {got}")
        return out

    return bind


def _abstract_out(prim, args, params):
    try:
        avals = [jax.api_util.shaped_abstractify(a) for a in args]
        out, _ = prim.abstract_eval(*avals, **params)
        if not isinstance(out, (list, tuple)):
            out = (out,)
        return tuple((tuple(o.shape), jnp.dtype(o.dtype).name) for o in out)
    except Exception:
        return None  # best effort; transparency check skipped


def _install() -> None:
    global _INSTALLED
    if _INSTALLED:
        return
    for name, prim in COLLECTIVE_PRIMS.items():
        _ORIG_BINDS[name] = prim.bind
        prim.bind = _make_bind(prim, _ORIG_BINDS[name])
    _INSTALLED = True


@contextlib.contextmanager
def hooking(handlers: Dict[str, Handler]):
    """Intercept collective primitives bound while the context is active.

    Keys are primitive names ("psum", "all_gather", "reduce_scatter",
    "all_to_all", "ppermute", "pmax", "pmin"); "psum" also covers the
    shard_map-internal "psum_invariant" binding.
    """
    _install()
    _STATE.stack.append(dict(handlers))
    try:
        yield
    finally:
        _STATE.stack.pop()


def hook_collectives(fn: Callable, handlers: Dict[str, Handler]) -> Callable:
    """Return fn with its collectives routed through ``handlers``.

    Tracing (jit/grad/vmap) of the wrapped function happens inside the hook
    context, so every collective the trace reaches — in any nesting of scan /
    shard_map / remat / library code — is intercepted. This is the
    "LD_PRELOAD entry point" of the adaptation.
    """
    def wrapped(*args, **kwargs):
        with hooking(handlers):
            return fn(*args, **kwargs)

    return wrapped
