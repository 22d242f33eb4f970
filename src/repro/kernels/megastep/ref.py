"""XLA reference for the megastep chunk.

The oracle IS the fleet engine's own inner loop: a ``lax.scan`` of
``chunk`` :func:`repro.core.fleet._step_core` steps, exactly what
``fleet._run_fleet`` / ``_run_fleet_span`` dispatch per chunk.  Parity
against this reference is therefore parity against the ``xla`` engine —
the megastep tier's pallas==xla property tests compare the kernel to
this function before comparing whole-run results.
"""
from __future__ import annotations

from typing import Optional

import jax

jax.config.update("jax_enable_x64", True)

from jax import lax

from repro.core import fleet as F
from repro.core.machine import MachineState


def megastep_chunk_ref(imgs: F.FleetImages, ids, s: MachineState,
                       tr: Optional[F.TraceState] = None, *, chunk: int):
    """``chunk`` masked steps as the XLA engine runs them (word planes
    flat across the chunk)."""
    s = F.flat_planes(s)
    if tr is None:
        def body(ss, _):
            return F._step_core(imgs, ids, ss, None)[0], None

        s, _ = lax.scan(body, s, None, length=chunk)
        return F.lane_planes(s)

    def body_t(c, _):
        return F._step_core(imgs, ids, c[0], c[1]), None

    (s, tr), _ = lax.scan(body_t, (s, tr), None, length=chunk)
    return F.lane_planes(s), tr
