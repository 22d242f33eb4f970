"""Bytes the span program needs in the long-running I/O batch, for its
roofline: the least state traffic of every executed lane-step
(:func:`roofline.span_bytes`) plus the syscall data the completed calls
move, as the plain reference counts it (stream reads, sink writes, file
reads and writes through the guest kernel's data loop), plus one ring
record per traced syscall.

The data is counted over the whole window and scaled to the lane-steps
of the traced part of it.  The count depends on the work only, never on
how the step does it.
"""
from __future__ import annotations

from typing import Dict, Sequence

import roofline
from reference_io import RING_RECORD_BYTES


def data_bytes(payload_bytes: int, ring_records: int) -> int:
    return int(payload_bytes) + RING_RECORD_BYTES * int(ring_records)


def io_span_bytes(lane_shapes: Dict[str, Sequence[int]], lane_steps: int,
                  window: dict, window_steps: int) -> float:
    """The least bytes ``lane_steps`` traced lane-steps move, with the
    window's syscall data (``window``: its ``payload_bytes`` and
    ``ring_records``) scaled from ``window_steps`` to them."""
    data = data_bytes(window["payload_bytes"], window["ring_records"])
    return roofline.span_bytes(lane_shapes, lane_steps,
                               data * lane_steps / window_steps)
