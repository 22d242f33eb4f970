"""Bytes an interpreter step needs, for the span program's roofline.

Per executed lane-step the least the step can move is:

* the lane's state outside the two large planes (``mem``, the guest
  memory, and ``k_ino_data``, the in-memory files), read once and written
  once: registers, flags, counters and the guest kernel's small tables;
* one instruction word (4 bytes) fetched;
* the data words its load, store or syscall moves.  Loads and stores
  are not visible from the state's counters, so only the bytes that the
  syscall stream counters record are added; for a loop of ``getpid`` that
  is nothing.  The count is therefore a lower bound, and the share a
  lower bound with it.

The function depends on the state's shapes and the lane-step count only,
never on how the step is implemented.
"""
from __future__ import annotations

from typing import Dict, Sequence

BIG_PLANES = ("mem", "k_ino_data")
INSTRUCTION_BYTES = 4


def state_bytes(lane_shapes: Dict[str, Sequence[int]], itemsize: int = 8) -> int:
    """Bytes of one lane's state outside the big planes; ``lane_shapes``
    maps each state field to its per-lane shape (``()`` for a scalar)."""
    total = 0
    for name, shape in lane_shapes.items():
        if name in BIG_PLANES:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        total += n * itemsize
    return total


def bytes_per_lane_step(lane_shapes: Dict[str, Sequence[int]]) -> int:
    return 2 * state_bytes(lane_shapes) + INSTRUCTION_BYTES


def span_bytes(lane_shapes: Dict[str, Sequence[int]], lane_steps: int,
               data_bytes: int = 0) -> int:
    """The least bytes ``lane_steps`` executed lane-steps move, plus the
    syscall data bytes counted over them."""
    return lane_steps * bytes_per_lane_step(lane_shapes) + int(data_bytes)


def roofline_share(bytes_needed: float, seconds: float,
                   peak_bytes_per_s: float) -> float:
    """Least time for the bytes at the peak bandwidth over the time taken,
    as a fraction."""
    return (bytes_needed / peak_bytes_per_s) / seconds
