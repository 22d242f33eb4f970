"""Trace records the stream took in over the window (its ``records_seen``
counter at the window's two ends), divided by the window's elapsed
time."""
UNIT, LAYER, MOVES, SOURCE = "records/s", "trace stream", \
    "guest_steps_per_s", "program_counter"


def reduce(ctx):
    w = ctx["counts"].get("window")
    n = w["stream"]["records_seen"] if w else 0
    return n / ctx["window_s"] if n > 0 else None
