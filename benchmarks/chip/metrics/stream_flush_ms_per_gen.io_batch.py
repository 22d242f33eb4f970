"""Host time of the stream flush (obs phase ``stream_flush``: landing each
flipped cold half on the host and reassembling it per lane) over the
window, per generation run in it."""
UNIT, LAYER, MOVES, SOURCE = "ms", "trace stream", "guest_steps_per_s", \
    "program_span"


def reduce(ctx):
    d = ctx["window"]
    n = d["generations"]
    t = d["phases"].get("stream_flush")
    return 1e3 * t / n if n and t else None
