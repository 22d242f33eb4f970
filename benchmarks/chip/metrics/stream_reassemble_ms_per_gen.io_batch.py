"""Host time of the stream's per-lane reassembly of the cold halves (obs
child phase ``stream_flush/reassemble``) over the window, per generation
run in it.  A program without the child phase gives nothing."""
UNIT, LAYER, MOVES, SOURCE = "ms", "trace stream", "guest_steps_per_s", \
    "program_span"


def reduce(ctx):
    d = ctx["window"]
    n = d["generations"]
    t = d["phases"].get("stream_flush/reassemble")
    return 1e3 * t / n if n and t else None
