"""Device time of the span program's executables in the traced window,
per lane-step executed in it."""
UNIT, LAYER, MOVES, SOURCE = "ns", "span program", "guest_steps_per_s", \
    "device_trace"


def reduce(ctx):
    tr = ctx["traced"]
    if not tr or not tr["span_s"] or tr["delta"]["executed_steps"] <= 0:
        return None
    return 1e9 * tr["span_s"] / tr["delta"]["executed_steps"]
