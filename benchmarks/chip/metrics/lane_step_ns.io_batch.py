"""The same reduction as ``lane_step_ns.batch``, in the long-running I/O
batch: the traced, streamed span with the guest kernel's data loop, the
serial I/O mover and the trace ring at full width."""
UNIT, LAYER, MOVES, SOURCE = "ns", "span program", "guest_steps_per_s", \
    "device_trace"


def reduce(ctx):
    tr = ctx["traced"]
    if not tr or not tr["span_s"] or tr["delta"]["executed_steps"] <= 0:
        return None
    return 1e9 * tr["span_s"] / tr["delta"]["executed_steps"]
