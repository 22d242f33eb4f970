"""The span program's share of its bandwidth roofline: the least time
the bytes its executed lane-steps need (``roofline.span_bytes``) take at
the chip's peak HBM bandwidth, over the span executables' device time in
the traced window."""
import roofline

UNIT, LAYER, MOVES, SOURCE = "%", "span program", "guest_steps_per_s", \
    "device_trace"


def reduce(ctx):
    tr = ctx["traced"]
    if not tr or not tr["span_s"] or tr["delta"]["executed_steps"] <= 0:
        return None
    peak = ctx["peaks"][ctx["device_kind"]]["hbm_bytes_per_s"]
    need = roofline.span_bytes(ctx["lane_shapes"],
                               tr["delta"]["executed_steps"])
    return 100.0 * roofline.roofline_share(need, tr["span_s"], peak)
