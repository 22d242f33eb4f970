"""The traced span's share of its bandwidth roofline in the long-running
I/O batch: the least time the bytes its executed lane-steps need, syscall
data and ring records included (``io_roofline.io_span_bytes``), take at
the chip's peak HBM bandwidth, over the span executables' device time in
the traced window."""
import io_roofline
import roofline

UNIT, LAYER, MOVES, SOURCE = "%", "span program", "guest_steps_per_s", \
    "device_trace"


def reduce(ctx):
    tr = ctx["traced"]
    window = ctx["counts"].get("window")
    steps = ctx["window"]["executed_steps"]
    if not tr or not tr["span_s"] or tr["delta"]["executed_steps"] <= 0 \
            or not window or steps <= 0:
        return None
    peak = ctx["peaks"][ctx["device_kind"]]["hbm_bytes_per_s"]
    need = io_roofline.io_span_bytes(ctx["lane_shapes"],
                                     tr["delta"]["executed_steps"], window,
                                     steps)
    return 100.0 * roofline.roofline_share(need, tr["span_s"], peak)
