"""The same reduction as ``device_idle_pct.batch``, in the served cells."""
UNIT, LAYER, MOVES, SOURCE = "%", "device", "latency_p95_ms", \
    "device_trace"


def reduce(ctx):
    tr = ctx["traced"]
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
