"""Time the serving loop spends blocked on the chip (obs child phase
``dispatch/device_wait`` plus phase ``device_sync``) over the window, per
generation run in it.  A program without the child phase gives nothing:
its dispatch phase holds the wait."""
UNIT, LAYER, MOVES, SOURCE = "ms", "serving loop", "latency_p95_ms", \
    "program_span"


def reduce(ctx):
    d = ctx["window"]
    n = d["generations"]
    wait = d["phases"].get("dispatch/device_wait")
    if not n or wait is None:
        return None
    return 1e3 * (wait + d["phases"].get("device_sync", 0.0)) / n
