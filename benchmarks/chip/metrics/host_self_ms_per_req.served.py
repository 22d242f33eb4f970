"""Host time of the serving loop's own work: every top-level obs phase
(names without ``/``) less the time blocked on the chip
(``dispatch/device_wait`` and ``device_sync``) over the window, per request
published in it.  A program without the child phase gives nothing: its
dispatch phase holds the wait."""
UNIT, LAYER, MOVES, SOURCE = "ms", "serving loop", "latency_p95_ms", \
    "program_span"


def reduce(ctx):
    d = ctx["window"]
    n = d["completed"]
    ph = d["phases"]
    wait = ph.get("dispatch/device_wait")
    if not n or wait is None:
        return None
    top = sum(v for k, v in ph.items() if "/" not in k)
    return 1e3 * (top - ph.get("device_sync", 0.0) - wait) / n
