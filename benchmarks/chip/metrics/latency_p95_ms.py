"""95th percentile (nearest rank), over all requests due in the window
that were published, of the time from the request's due time to the
return of the ``step()`` that published it."""
import math

UNIT, LAYER, MOVES, SOURCE = "ms", "benchmark", "latency_p95_ms", \
    "host_clock"


def reduce(ctx):
    lat = sorted(ctx["latencies_ms"] or [])
    if not lat:
        return None
    return lat[math.ceil(0.95 * len(lat)) - 1]
