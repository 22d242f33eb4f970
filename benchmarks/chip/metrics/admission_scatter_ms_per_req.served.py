"""Host time of admission's padding, stacking and admit/restore scatter
dispatch (obs child phase ``admission/scatter``) over the window, per
request admitted in it."""
UNIT, LAYER, MOVES, SOURCE = "ms", "serving loop", "latency_p95_ms", \
    "program_span"


def reduce(ctx):
    d = ctx["window"]
    n = d["admission_waits"]
    t = d["phases"].get("admission/scatter")
    return 1e3 * t / n if n and t else None
