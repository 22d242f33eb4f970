"""Host time of the serving loop's admission phase (``_admit_pending``,
obs phase ``admission``) over the window, per request admitted in it."""
UNIT, LAYER, MOVES, SOURCE = "ms", "serving loop", "latency_p95_ms", \
    "program_span"


def reduce(ctx):
    d = ctx["window"]
    n = d["admission_waits"]
    t = d["phases"].get("admission")
    return 1e3 * t / n if n and t else None
