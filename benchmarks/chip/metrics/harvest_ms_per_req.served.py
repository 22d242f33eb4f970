"""Host time of the serving loop's harvest phase (``_harvest``, obs phase
``harvest``) over the window, per request published in it."""
UNIT, LAYER, MOVES, SOURCE = "ms", "serving loop", "latency_p95_ms", \
    "program_span"


def reduce(ctx):
    d = ctx["window"]
    n = d["completed"]
    t = d["phases"].get("harvest")
    return 1e3 * t / n if n and t else None
