"""Host time of harvest's per-lane ``unstack_state`` and halt patch (obs
child phase ``harvest/unstack``) over the window, per request published
in it."""
UNIT, LAYER, MOVES, SOURCE = "ms", "serving loop", "latency_p95_ms", \
    "program_span"


def reduce(ctx):
    d = ctx["window"]
    n = d["completed"]
    t = d["phases"].get("harvest/unstack")
    return 1e3 * t / n if n and t else None
