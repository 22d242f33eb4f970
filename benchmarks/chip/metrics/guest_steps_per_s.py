"""Guest steps executed by all lanes over the window (the server's
``executed_steps`` delta, read from the device ``icount``), divided by the
window's elapsed time.  The window opens and closes at generation
boundaries."""
UNIT, LAYER, MOVES, SOURCE = "steps/s", "benchmark", "guest_steps_per_s", \
    "host_clock"


def reduce(ctx):
    steps = ctx["window"]["executed_steps"]
    return steps / ctx["window_s"] if steps > 0 else None
