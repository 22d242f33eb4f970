"""getpid calls completed by all lanes over the window (loop iterations,
read from each lane's loop counter x19 at the window's two ends), divided
by the window's elapsed time: the cost per call that Table 3 compares,
summed over the pool."""
UNIT, LAYER, MOVES, SOURCE = "calls/s", "benchmark", "getpid_calls_per_s", \
    "host_clock"


def reduce(ctx):
    calls = sum(ctx["counts"].get("loop_iterations", {}).values())
    return calls / ctx["window_s"] if calls > 0 else None
