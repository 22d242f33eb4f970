"""getpid calls completed per second by the lanes under PTRACE alone (the
window's loop iterations of those lanes over its elapsed time): Table 3's
comparison kept apart for one mechanism."""
UNIT, LAYER, MOVES, SOURCE = "calls/s", "hook mechanisms", \
    "getpid_calls_per_s", "program_counter"


def reduce(ctx):
    calls = ctx["counts"].get("loop_iterations", {}).get("PTRACE", 0)
    return calls / ctx["window_s"] if calls > 0 else None
