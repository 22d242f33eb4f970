"""Set-up: from process start to the window's start (compilation or
compile-cache reads, preparing the binaries, building the pool, filling
it, warm-up)."""
UNIT, LAYER, MOVES, SOURCE = "s", "benchmark", "setup_s", "host_clock"


def reduce(ctx):
    return ctx["setup_s"]
