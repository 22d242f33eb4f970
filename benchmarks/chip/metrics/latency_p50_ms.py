"""Median of the same samples as ``latency_p95_ms``."""
import statistics

UNIT, LAYER, MOVES, SOURCE = "ms", "benchmark", "latency_p50_ms", \
    "host_clock"


def reduce(ctx):
    lat = ctx["latencies_ms"] or []
    return statistics.median(lat) if lat else None
