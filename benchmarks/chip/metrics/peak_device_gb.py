"""The device allocator's ``peak_bytes_in_use`` after the window and the
drain, before any reference runs, in GB (1e9 bytes): the pool width a chip
holds."""
UNIT, LAYER, MOVES, SOURCE = "GB", "device", "peak_device_gb", \
    "device_trace"


def reduce(ctx):
    return ctx["peak_bytes"] / 1e9 if ctx["peak_bytes"] else None
