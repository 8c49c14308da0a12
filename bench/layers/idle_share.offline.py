"""Share of the traced window (one whole partition()) in which no operation
ran on the device: 1 - union of the device's op intervals / window."""

SOURCE = "device_trace"


def read(ctx):
    if ctx.reduced is None:
        return None
    return 100.0 * ctx.reduced.idle_share
