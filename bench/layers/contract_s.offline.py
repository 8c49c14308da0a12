"""Device seconds per partition of cluster contraction
(``core/contraction.contract_device``)."""

SOURCE = "device_trace"
FUNCTIONS = ("contract_device",)


def read(ctx):
    if ctx.reduced is None or not ctx.units:
        return None
    s = ctx.reduced.device_s(FUNCTIONS)
    return s / ctx.units if s > 0 else None
