"""Device seconds per partition of the device GA on the coarsest graph
(``core/evo_device.evo_seed_step`` and ``evo_generation_step``)."""

SOURCE = "device_trace"
FUNCTIONS = ("evo_seed_step", "evo_generation_step")


def read(ctx):
    if ctx.reduced is None or not ctx.units:
        return None
    s = ctx.reduced.device_s(FUNCTIONS)
    return s / ctx.units if s > 0 else None
