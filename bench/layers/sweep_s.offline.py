"""Device seconds per partition of the chunked LP sweep executables
(``core/label_propagation._lp_sweep``), coarsening and refinement alike."""

SOURCE = "device_trace"
FUNCTIONS = ("_lp_sweep",)


def read(ctx):
    if ctx.reduced is None or not ctx.units:
        return None
    s = ctx.reduced.device_s(FUNCTIONS)
    return s / ctx.units if s > 0 else None
