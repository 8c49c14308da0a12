"""The LP sweep's share of its memory roofline, in percent.

The sweep is bound by memory: per arc it reads the target's id, the arc's
weight and the target's label and does a handful of compares and adds, far
below the v5e's ridge of about 240 operations per byte.  So its least time
is the bytes it must move over the peak HBM bandwidth.  The bytes are those
of the algorithm on each sweep's real level size, never the padded bucket
shapes or XLA's cost analysis, so the count reads the same work whatever
implements it: per iteration run, 12 bytes per arc (target id, weight,
target label) and 12 per node (own label, node weight, label written).
"""

SOURCE = "device_trace"
FUNCTIONS = ("_lp_sweep",)
BYTES_PER_ARC = 12
BYTES_PER_NODE = 12


def sweep_bytes(sweeps):
    """Least bytes of the given LP sweeps: dicts with n, m and iters."""
    return sum(s["iters"] * (BYTES_PER_ARC * s["m"] + BYTES_PER_NODE * s["n"])
               for s in sweeps)


def read(ctx):
    if ctx.reduced is None or not ctx.sweeps or not ctx.peaks:
        return None
    s = ctx.reduced.device_s(FUNCTIONS)
    if s <= 0:
        return None
    return 100.0 * sweep_bytes(ctx.sweeps) / ctx.peaks["hbm_bytes_per_s"] / s
