"""Executables built (compiled, or loaded from the persistent cache) inside
the traced window of partitions: shapes that set-up did not warm."""

SOURCE = "program_counter"


def read(ctx):
    return ctx.compiles
