"""Backend compiles inside the measured window (count)."""


def read(ctx):
    return float(ctx["window_compiles"])
