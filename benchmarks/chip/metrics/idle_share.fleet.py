"""Share of the traced window in which no operation ran on the device (%)."""


def read(ctx):
    s = ctx["summary"]
    return None if s is None else 100.0 * s.idle_share
