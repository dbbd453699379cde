"""device_idle.paper: the share of the traced window in which no operation
ran on the device, LeNet cells.  Moves round_s."""
from bench import trace


def read(ctx):
    return trace.idle_pct(ctx["trace"])
