"""device_idle.lm: the share of the traced window in which no operation ran
on the device, LM cells.  Moves client_tokens_per_s."""
from bench import trace


def read(ctx):
    return trace.idle_pct(ctx["trace"])
