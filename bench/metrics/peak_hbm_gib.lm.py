"""peak_hbm_gib.lm: the fullest chip's peak_bytes_in_use after the window,
in GiB.  Moves client_tokens_per_s."""


def read(ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    return peak / 2 ** 30 if peak > 0 else None
