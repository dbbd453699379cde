"""mfu.lm: mamba2 training FLOPs of the rounds completed in the window (3 x
forward: projections, SSD intra- and inter-chunk, tied head; recomputed
work not counted; `bench/configs/mamba2-780m.py`) over the window's length
times the chips' bf16 peak.  Moves client_tokens_per_s."""
from bench import harness


def read(ctx):
    cell = ctx["cell"]
    flops = cell.model.train_flops_per_round(cell.config, cell.mix)
    peak = harness.peak(ctx["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * flops * ctx["rounds"] / (ctx["window_s"] * peak
                                            * cell.chips)
