"""Round program: model FLOPs utilisation of the traced window (%).

Model FLOPs per token (``bench/flops.py``) times the tokens per second
of the traced window, over the chips used times each chip's bf16 peak
(``bench/peaks.json``).
"""
from bench.flops import flops_per_token


def read(ctx):
    t = ctx.cell.traffic
    chips = t["mesh"][0] * t["mesh"][1]
    flops = flops_per_token(ctx.cell.config, t["seq_len"])
    return 100.0 * flops * ctx.tokens_per_s / (chips * ctx.peak("bf16_flops"))
