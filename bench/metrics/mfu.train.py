"""mfu.train (%): model FLOPs of the steps in the window (forward and
backward, ``bench.counts.train_flops_per_token``, no recomputation, no
embedding gather) over the window's length times the chips times the
chip's peak bf16 FLOP/s. Moves ``train_tokens_per_s``."""

from bench import counts


def read(record):
    ctx, w = record["ctx"], record["window"]
    if ctx.traffic["kind"] != "train":
        return None
    flops = counts.train_flops_per_token(ctx.config) * w["tokens"]
    return 100.0 * flops / (w["window_s"] * ctx.chips * ctx.peaks["flops"])
