"""mfu_seq.train (%): ``mfu.train``'s share of the chips' peak bf16
FLOP/s, with the model FLOPs counted at the traffic's row length
(``bench.counts.train_flops_per_token(config, seq_len)``), so that a
layer whose work grows with the row, causal softmax attention, is
counted: forward and backward, no recomputation, no embedding gather.
The whole train step's layer (train/step.py). Moves
``train_tokens_per_s``."""

from bench import counts


def read(record):
    ctx, w = record["ctx"], record["window"]
    if ctx.traffic["kind"] != "train":
        return None
    flops = counts.train_flops_per_token(
        ctx.config, ctx.traffic["seq_len"]) * w["tokens"]
    return 100.0 * flops / (w["window_s"] * ctx.chips * ctx.peaks["flops"])
