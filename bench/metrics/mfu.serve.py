"""mfu.serve (%): model FLOPs of the prompts admitted and the tokens
decoded in the window (``bench.counts``: the prompt's forward pass with
the head applied once, and one recurrent step per decoded token) over
the window's length times the chip's peak bf16 FLOP/s. Moves
``serve_tokens_per_s``."""

from bench import counts


def read(record):
    ctx, w = record["ctx"], record["window"]
    if ctx.traffic["kind"] != "serve":
        return None
    c = ctx.config
    close = w["window_s"]
    flops = 0.0
    for r in w["requests"]:
        if r.submitted <= close:
            flops += counts.prefill_flops(c, len(r.prompt))
        decoded = sum(1 for t in r.times[1:] if t <= close)
        flops += decoded * counts.decode_flops_per_token(c)
    return 100.0 * flops / (close * ctx.chips * ctx.peaks["flops"])
