"""flash_attention_roofline (%): the flash attention kernels
(``flash_attention_fwd``, ``_bwd_dq``, ``_bwd_dkv``) in the training
step, as a share of their roofline: the least time of each call's
causally needed work (``kernel_work`` in ``bench/kinds/softmax.py``, at
the chip's peaks, ``bench.counts.least_time``), summed over the
sequence-parallel ranks, each at its own offset, and the data-parallel
groups, over the calls' device
time summed over the chips. The kernels' layer (kernels/). Moves
``train_tokens_per_s``."""

import sys

from bench import counts, kinds


def read(record):
    ctx, tr = record["ctx"], record["trace"]
    if ctx.traffic["kind"] != "train" or tr is None:
        return None
    softmax = kinds.kind("softmax")
    lay = ctx.traffic["layout"]
    dp, sp = lay.get("dp", 1), lay.get("sp", 1)
    least, spent = 0.0, 0.0
    by_bound = {"compute": 0.0, "memory": 0.0}
    for prog in softmax.KERNELS:
        calls = tr["op_calls"].get(prog, 0)
        if not calls:
            continue
        for rank in range(sp):
            work = softmax.rank_work(ctx.config, ctx.traffic, rank)[prog]
            t, bound = counts.least_time(*work, ctx.peaks)
            least += dp * calls * t
            by_bound[bound] += dp * calls * t
        # op_s is the mean over the devices: their sum is ctx.chips times
        spent += tr["op_s"][prog] * ctx.chips
    if spent <= 0:
        return None
    print(f"flash_attention_roofline: "
          f"{max(by_bound, key=by_bound.get)}-bound", file=sys.stderr)
    return 100.0 * least / spent
