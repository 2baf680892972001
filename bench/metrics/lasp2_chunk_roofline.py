"""lasp2_chunk_roofline (%): the chunked linear-attention kernels
(``lasp2_chunk_fwd``, ``_bwd_dq``, ``_bwd_dkv``) in the training step, as
a share of their roofline (``bench.roofline``). Moves
``train_tokens_per_s``."""

import sys

from bench import roofline

PROGRAMS = ("lasp2_chunk_fwd", "lasp2_chunk_bwd_dq", "lasp2_chunk_bwd_dkv")


def read(record):
    ctx = record["ctx"]
    if ctx.traffic["kind"] != "train" or record["trace"] is None:
        return None
    from bench import train
    pct, bound = roofline.share(record, PROGRAMS,
                                train.kernel_shapes(ctx)["lasp2_chunk"])
    if pct is not None:
        print(f"lasp2_chunk_roofline: {bound}-bound", file=sys.stderr)
    return pct
