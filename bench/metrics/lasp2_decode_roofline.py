"""lasp2_decode_roofline (%): the recurrent decode kernel
(``lasp2_decode_step``) over all the engine's slots, as a share of its
roofline (``bench.roofline``); the fp32 state read and written bound it.
Moves ``serve_tokens_per_s``."""

import sys

from bench import roofline


def read(record):
    ctx = record["ctx"]
    if ctx.traffic["kind"] != "serve" or record["trace"] is None:
        return None
    from bench import serve
    dh = ctx.config["head_dim"]
    pct, bound = roofline.share(
        record, ("lasp2_decode_step",),
        dict(bh=serve.decode_bh(ctx), dk=dh, dv=dh, in_bytes=2))
    if pct is not None:
        print(f"lasp2_decode_roofline: {bound}-bound", file=sys.stderr)
    return pct
