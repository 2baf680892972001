"""decode_step_ms.serve (ms): median of the engine's own fenced
``decode_step_s`` (``repro.serve.engine``) over the window: one decode
of every slot with its sampling and the host round trip. Moves
``itl_p95_ms``."""


def read(record):
    if record["ctx"].traffic["kind"] != "serve":
        return None
    h = record["state"]["engine"].metrics.histograms.get("decode_step_s")
    if h is None or not h.count:
        return None
    return 1e3 * h.percentile(50)
