"""prefill_ms.serve (ms): median of the engine's own fenced ``prefill_s``
(``repro.serve.engine``) over the window: one admission's prefill, slot
insert and first-token sampling. Moves ``ttft_p95_ms``."""


def read(record):
    if record["ctx"].traffic["kind"] != "serve":
        return None
    h = record["state"]["engine"].metrics.histograms.get("prefill_s")
    if h is None or not h.count:
        return None
    return 1e3 * h.percentile(50)
