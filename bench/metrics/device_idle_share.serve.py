"""device_idle_share.serve (%): share of the traced window in which no
operation ran on the device, the worst device taken (``bench.trace``).
Moves ``serve_tokens_per_s``."""


def read(record):
    if record["ctx"].traffic["kind"] != "serve" or record["trace"] is None:
        return None
    return 100.0 * record["trace"]["idle_share"]
