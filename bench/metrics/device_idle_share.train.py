"""device_idle_share.train (%): share of the traced window in which no
operation ran on the device, the worst device taken (``bench.trace``).
Moves ``train_tokens_per_s``."""


def read(record):
    if record["ctx"].traffic["kind"] != "train" or record["trace"] is None:
        return None
    return 100.0 * record["trace"]["idle_share"]
