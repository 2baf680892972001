"""step_gap_ms.train (ms per step): mean device idle time between
consecutive executions of the train step, on the device's own clock, in
the second traced window (``bench.scoped``; the worst device). Moves
``train_tokens_per_s``."""

from bench import scoped


def read(record):
    r = scoped.measure(record)
    return None if r is None else r["step_gap_ms"]
