"""optimizer_ms.train (ms per step): device time of the scope
``optimizer`` (clipping, schedule, AdamW and the skip-on-non-finite
selects) in the second traced window (``bench.scoped``). Moves
``train_tokens_per_s``."""

from bench import scoped


def read(record):
    return scoped.scope_ms(record, "optimizer")
