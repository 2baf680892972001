"""mlp_ms.train (ms per step): device time of the layer scope ``mlp``
(ln2 and the gated MLP; forward, remat and backward) in the second
traced window (``bench.scoped``). Moves ``train_tokens_per_s``."""

from bench import scoped


def read(record):
    return scoped.scope_ms(record, "mlp")
