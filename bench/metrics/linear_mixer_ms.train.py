"""linear_mixer_ms.train (ms per step): device time of the layer scope
``mixer.linear`` (ln1, the q/k/v/o projections, RoPE and the chunk
kernels; forward, remat and backward) in the second traced window
(``bench.scoped``). Moves ``train_tokens_per_s``."""

from bench import scoped


def read(record):
    return scoped.scope_ms(record, "mixer.linear")
