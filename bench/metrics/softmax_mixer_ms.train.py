"""softmax_mixer_ms.train (ms per step): device time of the layer scope
``mixer.softmax`` (ln1, the q/k/v/o projections, RoPE and the flash
kernels; forward, remat and backward) on the device where it is
longest, from ``bench.ranks``' traced window. The K/V all-gathers and
their reduce-scatters sit in scopes of their own (``comm.lasp2h.*``,
read by ``exposed_collective_ms.train``). The softmax mixer's layer
(models/blocks.py). Moves ``train_tokens_per_s``."""

from bench import ranks


def read(record):
    return ranks.worst_scope_ms(record, "mixer.softmax")
