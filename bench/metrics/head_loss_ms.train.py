"""head_loss_ms.train (ms per step): device time of the scopes ``head``
(final norm and logits) and ``loss`` (cross-entropy), forward and
backward, in the second traced window (``bench.scoped``). Moves
``train_tokens_per_s``."""

from bench import scoped


def read(record):
    return scoped.scope_ms(record, "head", "loss")
