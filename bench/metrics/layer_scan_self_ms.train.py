"""layer_scan_self_ms.train (ms per step): device time of the scope
``layers`` outside its child scopes: the layer scan's own slicing of the
stacked weights, stacking of per-layer results and gradients, and the
residual adds, in the second traced window (``bench.scoped``). Moves
``train_tokens_per_s``."""

from bench import scoped


def read(record):
    return scoped.scope_ms(record, "layers")
