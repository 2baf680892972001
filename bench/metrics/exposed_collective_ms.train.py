"""exposed_collective_ms.train (ms per step): device time of the
collectives (all-gather, reduce-scatter, all-reduce, all-to-all,
collective-permute) that no other operation on the same device overlaps,
on the device of the step's critical path (``bench.ranks.critical_device``:
the one the others wait for), from ``bench.ranks``' traced window. There
the exposed time is the exchange itself; on the other devices it is
mostly the wait for that one, which standard error reports beside each
``comm.<tag>`` scope's part. The sequence-parallel exchange's layer
(comm/primitives.py). Moves ``train_tokens_per_s``."""

import sys

from bench import ranks


def read(record):
    r = ranks.measure(record)
    if r is None:
        return None
    plane = ranks.critical_device(r)
    ex = r["devices"][plane]["exposed"]
    waits = {p: d["exposed"]["exposed_ns"] - ex["exposed_ns"]
             for p, d in r["devices"].items()}
    longest = max(waits, key=waits.get)
    print(f"exposed_collective_ms.train: critical path {plane}: "
          + ", ".join(f"{k} {v * 1e-6:.3f} ms" for k, v in sorted(
              ex["by_label"].items(), key=lambda x: -x[1]))
          + f"; the longest wait beyond it, {longest}: "
          f"{waits[longest] * 1e-6:.3f} ms", file=sys.stderr, flush=True)
    return ex["exposed_ns"] * 1e-6
