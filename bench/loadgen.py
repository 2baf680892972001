"""The general traffic generator: reads a traffic file and makes the
cell's inputs from ``--seed``.

Training (``"kind": "train"``): rows of ``seq_len`` tokens, each packed
with documents whose lengths are drawn from the file's distribution; a
document's last token predicts nothing (label -1) and its first token
resets the linear-attention state. Every step draws fresh documents and
ids, so no two rows repeat. The work of a step does not depend on where
the boundaries fall.

Serving (``"kind": "serve"``): an open-loop schedule. Every seed gets the
same multiset of prompt lengths, output lengths and gaps between
arrivals, taken at evenly spaced quantiles of the file's distributions
(lognormal lengths, exponential gaps at ``rate_per_s``); the seed only
shuffles them and draws the token ids. So runs with different seeds do
the same work in another order.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List

import numpy as np


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """Evenly spaced quantiles (i + 0.5) / n of a length distribution,
    rounded and clipped to [min, max]."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = NormalDist(math.log(dist["median"]), dist["sigma"])
    x = np.array([math.exp(nd.inv_cdf((i + 0.5) / n)) for i in range(n)])
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)


def _draw(dist: dict, rng) -> int:
    x = rng.lognormal(math.log(dist["median"]), dist["sigma"])
    return int(np.clip(round(x), dist["min"], dist["max"]))


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def train_batch(t: dict, seed: int, step: int, vocab: int) -> dict:
    """(1, rows, seq_len) tokens, labels, resets and document ids for one
    step (one microbatch)."""
    rng = np.random.default_rng([seed, step])
    rows, s = t["rows"], t["seq_len"]
    tokens = rng.integers(0, vocab, size=(rows, s), dtype=np.int32)
    resets = np.zeros((rows, s), bool)
    for r in range(rows):
        pos = 0
        while pos < s:
            resets[r, pos] = True
            pos += _draw(t["docs"], rng)
    labels = np.where(np.roll(resets, -1, axis=1), -1,
                      np.roll(tokens, -1, axis=1)).astype(np.int32)
    labels[:, -1] = -1
    seg = (np.cumsum(resets, axis=1) - 1).astype(np.int32)
    return {"tokens": tokens[None], "labels": labels[None],
            "resets": resets[None], "seg": seg[None]}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

@dataclass
class Request:
    due: float                      # seconds after the window opens
    prompt: np.ndarray
    max_new: int
    index: int
    uid: int = -1
    noticed: float = math.nan       # when the loop saw it due
    submitted: float = math.nan
    times: List[float] = field(default_factory=list)   # per output token
    tokens: List[int] = field(default_factory=list)


def serve_schedule(t: dict, seed: int, seconds: float, vocab: int,
                   rate: float = None) -> List[Request]:
    rate = t["rate_per_s"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed)
    prompts = rng.permutation(_quantiles(t["prompt"], n))
    outputs = rng.permutation(_quantiles(t["output"], n))
    gaps = np.array([-math.log(1 - (i + 0.5) / n) / rate for i in range(n)])
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Request(due=float(due[i]),
                    prompt=rng.integers(0, vocab, size=int(prompts[i]),
                                        dtype=np.int32),
                    max_new=int(outputs[i]), index=i) for i in range(n)]


def prompt_buckets(t: dict, seconds: float, rate: float = None) -> list:
    """The distinct prompt lengths the schedule holds (every seed has the
    same multiset)."""
    rate = t["rate_per_s"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    return sorted(set(int(x) for x in _quantiles(t["prompt"], n)))


def max_context(t: dict) -> int:
    return t["prompt"]["max"] + t["output"]["max"]
