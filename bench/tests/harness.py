"""A tiny cell for the CPU tests: the layout of qwen1.5-1.8b-linear at
test widths, with fp32 compute so that program and reference agree to
rounding."""

import jax

from bench import run as B
from bench.model import load_json

BENCH = {"per_layer": [], "end_to_end": [
    {"name": n, "unit": "x"} for n in (
        "train_tokens_per_s", "serve_tokens_per_s", "ttft_p95_ms",
        "itl_p95_ms", "setup_s")]}
# the tiny cell's own limits: far above fp32 rounding (about 1e-6 here)
LIMITS = {"train": {"limits": {"loss_gap": 1e-4, "grad_gap": 1e-4,
                               "change_gap": 1e-3}},
          "serve": {"limits": {"logit_gap": 1e-3}}}


def ctx(kind, seed=2**31 + 11, seconds=2.0):
    c = load_json("bench/tests/data/tiny.json")
    t = load_json(f"bench/tests/data/tiny-{kind}.json")
    return B.Ctx(f"tiny-{kind}", {"chips": 1}, c, t, seed, seconds, False, 1)


def run(kind, **kw):
    return B.run_cell(ctx(kind, **kw), BENCH, jax.devices(),
                      limits=LIMITS[kind])
