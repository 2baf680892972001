"""Device time of each flash-attention kernel against its tiles.

    python scripts/flash_tile_sweep.py [--tiles 128,256,512,1024]
        [--offsets 0,49152] [--bh 16] [--dtype bfloat16] [--calls 3]

At one rank's shape of the ``hybrid-train-sp4-64k`` softmax layer (q
16,384 against K/V 65,536, B·H 16, d 128, causal, the rank offset traced)
the gradient of the flash attention is compiled for every pair of tiles
``(block_q, block_k)`` from ``--tiles``, passed explicitly, and run
``--calls`` times at each offset under the profiler. Prints one JSON line
per tile pair and offset: microseconds per call of ``flash_attention_fwd``,
``_bwd_dq`` and ``_bwd_dkv`` from the device trace (``bench.trace``), and
the same per live 128 × 128 block pair, the unit of the causal work at
that offset. Writes every line to ``chiprun_out/flash_tile_sweep.json``
(``flash_tile_sweep_<dtype>.json`` for another dtype). Needs a TPU.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax                                         # noqa: E402
import jax.numpy as jnp                            # noqa: E402

from bench import trace                            # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402

PROGRAMS = ("flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv")
SQ, SK, D = 16384, 65536, 128


def live_pairs(sq, offset, blk=128):
    """Causally needed 128 × 128 block pairs of one head at ``offset``."""
    return sum((offset + (i + 1) * blk - 1) // blk + 1
               for i in range(sq // blk))


def per_call_us(red, program):
    """Microseconds a call of ``program``. Under ``jax.grad`` the compiled
    instructions carry the transformation in their names
    (``jvp_flash_attention_fwd_``, ``transpose_jvp_flash_attention_bwd_dq__``),
    so every op whose name holds the program's counts."""
    names = [n for n in red["op_s"] if program in n]
    if not names:
        raise SystemExit(f"no {program} in the trace: {sorted(red['op_s'])}")
    return (1e6 * sum(red["op_s"][n] for n in names)
            / sum(red["op_calls"][n] for n in names))


def inputs(bh, dtype, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, bh, SQ, D), dtype)
    k, v = (jax.random.normal(kk, (1, bh, SK, D), dtype) for kk in ks[1:3])
    co = jax.random.normal(ks[3], (1, bh, SQ, D), jnp.float32)
    return q, k, v, co


def measure(bq, bk, args, offsets, calls):
    q, k, v, co = args

    def loss(q_, k_, v_, off):
        o = flash_attention(q_, k_, v_, q_offset=off, block_q=bq,
                            block_k=bk)
        return jnp.sum(o.astype(jnp.float32) * co)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    jax.block_until_ready(grad(q, k, v, jnp.int32(0)))
    rows = []
    for off in offsets:
        o_ = jnp.int32(off)
        jax.block_until_ready(grad(q, k, v, o_))
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                with jax.profiler.TraceAnnotation("window"):
                    for _ in range(calls):
                        out = grad(q, k, v, o_)
                    jax.block_until_ready(out)
            red = trace.reduce(trace.load(trace.find_xplane(d)))
        pairs = q.shape[1] * live_pairs(SQ, off)
        us = {p: per_call_us(red, p) for p in PROGRAMS}
        rows.append({"block_q": bq, "block_k": bk, "offset": off,
                     "dtype": str(q.dtype), "bh": q.shape[1],
                     "live_pairs": pairs, **us,
                     **{p + "_per_pair": us[p] / pairs for p in PROGRAMS}})
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiles", default="128,256,512,1024")
    ap.add_argument("--offsets", default="0,49152")
    ap.add_argument("--bh", type=int, default=16)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("needs a TPU")
    args = inputs(a.bh, jnp.dtype(a.dtype), a.seed)
    offsets = [int(x) for x in a.offsets.split(",")]
    tiles = [int(x) for x in a.tiles.split(",")]
    rows = []
    for bq, bk in itertools.product(tiles, tiles):
        for row in measure(bq, bk, args, offsets, a.calls):
            rows.append(row)
            print(json.dumps(row), flush=True)
    name = ("flash_tile_sweep.json" if a.dtype == "bfloat16"
            else f"flash_tile_sweep_{a.dtype}.json")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
