"""Device time of each chunk-kernel program against the tokens per grid step.

    python scripts/chunk_tile_sweep.py [--tiles 128,256,512,1024,2048]

For each tile the module's ``MAX_TILE`` is set to it (``seq_tile`` then
gives that tile at S 8192), the forward and the backward are compiled at
the ``linear-train-8k`` kernel shape (B·H 16, S 8192, d 128, bf16, chunk
128, document resets in ``log_a``) and run ``--calls`` times each under the
profiler. Prints one JSON line per tile: microseconds per call of
``lasp2_chunk_fwd``, ``_bwd_dq`` and ``_bwd_dkv`` from the device trace
(``bench.trace``), and writes them all to ``chiprun_out/tile_sweep.json``.
Needs a TPU.
"""

from __future__ import annotations

import argparse
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
from repro.core.linear_attention import RESET_LOG_A  # noqa: E402
from repro.kernels import lasp2_chunk as lc        # noqa: E402

PROGRAMS = ("lasp2_chunk_fwd", "lasp2_chunk_bwd_dq", "lasp2_chunk_bwd_dkv")


def inputs(bh, s, d, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v, do = (jax.random.normal(kk, (bh, s, d), jnp.bfloat16)
                   for kk in ks[:4])
    starts = jax.random.uniform(ks[4], (bh, s)) < 1 / 600
    la = jnp.where(starts, RESET_LOG_A, 0.0).astype(jnp.float32)
    dstate = jnp.ones((bh, d, d), jnp.float32)
    return q, k, v, la, do, dstate


def measure(tile, args, calls):
    lc.MAX_TILE = tile
    jax.clear_caches()
    q, k, v, la, do, dstate = args
    fwd = jax.jit(lambda *a: lc.lasp2_chunk_fwd(*a))
    bwd = jax.jit(lambda *a: lc.lasp2_chunk_bwd(*a))
    o = fwd(q, k, v, la)[0]
    jax.block_until_ready(bwd(q, k, v, la, o, do, dstate))
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            with jax.profiler.TraceAnnotation("window"):
                for _ in range(calls):
                    out = fwd(q, k, v, la), bwd(q, k, v, la, o, do, dstate)
                jax.block_until_ready(out)
        red = trace.reduce(trace.load(trace.find_xplane(d)))
    return {"tile": tile, "seq_tile": lc.seq_tile(q.shape[1], 128),
            "grid_steps": q.shape[0] * q.shape[1] // tile,
            **{p: 1e6 * red["op_s"][p] / red["op_calls"][p]
               for p in PROGRAMS}}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiles", default="128,256,512,1024,2048")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("needs a TPU")
    args = inputs(16, 8192, 128, a.seed)
    rows = []
    for t in (int(x) for x in a.tiles.split(",")):
        rows.append(measure(t, args, a.calls))
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "tile_sweep.json"), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
