"""Record the small device trace that the trace-reduction tests read.

    python3 bench/tests/record_trace.py [out_dir]

Runs on a TPU: a jitted step holding the ``lasp2_chunk_fwd`` Pallas
kernel and a matmul, inside the benchmark's own host spans (``data``,
``step``, ``fence``), with a 50 ms host-only pause in ``data`` so the
trace holds a known idle gap. Writes ``trace.xplane.pb`` to ``out_dir``
(default ``bench/tests/data``) and prints the planes and lines it holds.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    out_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "bench", "tests", "data")
    os.makedirs(out_dir, exist_ok=True)

    def step(q, k, v, w):
        o, _, _ = ops.linear_attention_op(q, k, v, None)
        return jnp.sum((o.reshape(-1, 128) @ w).astype(jnp.float32))

    f = jax.jit(step)
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (16, 2048, 128), jnp.bfloat16)
               for kk in jax.random.split(key, 3))
    w = jax.random.normal(key, (128, 4096), jnp.bfloat16)
    f(q, k, v, w).block_until_ready()

    tmp = tempfile.mkdtemp(dir=out_dir)
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        with TraceAnnotation("data"):
            time.sleep(0.05)
        with TraceAnnotation("step"):
            r = f(q, k, v, w)
        with TraceAnnotation("fence"):
            r.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    dest = os.path.join(out_dir, "trace.xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(tmp)
    pd = ProfileData.from_file(dest)
    for plane in pd.planes:
        print("PLANE", plane.name, dict(plane.stats))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:6]:
                print("    EV", repr(e.name), e.start_ns, e.duration_ns,
                      {k: str(v)[:80] for k, v in e.stats})
    print("bytes", os.path.getsize(dest))
    return 0


if __name__ == "__main__":
    sys.exit(main())
