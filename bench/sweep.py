"""Find the knee of a serving cell once, by a sweep on the chip.

    python3 bench/sweep.py --workload <cell> --rates 10,20,30 \
        [--seconds S] [--seed N]

One process sets the cell up once, then offers the cell's traffic at each
rate in turn for ``--seconds`` (each window drained before the next) and
prints one JSON line per rate: completed output tokens per second, time
to first token and gap between tokens, generator lateness, and the
client-side backlog half-way through the window and at its close. The
knee is the highest rate whose backlog does not grow over the window;
the cell's traffic file then takes four fifths of it as ``rate_per_s``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as B  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()
    from bench import serve
    from bench.stats import percentile

    bench = B.load_benchmark()
    cell, config, traffic = B.load_cell(bench, a.workload)
    B.require_accelerator(cell["chips"])
    B.enable_compile_cache()
    rates = [float(x) for x in a.rates.split(",")]
    ctx = B.Ctx(a.workload, cell, config, traffic, a.seed, a.seconds, False,
                cell["chips"])
    s = serve.setup(ctx, rate=max(rates))
    for rate in rates:
        s["engine"].reset_metrics()
        w = serve.window(ctx, s, rate=rate)
        e2e = serve.end_to_end(ctx, w)
        q = lambda xs, p: 1e3 * percentile(xs, p) if xs else None
        print(json.dumps({
            "rate_per_s": rate, "requests": w["attempted"],
            "failed": w["failed"], **e2e,
            "ttft_p50_ms": q(w["ttft"], 50),
            "decode_step_p50_ms": 1e3 * s["engine"].metrics.histograms[
                "decode_step_s"].percentile(50),
            "late_submitted_p95_ms": q(w["late_submitted"], 95),
            "backlog_mid": w["backlog_mid"],
            "backlog_end": w["backlog_end"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
