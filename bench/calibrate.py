"""Readings from which the limits of ``correct`` are set (``PERF.md``).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 101,102,103 [--seconds S] [--out file.json]

In one process, on the chip, at the cell's own size:

* for every seed of ``--seeds``, the program's readings against the
  reference, as a run of the cell reads them (training: the first steps
  of set-up; serving: a window of ``--seconds`` at the cell's load);
* for every seed of ``--control-seeds``, the control's: the reference
  computed in float8 put in the program's place (serving: at every
  position of the same prompts and tokens, the token the float8 reference
  puts first), and the faults planted in the reference put in the
  program's place (training: half of each row's tokens left out of the
  loss; serving: one served token altered).

Prints one JSON line per seed and writes them all to ``--out``.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as B  # noqa: E402


def _train(ctx, control):
    import jax.numpy as jnp
    from bench import correct, train
    from bench.reference import train as ref_train
    from bench.weights import make_weights

    wf = functools.partial(make_weights, ctx.seed, ctx.config, jnp.float32)
    batches = train._ref_batches(ctx.traffic, ctx.seed,
                                 ctx.config["vocab_size"])

    def ref(precision="fp32", bs=batches):
        losses, grad, change = ref_train.run(wf, bs, ctx.config, precision)
        return {"losses": losses, "grad": grad, "change": change}

    out = {}
    if not control:
        s = train.setup(ctx)
        prog = s.pop("prog")
        s.clear()
        gc.collect()
        r = ref()
        out["program"] = correct.train_readings(prog, r)
        return out
    r = ref()
    out["control_fp8"] = correct.train_readings(ref("fp8"), r)
    half = []
    for b in batches:
        lab = b["labels"]
        lab = jnp.where(jnp.arange(lab.shape[0]) >= lab.shape[0] // 2, -1,
                        lab)
        half.append(dict(b, labels=lab))
    out["fault_half_batch"] = correct.train_readings(ref(bs=half), r)
    return out


def _serve(ctx, control):
    from bench import serve

    s = serve.setup(ctx)
    w = serve.window(ctx, s)
    s.clear()
    gc.collect()
    picked = serve.sample(ctx, w)
    out = {"program": serve.reference_gaps(ctx, picked),
           "ttft_p95_ms": serve.end_to_end(ctx, w)["ttft_p95_ms"]}
    if control:
        out["control_fp8"] = serve.reference_gaps(ctx, picked, control=True)
        longest = picked[0]
        tok = longest.tokens[0]
        longest.tokens[0] = (tok + 1) % ctx.config["vocab_size"]
        out["fault_token_altered"] = serve.reference_gaps(ctx, picked)
        longest.tokens[0] = tok
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    bench = B.load_benchmark()
    cell, config, traffic = B.load_cell(bench, a.workload)
    B.require_accelerator(cell["chips"])
    B.enable_compile_cache()
    seeds = [int(x) for x in a.seeds.split(",") if x]
    controls = [int(x) for x in a.control_seeds.split(",") if x]
    fn = _train if traffic["kind"] == "train" else _serve
    rows = []
    for seed, control in [(x, False) for x in seeds] + \
            [(x, True) for x in controls]:
        ctx = B.Ctx(a.workload, cell, config, traffic, seed, a.seconds,
                    False, cell["chips"])
        row = {"seed": seed, **fn(ctx, control)}
        print(json.dumps(row, default=str), flush=True)
        rows.append(row)
        gc.collect()
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(rows, f, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
