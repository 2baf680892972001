"""The tiny 1/4 hybrid (3 linear : 1 causal softmax) at dp 1 × sp 4 on
four CPU devices, held to the limits of ``hybrid-train-sp4-64k``, with a
fault planted in its softmax layer or none; one JSON line per case.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 bench/tests/hybrid_four_devices.py sound kv_local

Faults, planted underneath the harness:

``kv_local``  K and V not gathered: each chip attends its own chunk;
``q_offset_zero``  the gathered keys masked as if every chip held the
    row's first chunk (rank offset 0);
``non_causal``  the softmax layer sees the keys after each query too.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "hybrid-train-sp4-64k"
HYBRID = {"layer_pattern": ["linear", "linear", "linear", "softmax"],
          "num_hidden_layers": 4}


def traffic(seq_len=512):
    """The tiny training traffic at the cell's layout: one document a
    row, split over four chips."""
    return {"seq_len": seq_len, "layout": {"dp": 1, "sp": 4,
                                           "remat": "full"},
            "docs": {"dist": "lognormal", "median": seq_len, "sigma": 1.0,
                     "min": seq_len, "max": seq_len}}


def plant(fault, setattr):
    from repro.comm import primitives
    from repro.kernels import ops

    real_flash, real_gather = ops.flash_attention_op, \
        primitives.allgather_states

    def flash(q, k, v, **kw):
        if fault == "non_causal":
            kw["causal"] = False
        elif kw.get("q_offset") is not None:
            kw["q_offset"] = 0
        return real_flash(q, k, v, **kw)

    def local_kv(x, axis, *, tag="", **kw):
        if tag.startswith("lasp2h."):
            return x
        return real_gather(x, axis, tag=tag, **kw)

    if fault not in ("kv_local", "q_offset_zero", "non_causal"):
        raise ValueError(f"unknown fault {fault!r}")
    setattr(ops, "flash_attention_op", flash)
    if fault == "kv_local":
        setattr(primitives, "allgather_states", local_kv)


def main(cases) -> int:
    import jax
    import pytest

    from bench import correct, run as B
    from bench.tests import harness

    if len(jax.devices()) != 4:
        print(f"needs 4 devices, JAX sees {len(jax.devices())}",
              file=sys.stderr)
        return 1
    limits = correct.load_limits(CELL)
    for case in cases:
        with pytest.MonkeyPatch.context() as mp:
            if case != "sound":
                plant(case, mp.setattr)
            ctx = harness.ctx("train", config=HYBRID, traffic=traffic(),
                              chips=4)
            r = B.run_cell(ctx, harness.BENCH, jax.devices(), limits=limits)
        print(json.dumps({"case": case, "correct": r["correct"],
                          "check": r["check"],
                          "count": r["device"]["count"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
