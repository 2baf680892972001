import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")
# A CPU tool that counts (virtual host devices); its per-cell children
# inherit this, so on a TPU host none of them takes the chip.
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse          # noqa: E402
import dataclasses      # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

"""Roofline analysis per (arch × shape) on the single-pod mesh.

Methodology (DESIGN/EXPERIMENTS): XLA's ``cost_analysis`` counts each
``while`` (scan) body ONCE, so instead of trusting the full-depth compile
we lower reduced-depth *unrolled* programs and solve the exact cost model

    cost(A, G) = c0 + A·(c1 + G·c2)        (train; A = microbatches,
                                            G = layer-group count)
    cost(G)    = c0 + G·c1                 (prefill / decode)

which is exact because every layer group is identical by construction.
FLOPs / HBM bytes come from ``cost_analysis`` (per-device, post-SPMD);
collective bytes from parsing the compiled HLO (ring cost model, see
``hlo_analysis``). Terms are reported in seconds against TPU v5e peaks.

  python -m repro.launch.roofline --arch mamba2-2.7b --shape prefill_32k
  python -m repro.launch.roofline --all
"""


def _measure_cell(arch, shape_name, mesh, *, n_units, microbatches=None,
                  cfg_override=None, overrides=None):
    """Lower+compile a reduced-depth unrolled cell; return CostVector."""
    from repro.configs.base import RunConfig
    from repro.launch import hlo_analysis as H
    from repro.launch.cells import build_cell, reduced_depth_config, \
        resolve_config

    cfg, _note = (cfg_override, "override") if cfg_override is not None \
        else resolve_config(arch, shape_name)
    cfg_small = reduced_depth_config(cfg, n_units)
    run = RunConfig(scan_unroll=True, **(overrides or {}))
    cell = build_cell(arch, shape_name, mesh, run=run,
                      cfg_override=cfg_small)
    if microbatches is not None and cell.shape.kind == "train":
        # rebuild with a forced microbatch count
        run = dataclasses.replace(run, num_microbatches=microbatches)
        cell = _rebuild_train_cell(arch, shape_name, mesh, cfg_small, run)
    compiled = cell.lower().compile()
    return H.measure(compiled, mesh.size)


def _rebuild_train_cell(arch, shape_name, mesh, cfg, run):
    import jax
    import jax.numpy as jnp

    from repro.configs.base import SHAPES
    from repro.launch.cells import (Cell, _batch_sharding_tree, _sds,
                                    _state_shardings, aux_input_specs)
    from repro.sharding.rules import make_plan
    from repro.train.step import init_state, make_train_step

    shape = SHAPES[shape_name]
    plan = make_plan(mesh, shape.kind, global_batch=shape.global_batch,
                     n_kv_heads=cfg.n_kv_heads)
    plan.banded_windows = run.banded_windows
    a = run.num_microbatches
    # per-µb rows fixed to the production cell's value so the per-µb cost
    # c1 + G·c2 measured here matches the production program exactly
    from repro.launch.cells import choose_microbatches
    import numpy as np
    dp = int(np.prod([mesh.shape[ax] for ax in plan.dp_axes
                      if ax in mesh.axis_names]))
    a_prod = choose_microbatches(shape, dp, target=run.microbatch_tokens)
    bm = shape.global_batch // a_prod
    state_shapes = jax.eval_shape(
        lambda: init_state(jax.random.PRNGKey(0), cfg, run))
    batch = {"tokens": _sds((a, bm, shape.seq_len), jnp.int32),
             "labels": _sds((a, bm, shape.seq_len), jnp.int32),
             "resets": _sds((a, bm, shape.seq_len), jnp.bool_)}
    batch.update(aux_input_specs(cfg, bm, lead=(a,)))
    fn = make_train_step(cfg, run, plan)
    sspec = _state_shardings(state_shapes, plan)
    bspec = _batch_sharding_tree(batch, plan, lead_micro=True)
    return Cell(arch, shape, cfg, plan, run, fn, (state_shapes, batch),
                (sspec, bspec), (0,))


# single source of truth moved to hlo_analysis (import-side-effect-free)
# so repro.obs can reuse it; re-exported here for back-compat.
from repro.launch.hlo_analysis import model_flops  # noqa: E402, F401


def run_one(arch: str, shape_name: str, out_dir: str, *,
            overrides=None, tag=""):
    from repro.configs.base import SHAPES
    from repro.launch import hlo_analysis as H
    from repro.launch.cells import choose_microbatches, resolve_config
    from repro.launch.mesh import make_production_mesh
    from repro.sharding.rules import make_plan
    import numpy as np

    mesh = make_production_mesh(multi_pod=False)
    shape = SHAPES[shape_name]
    cfg, note = resolve_config(arch, shape_name)
    rec = {"arch": arch, "shape": shape_name, "config": cfg.name,
           "note": note, "mesh": "16x16", "status": "running",
           "overrides": overrides or {}, "tag": tag}
    t0 = time.time()
    try:
        if shape.kind == "train":
            f11 = _measure_cell(arch, shape_name, mesh, n_units=1,
                                microbatches=1, overrides=overrides)
            f12 = _measure_cell(arch, shape_name, mesh, n_units=2,
                                microbatches=1, overrides=overrides)
            f21 = _measure_cell(arch, shape_name, mesh, n_units=1,
                                microbatches=2, overrides=overrides)
            c2 = f12 - f11
            c1 = (f21 - f11) - c2
            c0 = f11 - c1 - c2
            plan = make_plan(mesh, "train",
                             global_batch=shape.global_batch,
                             n_kv_heads=cfg.n_kv_heads)
            dp = int(np.prod([mesh.shape[ax] for ax in plan.dp_axes
                              if ax in mesh.axis_names]))
            from repro.configs.base import RunConfig as _RC
            a = choose_microbatches(
                shape, dp, target=_RC(**(overrides or {})).microbatch_tokens)
            g = cfg.n_groups
            total = c0 + (c1 + c2.scale(g)).scale(a)
            rec["extrapolation"] = {"A": a, "G": g}
        else:
            f1 = _measure_cell(arch, shape_name, mesh, n_units=1,
                               overrides=overrides)
            f2 = _measure_cell(arch, shape_name, mesh, n_units=2,
                               overrides=overrides)
            c1 = f2 - f1
            c0 = f1 - c1
            g = cfg.n_groups
            total = c0 + c1.scale(g)
            rec["extrapolation"] = {"G": g}

        terms = H.roofline_terms(total)
        mf = model_flops(cfg, shape)
        hlo_flops_global = total.flops * mesh.size
        ideal_s = mf / H.PEAK_FLOPS / mesh.size   # perfect-MFU step time
        bound_s = max(terms["compute_s"], terms["memory_s"],
                      terms["collective_s"])
        rec.update({
            "status": "ok",
            "per_device": {"flops": total.flops,
                           "hbm_bytes": total.hbm_bytes,
                           "collective_bytes": total.coll_bytes,
                           "coll_by_op": total.coll_by_op},
            "terms": terms,
            "model_flops": mf,
            "hlo_flops_global": hlo_flops_global,
            "useful_flops_ratio": mf / hlo_flops_global
            if hlo_flops_global else 0.0,
            # how close the roofline-bound step time is to perfect MFU
            "roofline_fraction": ideal_s / bound_s if bound_s else 0.0,
        })
        print(f"[roofline] {arch} x {shape_name}: "
              f"compute {terms['compute_s']*1e3:.2f}ms "
              f"memory {terms['memory_s']*1e3:.2f}ms "
              f"collective {terms['collective_s']*1e3:.2f}ms "
              f"-> {terms['dominant']}-bound; "
              f"useful-FLOPs {rec['useful_flops_ratio']:.2%}; "
              f"roofline-fraction {rec['roofline_fraction']:.2%}")
    except Exception as e:  # noqa: BLE001
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[roofline] {arch} x {shape_name}: FAIL {e}",
              file=sys.stderr)
    rec["total_s"] = round(time.time() - t0, 1)
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}".replace("/", "_") \
        + (f"__{tag}" if tag else "")
    with open(os.path.join(out_dir, fname + ".json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec["status"] == "ok"


def run_all(out_dir: str, timeout: int = 2400):
    from repro.configs import ARCH_IDS
    from repro.configs.base import SHAPES
    results = {}
    for arch in ARCH_IDS:
        for shape in SHAPES:
            tag = f"{arch}__{shape}"
            path = os.path.join(out_dir, tag.replace("/", "_") + ".json")
            if os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("status") == "ok":
                        results[tag] = "cached"
                        continue
            cmd = [sys.executable, "-m", "repro.launch.roofline",
                   "--arch", arch, "--shape", shape, "--out", out_dir]
            try:
                proc = subprocess.run(cmd, timeout=timeout,
                                      capture_output=True, text=True)
                results[tag] = "ok" if proc.returncode == 0 else "fail"
            except subprocess.TimeoutExpired:
                results[tag] = "timeout"
            print(f"{tag}: {results[tag]}", flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/roofline")
    ap.add_argument("--set", action="append", default=[],
                    help="RunConfig override key=value (hillclimb variants)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        import ast
        try:
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v
    if args.all:
        res = run_all(args.out)
        bad = [k for k, v in res.items() if v not in ("ok", "cached")]
        print(f"\n{len(res) - len(bad)}/{len(res)} roofline cells OK")
        sys.exit(1 if bad else 0)
    ok = run_one(args.arch, args.shape, args.out,
                 overrides=overrides or None, tag=args.tag)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
