import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# A CPU tool that counts: it needs the virtual host devices, never the
# chip. Its per-cell child processes inherit both settings, so on a TPU
# host none of them takes the accelerator from a process that holds it.
os.environ["JAX_PLATFORMS"] = "cpu"

# The lines above MUST run before any other import (jax locks the
# device count at first initialization). Everything else follows.

import argparse          # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

"""Multi-pod dry-run: prove every (architecture × shape × mesh) cell
lowers AND compiles on the production meshes, and record the per-device
memory/cost/collective evidence for EXPERIMENTS.md §Dry-run.

Usage:
  python -m repro.launch.dryrun --arch codeqwen1.5-7b --shape train_4k
  python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod] [--jobs-file f.json]

``--all`` drives each cell in a fresh subprocess (compile-state isolation;
one cell's failure cannot poison the next) and aggregates JSON results
under results/dryrun/.
"""


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str):
    from repro.launch import hlo_analysis as H
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "devices": n_dev, "status": "building"}
    t0 = time.time()
    try:
        cell = build_cell(arch, shape_name, mesh)
        rec["note"] = cell.note
        rec["config_name"] = cell.cfg.name
        rec["params_b"] = cell.cfg.param_count() / 1e9
        rec["num_microbatches"] = cell.run.num_microbatches
        lowered = cell.lower()
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        rec["status"] = "ok"
        rec["memory"] = H.memory_report(compiled)
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {"flops": float(ca.get("flops", 0.0)),
                       "bytes_accessed": float(ca.get("bytes accessed",
                                                      0.0))}
        colls = H.parse_collectives(compiled.as_text(), n_dev)
        rec["collectives"] = H.collective_summary(colls)
        print(f"[dryrun] {arch} x {shape_name} x {rec['mesh']}: OK "
              f"(lower {rec['lower_s']}s, compile {rec['compile_s']}s)")
        print(f"  memory_analysis: {compiled.memory_analysis()}")
        print(f"  cost_analysis: flops={rec['cost']['flops']:.3e} "
              f"bytes={rec['cost']['bytes_accessed']:.3e}")
        print(f"  collectives: { {k: round(v/1e6, 2) for k, v in rec['collectives'].items() if not k.endswith('_count')} } MB")
    except Exception as e:  # noqa: BLE001 — record, don't crash the sweep
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {arch} x {shape_name} x {rec['mesh']}: FAIL {e}",
              file=sys.stderr)
    rec["total_s"] = round(time.time() - t0, 1)
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{rec['mesh']}".replace("/", "_")
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec["status"] == "ok"


def run_all(multi_pod: bool, out_dir: str, archs=None, shapes=None,
            timeout: int = 3600):
    """Spawn one subprocess per cell (isolation + bounded memory)."""
    from repro.configs import ARCH_IDS
    from repro.configs.base import SHAPES
    archs = archs or ARCH_IDS
    shapes = shapes or list(SHAPES)
    results = {}
    for arch in archs:
        for shape in shapes:
            tag = f"{arch}__{shape}__{'2x16x16' if multi_pod else '16x16'}"
            path = os.path.join(out_dir, tag.replace("/", "_") + ".json")
            if os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("status") == "ok":
                        results[tag] = "cached"
                        continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", out_dir]
            if multi_pod:
                cmd.append("--multi-pod")
            try:
                proc = subprocess.run(cmd, timeout=timeout,
                                      capture_output=True, text=True)
                ok = proc.returncode == 0
            except subprocess.TimeoutExpired:
                ok = False
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape,
                               "status": "timeout"}, f)
            results[tag] = "ok" if ok else "fail"
            print(f"{tag}: {results[tag]}", flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()
    if args.all:
        res = run_all(args.multi_pod, args.out, timeout=args.timeout)
        bad = [k for k, v in res.items() if v == "fail"]
        print(f"\n{len(res) - len(bad)}/{len(res)} cells OK")
        sys.exit(1 if bad else 0)
    ok = run_one(args.arch, args.shape, args.multi_pod, args.out)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
