"""Run one cell of the benchmark once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json``; the per-layer metric readers by name in
``bench/metrics/<metric>.py``. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; the numbers
compared with the reference come last, under ``check``, and again as the
last lines of standard error.

Without an accelerator, with fewer chips than the cell asks for, or on a
device kind missing from ``bench/peaks.py``, it exits non-zero and prints
no result.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


class BenchError(RuntimeError):
    pass


@dataclass
class Ctx:
    workload: str
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    peaks: dict = field(default_factory=dict)


def load_benchmark() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {ROOT}")
    with open(path) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str):
    from bench.model import load_json
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(configs[cell["config"]]["file"])
    traffic = load_json(os.path.join("bench", "traffic",
                                     f"{cell['traffic']}.json"))
    return cell, config, traffic


def metric_reader(name: str):
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"no reader for per-layer metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_accelerator(chips: int):
    """The devices, or an error: never a run on the CPU."""
    import jax
    from bench.peaks import peaks_for

    devices = jax.devices()
    if devices[0].platform not in ("tpu", "gpu"):
        raise BenchError(f"no accelerator: JAX sees {devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names another; every program is
    cached, however fast it compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def driver(traffic: dict):
    if traffic["kind"] == "train":
        from bench import train
        return train
    if traffic["kind"] == "serve":
        from bench import serve
        return serve
    raise BenchError(f"unknown traffic kind {traffic['kind']!r}")


def trace_dir(workload: str) -> str:
    return os.path.join(ROOT, "bench", ".traces", workload)


def run_cell(ctx: Ctx, bench: dict, devices, limits="file") -> dict:
    """Set-up, window, per-layer reading and check; returns the result."""
    import jax
    from bench import correct, trace as tr
    from bench.peaks import peaks_for

    ctx.peaks = peaks_for(devices[0].device_kind) \
        if devices[0].platform != "cpu" else {"flops": 1.0, "hbm_bw": 1.0}
    drv = driver(ctx.traffic)
    state = drv.setup(ctx)
    setup_s = time.perf_counter() - T_START

    tdir = trace_dir(ctx.workload)
    if ctx.trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
    w = drv.window(ctx, state)
    if ctx.trace:
        jax.profiler.stop_trace()
    used = devices[:ctx.chips]
    stats = [d.memory_stats() or {} for d in used]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(used),
              "memory_peak_bytes": peak}

    result = {"correct": False, "attempted": w["attempted"],
              "failed": w["failed"], "metrics": {}, "device": device}
    if ctx.trace:
        reduced = tr.reduce(tr.load(tr.find_xplane(tdir), spans=(
            "window", "data", "step", "fence", "submit", "wait", "record")))
        shutil.rmtree(tdir, ignore_errors=True)
        if reduced is None:
            raise BenchError("the traced window holds no device op")
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        record = {"ctx": ctx, "window": w, "trace": reduced, "state": state}
        for m in bench["per_layer"]:
            if ctx.workload not in m.get("workloads", [ctx.workload]):
                continue
            v = metric_reader(m["name"])(record)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        e2e = drv.end_to_end(ctx, w)
        e2e["setup_s"] = setup_s
        for m in bench["end_to_end"]:
            if m["name"] in e2e and ctx.workload in m.get(
                    "workloads", [ctx.workload]):
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    if hasattr(drv, "lateness_line"):
        print(drv.lateness_line(w), file=sys.stderr, flush=True)

    gc.collect()
    readings, detail = drv.check(ctx, state, w)
    gc.collect()
    if limits == "file":
        limits = correct.load_limits(ctx.workload)
    ok, check = correct.judge(readings, limits)
    result["correct"] = ok and w["failed"] == 0
    result["check"] = check
    print(f"check detail: {json.dumps(detail, default=str)[:6000]}",
          file=sys.stderr, flush=True)
    print(f"readings: {json.dumps(readings, default=str)}", file=sys.stderr,
          flush=True)
    for name, c in check.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench = load_benchmark()
        cell, config, traffic = load_cell(bench, args.workload)
        devices = require_accelerator(cell["chips"])
        enable_compile_cache()
        ctx = Ctx(args.workload, cell, config, traffic, args.seed,
                  args.seconds, bool(args.trace), cell["chips"])
        result = run_cell(ctx, bench, devices)
    except Exception as e:   # no result line on any failure
        import traceback
        traceback.print_exc()
        print(f"[bench] FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
