"""Shared benchmark utilities.

Wall-clock numbers on this CPU container are *indicative* (the TPU is the
target, not the runtime); every bench therefore also derives the analytic
quantity the paper's table is actually about (loss, comm steps, traffic,
memory). Multi-device timing benches run in subprocesses with 8 virtual
host devices so the main process keeps its single default device.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def timeit(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    _block(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _block(out)
    return (time.perf_counter() - t0) / iters


def percentile(xs, p):
    """Nearest-rank percentile of a non-empty list — shared with the
    bench subprocess payloads (run_subprocess_bench puts the repo root on
    the subprocess path) so the median/p90 policy lives in one place."""
    xs = sorted(xs)
    idx = min(len(xs) - 1, max(0, int(round(p / 100 * (len(xs) - 1)))))
    return xs[idx]


def write_bench_json(name: str, payload) -> str:
    """Write BENCH_<name>.json at the repo root — the machine-readable
    artifact CI uploads so the perf trajectory is tracked across PRs.
    ``payload``: dict (preferred: {"rows": [...], ...stats}) or a list of
    (name, us_per_call, derived) CSV rows."""
    if not isinstance(payload, dict):
        payload = {"rows": [
            {"name": n, "us_per_call": us, "derived": derived}
            for n, us, derived in payload]}
    payload = dict(payload)
    payload.setdefault("bench", name)
    payload.setdefault("schema_version", 1)
    path = os.path.join(ROOT, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {os.path.relpath(path, ROOT)}", file=sys.stderr)
    return path


def telemetry_block(*, phases=None, model_flops_per_call=None,
                    wall_s=None, n_devices=1,
                    expected_collective_bytes=None,
                    measured_collective_bytes=None, **extra) -> dict:
    """Assemble the optional ``telemetry`` block a bench attaches to its
    BENCH_*.json payload (docs/observability.md): phase wall breakdown,
    achieved MFU (``model_flops_per_call / wall_s`` against ``n_devices``
    times the local device's peak; ``None`` for a device not in
    ``DEVICE_PEAKS``), and the expected (CommRecord tape) vs
    measured (compiled HLO) collective bytes.

    Informational for now: scripts/bench_gate.py ignores metrics absent
    from the stored baseline, so adding this block changes no gate
    verdict — once baselines are refreshed the byte fields start gating
    as traffic (any increase fails)."""
    t = dict(extra)
    if phases:
        t["phases"] = {k: float(v) for k, v in phases.items()}
    if wall_s is not None:
        t["wall_s"] = float(wall_s)
    if model_flops_per_call and wall_s:
        from repro.launch.hlo_analysis import device_peak_flops
        achieved = model_flops_per_call / wall_s
        peak = device_peak_flops()
        t["achieved_flops"] = achieved
        t["mfu"] = achieved / (peak * max(n_devices, 1)) if peak else None
    if expected_collective_bytes is not None:
        t["expected_collective_bytes"] = float(expected_collective_bytes)
    if measured_collective_bytes is not None:
        t["measured_collective_bytes"] = float(measured_collective_bytes)
        if expected_collective_bytes:
            t["measured_over_expected"] = \
                float(measured_collective_bytes) / expected_collective_bytes
    return t


def _block(out):
    import jax
    jax.tree.map(lambda x: x.block_until_ready()
                 if hasattr(x, "block_until_ready") else x, out)


def run_subprocess_bench(code: str, *, devices: int = 8,
                         timeout: int = 1200) -> dict:
    """Run `code` (which must print a JSON dict on its last line) in a
    subprocess with N virtual host devices. The child is pinned to the
    CPU platform: the parent may already hold the chip on a TPU host,
    and one chip belongs to one process."""
    prelude = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={devices}'\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        f"import sys; sys.path.insert(0, {SRC!r})\n"
        f"sys.path.insert(0, {ROOT!r})\n")   # benchmarks.common importable
    proc = subprocess.run([sys.executable, "-c", prelude + code],
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def emit(rows, header=None):
    """Print CSV rows: name,us_per_call,derived."""
    if header:
        print(header)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
