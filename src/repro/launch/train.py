"""Training launcher.

Single-host (this container): runs the fault-tolerant loop on the local
device(s). On a real multi-host TPU/TRN cluster the same entry point is
launched per host with ``jax.distributed.initialize()`` (coordinator from
env) and the production mesh; data sharding per host falls out of the
deterministic pipeline (batch(step) is a pure function).

  PYTHONPATH=src python -m repro.launch.train --arch linear-llama3-1b \
      --steps 300 --batch 8 --seq 512 --smoke --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="linear-llama3-1b")
    ap.add_argument("--variant", default=None,
                    help="config-module variant (e.g. HYBRID, DENSE)")
    ap.add_argument("--linearize", type=int, default=None,
                    help="paper recipe: 0=pure linear, k=1/k hybrid")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--guard", action="store_true",
                    help="in-graph numerical health guard "
                         "(docs/resilience.md): finite check piggybacked "
                         "on the packed grad all-reduce (zero extra "
                         "collectives), skip-step on non-finite updates, "
                         "rolling-median grad-norm spike clipping, abort "
                         "after --guard-max-skips consecutive skips")
    ap.add_argument("--guard-max-skips", type=int, default=8,
                    help="consecutive skipped steps before the loop "
                         "aborts with GuardAbort")
    ap.add_argument("--ckpt-verify", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="verify per-array SHA-256 checksums on restore; "
                         "a corrupt latest checkpoint falls back to the "
                         "newest valid one (--no-ckpt-verify to disable)")
    ap.add_argument("--remat", default="none", choices=["none", "full"])
    ap.add_argument("--multi-device", action="store_true",
                    help="use all local devices as a (data,) mesh")
    ap.add_argument("--dp-degree", type=int, default=0,
                    help="data-parallel degree of the 2D (data, sequence) "
                         "training mesh; with --sp-degree, dp×sp must "
                         "equal the device count (docs/parallelism.md)")
    ap.add_argument("--sp-degree", type=int, default=0,
                    help="sequence-parallel degree of the 2D training "
                         "mesh (LASP-2 SP over the 'sequence' axis)")
    ap.add_argument("--tp-degree", type=int, default=0,
                    help="head-parallel degree of the 3D DP×SP×TP "
                         "training mesh ('model' axis — the ulysses "
                         "All-to-All head repartition for hybrid "
                         "layers; docs/parallelism.md §3D)")
    ap.add_argument("--no-zero1", action="store_true",
                    help="replicate optimizer state instead of ZeRO-1 "
                         "sharding it over the data axis")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--comm-strategy", default="allgather",
                    choices=["allgather", "ring", "pipelined", "ulysses"],
                    help="SP state-exchange strategy (repro/comm)")
    ap.add_argument("--comm-overlap", default="overlap",
                    choices=["overlap", "none"],
                    help="comm/compute overlap mode (A/B benchmarking)")
    ap.add_argument("--comm-dtype", default="fp32",
                    choices=["fp32", "bf16"],
                    help="wire dtype of the SP state/KV exchanges (bf16 "
                         "halves per-layer collective bytes; combines "
                         "stay fp32 — docs/communication.md)")
    ap.add_argument("--metrics-out", default=None,
                    help="write run telemetry (per-step phase walls, "
                         "tokens/s, MFU, expected-vs-compiled collective "
                         "bytes) as JSONL here; render with "
                         "scripts/report.py (docs/observability.md)")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["xla", "pallas", "interpret"],
                    help="intra-chunk/attention kernel path "
                         "(repro/kernels/ops.py; default: pallas on TPU, "
                         "xla elsewhere)")
    args = ap.parse_args()

    import jax

    from repro.configs import get_config, get_smoke, get_variant
    from repro.configs.base import RunConfig
    from repro.data.pipeline import SyntheticLM
    from repro.launch.compile_cache import enable_compile_cache
    from repro.sharding.rules import make_plan
    from repro.train.loop import train

    enable_compile_cache()
    if args.smoke:
        cfg = get_smoke(args.arch)
    elif args.variant:
        cfg = get_variant(args.arch, args.variant)
    else:
        cfg = get_config(args.arch, linearize=args.linearize)

    run = RunConfig(num_microbatches=args.microbatches,
                    learning_rate=args.lr, total_steps=args.steps,
                    warmup_steps=max(args.steps // 20, 5),
                    remat=args.remat, seed=args.seed,
                    grad_compression=args.grad_compression,
                    comm_strategy=args.comm_strategy,
                    comm_overlap=args.comm_overlap,
                    comm_dtype=args.comm_dtype,
                    kernel_backend=args.kernel_backend,
                    zero1=not args.no_zero1,
                    dp_degree=args.dp_degree, sp_degree=args.sp_degree,
                    tp_degree=args.tp_degree,
                    guard=args.guard,
                    guard_max_consecutive_skips=args.guard_max_skips,
                    ckpt_verify=args.ckpt_verify)
    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch,
                       seed=args.seed)
    plan = None
    if run.dp_degree or run.sp_degree or run.tp_degree:
        # DP×SP(×TP) training mesh (the paper's deployment shape plus
        # the optional ulysses head-parallel axis): batch over "data" ×
        # sequence over "sequence" (× "model"), ZeRO-1 optimizer state.
        from repro.launch.mesh import make_training_mesh
        # whichever degree is unset is inferred from the device count
        n_dev = len(jax.devices())
        tp = max(run.tp_degree, 1)
        dp = run.dp_degree or max(n_dev // (max(run.sp_degree, 1) * tp), 1)
        sp = run.sp_degree or max(n_dev // (dp * tp), 1)
        mesh = make_training_mesh(dp, sp, tp)
        mb = args.batch // args.microbatches
        if mb % dp or args.seq % max(sp * tp, 1):
            raise SystemExit(
                f"--batch/microbatches ({mb}) must divide by dp ({dp}) "
                f"and --seq ({args.seq}) by sp×tp ({sp}×{tp})")
        plan = make_plan(mesh, "train", global_batch=args.batch,
                         n_kv_heads=cfg.n_kv_heads, n_heads=cfg.n_heads,
                         backend=run.kernel_backend,
                         comm=run.comm_spec(), zero1=run.zero1)
    elif args.multi_device and len(jax.devices()) > 1:
        from repro.launch.mesh import DATA_AXIS
        mesh = jax.make_mesh((len(jax.devices()),), (DATA_AXIS,),
                             axis_types=(jax.sharding.AxisType.Auto,))
        plan = make_plan(mesh, "train", global_batch=args.batch,
                         n_kv_heads=cfg.n_kv_heads,
                         backend=run.kernel_backend,
                         comm=run.comm_spec())
    sink = None
    if args.metrics_out:
        from repro.obs import JsonlSink
        sink = JsonlSink(args.metrics_out)
    try:
        state, history = train(cfg, run, data, plan=plan,
                               ckpt_dir=args.ckpt_dir,
                               ckpt_every=args.ckpt_every, sink=sink)
    finally:
        if sink is not None:
            sink.close()
            print(f"[train] telemetry -> {args.metrics_out}")
    first = sum(h["loss"] for h in history[:10]) / max(len(history[:10]), 1)
    last = sum(h["loss"] for h in history[-10:]) / max(len(history[-10:]), 1)
    print(f"[train] {cfg.name}: loss {first:.4f} -> {last:.4f} over "
          f"{len(history)} steps "
          f"({'improved' if last < first else 'NOT improved'})")


if __name__ == "__main__":
    main()
