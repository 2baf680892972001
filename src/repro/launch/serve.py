"""Serving launcher: load/initialize a model and serve batched requests
through the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch hymba-1.5b --smoke \
      --requests 8 --prompt-len 64 --new-tokens 32
"""

from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="linear-llama3-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--linearize", type=int, default=None)
    ap.add_argument("--requests", type=int, default=8,
                    help="number of requests to submit")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots (continuous-batching grid)")
    ap.add_argument("--prompt-len", type=int, default=64,
                    help="max prompt length (ragged, varied per request)")
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound the admission queue; submissions beyond "
                         "this many waiting requests are rejected with "
                         "backpressure (0 = unbounded; "
                         "docs/resilience.md)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request deadline: unfinished requests are "
                         "evicted (finish_reason=deadline, partial "
                         "tokens kept) this many seconds after submit "
                         "(0 = none)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--metrics-out", default=None,
                    help="write serve telemetry (per-request records + "
                         "summary with TTFT / decode-latency percentiles) "
                         "as JSONL here (docs/observability.md)")
    args = ap.parse_args()

    import jax
    import numpy as np

    from repro.checkpoint.manager import CheckpointManager
    from repro.configs import get_config, get_smoke
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke \
        else get_config(args.arch, linearize=args.linearize)
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        step = mgr.latest_step()
        if step is not None:
            state = mgr.restore(step, {"params": params})
            params = state["params"]
            print(f"[serve] restored params from step {step}")

    sink = None
    if args.metrics_out:
        from repro.obs import JsonlSink
        sink = JsonlSink(args.metrics_out)

    max_len = args.prompt_len + args.new_tokens
    engine = ServeEngine(cfg, params, max_len=max_len,
                         max_batch=args.max_batch, sink=sink,
                         max_queue=args.max_queue or None)

    if cfg.encoder is not None or cfg.n_image_tokens:
        # encoder / image-conditioned models run the static-batch path
        kw = {}
        if cfg.encoder is not None:
            kw["enc_frames"] = jax.random.normal(
                key, (args.max_batch, cfg.encoder.n_frames,
                      cfg.d_model)) * 0.1
        if cfg.n_image_tokens:
            kw["img_emb"] = jax.random.normal(
                key, (args.max_batch, cfg.n_image_tokens, cfg.d_model)) * 0.1
        prompts = jax.random.randint(
            key, (args.max_batch, args.prompt_len), 0, cfg.vocab_size)
        t0 = time.perf_counter()
        out = engine.generate(prompts, args.new_tokens,
                              temperature=args.temperature, **kw)
        dt = time.perf_counter() - t0
        total_new = out.shape[0] * args.new_tokens
        print(f"[serve] {cfg.name}: static batch {out.shape} in {dt:.2f}s "
              f"({total_new / dt:.1f} tok/s incl. prefill+compile)")
        return

    # continuous batching: ragged prompts, more requests than slots
    rng = np.random.default_rng(0)
    lens = rng.integers(max(args.prompt_len // 2, 1), args.prompt_len + 1,
                        size=args.requests)
    from repro.serve.scheduler import QueueFullError
    uids = []
    rejected = 0
    for i, ln in enumerate(lens):
        prompt = rng.integers(0, cfg.vocab_size, size=int(ln))
        try:
            uids.append(engine.submit(
                prompt, args.new_tokens, temperature=args.temperature,
                seed=0, stream=i,
                deadline_s=args.deadline_s or None))
        except QueueFullError:
            rejected += 1
    if rejected:
        print(f"[serve] queue full: rejected {rejected}/{args.requests} "
              f"requests (--max-queue {args.max_queue})")
    t0 = time.perf_counter()
    results = engine.run()
    dt = time.perf_counter() - t0
    total_new = sum(len(v) for v in results.values())
    stats = engine.cache_stats()
    print(f"[serve] {cfg.name}: {len(results)} requests "
          f"(prompts {lens.min()}..{lens.max()}) on {args.max_batch} slots "
          f"in {dt:.2f}s ({total_new / dt:.1f} tok/s incl. prefill+compile)")
    print(f"[serve] cache bytes: linear_state={stats['linear_state']} "
          f"kv_ring={stats['kv_ring']} conv={stats['conv']} "
          f"total={stats['total']}")
    s = engine.stats()
    if "ttft_s_p50" in s:
        print(f"[serve] ttft p50 {s['ttft_s_p50']*1e3:.1f}ms "
              f"p99 {s['ttft_s_p99']*1e3:.1f}ms; decode p50 "
              f"{s.get('decode_step_s_p50', 0)*1e3:.1f}ms p99 "
              f"{s.get('decode_step_s_p99', 0)*1e3:.1f}ms; "
              f"queue_depth peak {s.get('queue_depth_peak', 0):.0f}; "
              f"{s.get('decode_tokens_per_s', 0):.1f} decode tok/s")
    if sink is not None:
        engine.emit_summary(requests=len(results))
        sink.close()
        print(f"[serve] telemetry -> {args.metrics_out}")
    if uids and uids[0] in results:
        print("[serve] first result:", results[uids[0]][:16], "...")


if __name__ == "__main__":
    main()
