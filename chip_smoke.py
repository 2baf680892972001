"""Bring-up smoke test of the main path on a TPU.

    python chip_smoke.py              # one chip: kernels, train, serve
    python chip_smoke.py --chips 4    # four chips: DP×SP train step only

Everything runs in this one process through the entry points the CLIs
use, at the published widths of Linear-Llama3-1B (d_model 2048, 16
heads of 128, d_ff 5504, vocab 128256) with random weights from a seed.
Phases (one chip):

* kernels — every Pallas kernel (chunk fwd/bwd, decode, flash fwd/bwd)
  at those widths, compared with the XLA path of ``repro.kernels.ops``
  on the same inputs;
* train   — ``repro.train.loop.train`` on the default kernel backend
  (Pallas on TPU) with depth cut to 4 layers (a 16-layer step does not
  fit one chip's HBM), 1 × 4096 tokens, 5 steps; step-0 loss against the
  XLA path on the same params and batch;
* serve   — ``ServeEngine`` holding the full 16-layer model and then the
  1/4 hybrid, ragged requests on fewer slots than requests, greedy
  tokens against the XLA-path engine.

The compiled train, prefill and decode programs are checked for a
``tpu_custom_call`` per Pallas kernel they dispatch. ``--chips 4`` runs
the manual DP×SP train step (dp=1, sp=4) once per SP exchange strategy,
``allgather`` (LASP-2) against ``ring`` (LASP-1), and checks the
collective budget and the placement on every chip.

The last line of standard output is one JSON object with ``"ok": true``
and the device as JAX reports it. Any failure exits non-zero without
that line; without a TPU the script fails before any phase.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "linear-llama3-1b"
TRAIN_LAYERS = 4          # a 16-layer fp32 train step needs 20.6 GB of HBM
SEQ = 4096
TRAIN_STEPS = 5
SEED = 0

# Relative L2 error of a Pallas kernel's outputs and gradients (bf16
# inputs, fp32 math) against the XLA path on the same values in fp32 at
# highest matmul precision; bf16 outputs alone carry ~4e-3.
KERNEL_TOL = 2e-2
# |loss_pallas - loss_xla| / loss_xla for the step-0 loss.
LOSS_RTOL = 2e-3
# allgather vs ring (same math, different exchange and combine order).
STRATEGY_LOSS_RTOL = 1e-3
STRATEGY_GNORM_RTOL = 1e-2


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def expect_kernels(hlo_text: str, names, what: str) -> None:
    from repro.launch.hlo_analysis import tpu_kernels
    found = tpu_kernels(hlo_text)
    missing = [n for n in names if n not in found]
    check(not missing, f"{what}: compiled HLO lacks tpu_custom_call for "
                       f"{missing} (found {sorted(found)})")
    log(f"[hlo] {what}: tpu_custom_call {sorted(found)}")


def memory_stat(device, key: str) -> int:
    return device.memory_stats()[key]


def require_tpu(n_chips: int):
    """The device list, or a failure: this script never runs on the CPU."""
    import jax
    from repro.launch.hlo_analysis import DEVICE_PEAKS
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX sees {devices[0].platform} devices")
    check(len(devices) >= n_chips,
          f"{n_chips} chips needed, JAX sees {len(devices)}")
    kind = devices[0].device_kind
    check(kind in DEVICE_PEAKS,
          f"device kind {kind!r} has no entry in the peaks table "
          f"(repro.launch.hlo_analysis.DEVICE_PEAKS)")
    return devices


def train_config():
    from repro.configs import get_config
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    log(f"[config] {cfg.name}: widths as published (d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads x {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}); depth cut {full.n_layers} -> {cfg.n_layers} "
        f"layers for training")
    return cfg


# ---------------------------------------------------------------------------
# Phase 1: kernels at Linear-Llama3 widths vs the XLA path.
# ---------------------------------------------------------------------------

def phase_kernels():
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    check(ops.default_backend() == "pallas",
          f"default kernel backend is {ops.default_backend()!r}, not pallas")
    b, h, dh = 1, 16, 128
    ks = jax.random.split(jax.random.PRNGKey(SEED), 8)
    bf16 = jnp.bfloat16
    q = (jax.random.normal(ks[0], (b, h, SEQ, dh)) * 0.3).astype(bf16)
    k = (jax.random.normal(ks[1], (b, h, SEQ, dh)) * 0.3).astype(bf16)
    v = (jax.random.normal(ks[2], (b, h, SEQ, dh)) * 0.5).astype(bf16)
    la = -jnp.abs(jax.random.normal(ks[3], (b, h, SEQ))) * 0.01
    co = jax.random.normal(ks[4], (b, h, SEQ, dh))
    cs = jax.random.normal(ks[5], (b, h, dh, dh))
    cl = jax.random.normal(ks[6], (b, h))

    def f32(*xs):
        return [x.astype(jnp.float32) for x in xs]

    def compare(name, got, ref):
        for i, (g, r) in enumerate(zip(jax.tree.leaves(got),
                                       jax.tree.leaves(ref))):
            e = rel_err(g, r)
            log(f"[kernels] {name}[{i}] {tuple(g.shape)}: rel_err {e:.3e} "
                f"(tol {KERNEL_TOL})")
            check(e <= KERNEL_TOL, f"{name}[{i}] rel_err {e} > {KERNEL_TOL}")

    def run(name, fn, args, ref_args, kernels):
        compiled = jax.jit(lambda *a: fn(None, *a)).lower(*args).compile()
        expect_kernels(compiled.as_text(), kernels, f"kernel {name}")
        got = compiled(*args)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *a: fn("xla", *a))(*ref_args)
        compare(name, got, ref)

    # chunked linear attention, forward and gradients (both backward passes)
    def chunk_fwd(backend, q_, k_, v_, la_):
        return ops.linear_attention_op(q_, k_, v_, la_, backend=backend)

    def chunk_grad(backend, q_, k_, v_, la_):
        def loss(*a):
            o, st, ld = ops.linear_attention_op(*a, backend=backend)
            return (jnp.sum(o.astype(jnp.float32) * co) + jnp.sum(st * cs)
                    + jnp.sum(ld * cl))
        return jax.grad(loss, argnums=(0, 1, 2, 3))(q_, k_, v_, la_)

    run("lasp2_chunk fwd", chunk_fwd, (q, k, v, la), (*f32(q, k, v), la),
        ["lasp2_chunk_fwd"])
    run("lasp2_chunk grad", chunk_grad, (q, k, v, la), (*f32(q, k, v), la),
        ["lasp2_chunk_fwd", "lasp2_chunk_bwd_dq", "lasp2_chunk_bwd_dkv"])

    # single-token decode on a 4-slot batch
    nb = 4
    dq_, dk_, dv_ = (x[0, :, :nb].transpose(1, 0, 2) for x in (q, k, v))
    dla = la[0, :, :nb].T
    st0 = jax.random.normal(ks[7], (nb, h, dh, dh)) * 0.1
    ld0 = jnp.zeros((nb, h), jnp.float32)

    def decode(backend, q_, k_, v_, la_, st_, ld_):
        return ops.linear_decode_op(q_, k_, v_, la_, st_, ld_,
                                    backend=backend)

    run("lasp2_decode", decode, (dq_, dk_, dv_, dla, st0, ld0),
        (*f32(dq_, dk_, dv_), dla, st0, ld0), ["lasp2_decode_step"])

    # flash attention with the hybrid's sliding window, fwd and gradients
    def flash_fwd(backend, q_, k_, v_):
        return ops.flash_attention_op(q_, k_, v_, causal=True,
                                      sliding_window=2048, backend=backend)

    def flash_grad(backend, q_, k_, v_):
        def loss(*a):
            o = ops.flash_attention_op(*a, causal=True, sliding_window=2048,
                                       backend=backend)
            return jnp.sum(o.astype(jnp.float32) * co)
        return jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)

    run("flash_attention fwd", flash_fwd, (q, k, v), f32(q, k, v),
        ["flash_attention_fwd"])
    run("flash_attention grad", flash_grad, (q, k, v), f32(q, k, v),
        ["flash_attention_fwd", "flash_attention_bwd_dq",
         "flash_attention_bwd_dkv"])


# ---------------------------------------------------------------------------
# Phase 2: training on one chip.
# ---------------------------------------------------------------------------

def phase_train(device):
    import jax
    import numpy as np

    from repro.configs.base import RunConfig
    from repro.data.pipeline import SyntheticLM
    from repro.models import model as M
    from repro.sharding.rules import local_plan
    from repro.train.loop import train
    from repro.train.step import init_state, make_loss_fn, make_train_step

    cfg = train_config()
    run = RunConfig(seed=SEED, total_steps=TRAIN_STEPS, warmup_steps=2,
                    remat="full")
    data = SyntheticLM(cfg.vocab_size, SEQ, 1, seed=SEED)
    batch0 = data.microbatched(0, run.num_microbatches)

    # XLA-path step-0 loss: the same params and batch through the loss the
    # train step differentiates.
    params = M.init_params(jax.random.PRNGKey(run.seed), cfg)
    micro0 = jax.tree.map(lambda x: x[0], batch0)
    _, loss_xla = jax.jit(make_loss_fn(cfg, run, local_plan("xla")))(
        params, micro0)
    loss_xla = float(loss_xla)
    del params

    # The compiled train step holds every kernel it dispatches.
    state_shapes = jax.eval_shape(
        lambda key: init_state(key, cfg, run, local_plan()),
        jax.random.PRNGKey(run.seed))
    step = jax.jit(make_train_step(cfg, run, local_plan()),
                   donate_argnums=(0,))
    expect_kernels(step.lower(state_shapes, batch0).compile().as_text(),
                   ["lasp2_chunk_fwd", "lasp2_chunk_bwd_dq",
                    "lasp2_chunk_bwd_dkv"], "train step")

    t0 = time.perf_counter()
    state, hist = train(cfg, run, data, max_steps=TRAIN_STEPS, log_every=1,
                        log_fn=lambda m: log(f"[train] {m}"))
    jax.block_until_ready(state)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in hist]
    check(len(losses) == TRAIN_STEPS, f"ran {len(losses)} steps")
    check(all(np.isfinite(losses)), f"non-finite losses {losses}")
    err = abs(losses[0] - loss_xla) / abs(loss_xla)
    log(f"[train] step-0 loss pallas {losses[0]:.6f} xla {loss_xla:.6f} "
        f"rel_err {err:.3e} (tol {LOSS_RTOL})")
    check(err <= LOSS_RTOL, f"step-0 loss rel_err {err} > {LOSS_RTOL}")
    warm = [h["dt"] for h in hist[1:]]
    peak = memory_stat(device, "peak_bytes_in_use")
    log(f"[train] losses {losses}")
    log(f"[train] step time after warm-up: median "
        f"{float(np.median(warm)):.4f} s over {len(warm)} steps "
        f"(each fenced by reading its metrics); first step incl. compile "
        f"{hist[0]['dt']:.1f} s; train() wall {wall:.1f} s")
    log(f"[train] peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB)")
    del state


# ---------------------------------------------------------------------------
# Phase 3: serving the full-depth model and the 1/4 hybrid.
# ---------------------------------------------------------------------------

def serve_once(cfg, params, plan, prompts, new_tokens, slots):
    from repro.serve.engine import ServeEngine
    max_len = max(len(p) for p in prompts) + new_tokens
    engine = ServeEngine(cfg, params, plan=plan, max_len=max_len,
                         max_batch=slots)
    uids = [engine.submit(p, new_tokens, seed=SEED, stream=i)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    results = engine.run()
    wall = time.perf_counter() - t0
    return engine, [results[u] for u in uids], wall


def phase_serve(variant: str, lengths, prefill_shape, prefill_kernels):
    """``lengths``: one prompt length per request. ``prefill_shape``: one
    (rows, length) prefill batch the scheduler forms from them, whose
    compiled program is checked for ``prefill_kernels``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_variant
    from repro.models import model as M
    from repro.sharding.rules import local_plan

    cfg = get_variant(ARCH, variant)
    new_tokens, slots = 32, 4
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in lengths]
    params = M.init_params(jax.random.PRNGKey(SEED), cfg)
    log(f"[serve:{variant}] {cfg.name}: {cfg.n_layers} layers, "
        f"{len(prompts)} requests (prompts {min(map(len, prompts))}.."
        f"{max(map(len, prompts))}) on {slots} slots, {new_tokens} new "
        f"tokens each")

    engine, toks, wall = serve_once(cfg, params, None, prompts, new_tokens,
                                    slots)
    stats = engine.stats()
    log(f"[serve:{variant}] pallas: {wall:.1f} s incl. compile; decode "
        f"step p50 {stats.get('decode_step_s_p50', float('nan')):.4f} s")
    for t in toks:
        check(len(t) == new_tokens, f"request produced {len(t)} tokens")

    # The engine's compiled programs hold their kernels.
    prefill_args = (engine.params, jnp.zeros(prefill_shape, jnp.int32))
    if engine.bucket_lengths:
        prefill_fn = engine._prefill
        prefill_args += (jnp.zeros(prefill_shape[:1], jnp.int32),)
    else:
        prefill_fn = engine._prefill_exact
    expect_kernels(prefill_fn.lower(*prefill_args).compile().as_text(),
                   prefill_kernels, f"{variant} prefill")
    expect_kernels(engine._decode.lower(
        engine.params, jnp.zeros((slots,), jnp.int32),
        engine._cache).compile().as_text(),
        ["lasp2_decode_step"], f"{variant} decode step")
    del engine

    _, ref, wall_x = serve_once(cfg, params, local_plan("xla"), prompts,
                                new_tokens, slots)
    agree = [int(np.argmin(np.append(a == b, False))) for a, b in
             zip(toks, ref)]
    log(f"[serve:{variant}] xla: {wall_x:.1f} s; greedy tokens agreeing "
        f"with the xla path before the first difference, per request: "
        f"{agree} of {new_tokens}")
    log(f"[serve:{variant}] first request, first 8 tokens: pallas "
        f"{toks[0][:8].tolist()} xla {ref[0][:8].tolist()}")
    check(agree[0] >= 1, "first greedy token of the first request differs "
                         "from the xla path")


# ---------------------------------------------------------------------------
# Four chips: the manual DP×SP train step, allgather against ring.
# ---------------------------------------------------------------------------

def phase_sp4(devices):
    import jax
    import numpy as np
    from repro.comm import CommSpec
    from repro.comm.budget import check_axis_budget, train_step_axis_budget
    from repro.configs.base import RunConfig
    from repro.data.pipeline import SyntheticLM
    from repro.launch.hlo_analysis import collective_axis_counts
    from repro.launch.mesh import make_training_mesh
    from repro.sharding.rules import make_plan
    from repro.train.step import init_state, make_train_step

    cfg = train_config()
    dp, sp, rows = 1, 4, 4
    mesh = make_training_mesh(dp, sp, devices=devices[:dp * sp])
    run = RunConfig(seed=SEED, total_steps=TRAIN_STEPS, warmup_steps=2,
                    remat="full", scan_unroll=True)
    data = SyntheticLM(cfg.vocab_size, SEQ, rows, seed=SEED)
    batch = data.microbatched(0, run.num_microbatches)
    log(f"[sp4] mesh dp={dp} x sp={sp}; batch {rows} x {SEQ} tokens "
        f"({rows * SEQ // sp} tokens per chip)")

    out = {}
    for strategy in ("allgather", "ring"):
        plan = make_plan(mesh, "train", global_batch=rows,
                         n_kv_heads=cfg.n_kv_heads, n_heads=cfg.n_heads,
                         comm=CommSpec(strategy=strategy))
        state = init_state(jax.random.PRNGKey(run.seed), cfg, run, plan)
        t0 = time.perf_counter()
        compiled = jax.jit(make_train_step(cfg, run, plan),
                           donate_argnums=(0,)).lower(state, batch).compile()
        hlo = compiled.as_text()
        log(f"[sp4:{strategy}] compiled in {time.perf_counter() - t0:.1f} s")
        expect_kernels(hlo, ["lasp2_chunk_fwd", "lasp2_chunk_bwd_dq",
                             "lasp2_chunk_bwd_dkv"], f"sp4 {strategy} step")
        counts = collective_axis_counts(hlo, mesh)
        log(f"[sp4:{strategy}] collectives by (op, axes): "
            f"{dict(sorted(counts.items()))}")
        if strategy == "allgather":
            budget = train_step_axis_budget(
                mesh, n_sp_layers=cfg.n_layers, backward="autodiff",
                zero1=plan.zero1_axis is not None, remat=run.remat)
            bad = check_axis_budget(hlo, mesh, budget)
            check(not bad, "allgather step off its collective budget: "
                           + "; ".join(bad))
            log(f"[sp4:allgather] budget holds: 1 forward all-gather per "
                f"linear layer ({cfg.n_layers} layers; remat={run.remat}) "
                f"({budget.note})")
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        metrics = {k: float(v) for k, v in metrics.items()}
        dt = time.perf_counter() - t0
        for leaf in jax.tree.leaves(state["params"]):
            on = {s.device for s in leaf.addressable_shards}
            check(on == set(mesh.devices.flat),
                  f"a param lives on {len(on)} of {mesh.size} chips")
        in_use = [memory_stat(d, "bytes_in_use") for d in mesh.devices.flat]
        check(min(in_use) > 2 ** 30,
              f"a chip holds under 1 GiB: bytes_in_use {in_use}")
        log(f"[sp4:{strategy}] step 0: loss {metrics['loss']:.6f} grad_norm "
            f"{metrics['grad_norm']:.6f} ({dt:.2f} s); bytes_in_use per "
            f"chip {in_use}")
        check(np.isfinite(metrics["loss"]) and
              np.isfinite(metrics["grad_norm"]), f"non-finite {metrics}")
        out[strategy] = metrics
        del state

    a, r = out["allgather"], out["ring"]
    el = abs(a["loss"] - r["loss"]) / abs(r["loss"])
    eg = abs(a["grad_norm"] - r["grad_norm"]) / abs(r["grad_norm"])
    log(f"[sp4] allgather vs ring: loss rel_err {el:.3e} (tol "
        f"{STRATEGY_LOSS_RTOL}), grad_norm rel_err {eg:.3e} (tol "
        f"{STRATEGY_GNORM_RTOL})")
    check(el <= STRATEGY_LOSS_RTOL, f"loss rel_err {el}")
    check(eg <= STRATEGY_GNORM_RTOL, f"grad_norm rel_err {eg}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernels, train and serve on one chip; 4: the "
                         "DP×SP train step on four chips, and nothing else")
    args = ap.parse_args()
    try:
        from repro.launch.compile_cache import enable_compile_cache
        devices = require_tpu(args.chips)
        log(f"[device] {devices[0].device_kind} x {len(devices)}; compile "
            f"cache {enable_compile_cache()}")
        t0 = time.perf_counter()
        if args.chips == 4:
            phase_sp4(devices)
        else:
            phase_kernels()
            phase_train(devices[0])
            # 8 ragged prompts in one length bucket: two (4, 512) prefills
            phase_serve("CONFIG", [300, 420, 512, 480, 260, 380, 500, 330],
                        (4, 512), ["lasp2_chunk_fwd"])
            # exact-length groups (hybrids are not pad-safe): (2, 384) and
            # (2, 256) prefills
            phase_serve("HYBRID", [384, 384, 256, 256], (2, 384),
                        ["lasp2_chunk_fwd", "flash_attention_fwd"])
        log(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s")
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
