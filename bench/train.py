"""Training driver: one cell's set-up, timed window and check.

Set-up builds the train state on the device from the seed in one jitted
call, compiles the program's train step (``repro.train.step``) on the plan
of ``repro.sharding.rules.make_plan`` for the traffic's ``layout`` (dp ×
sp over the cell's chips, see :func:`layout`), and drives that one
compiled step through the cell's first ``CHECK_STEPS`` steps, which the
reference follows. The window then drives the same step on fresh rows for
``seconds``, with one step in flight while the host makes the next
batch. After the window the program's state is freed and the reference
runs its own steps on the same rows.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation as span
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import correct, loadgen
from bench.model import model_config, run_config
from bench.reference import train as ref_train
from bench.weights import _make, flat_from_program, make_weights, \
    program_tree, seed_key

CHECK_STEPS = 3


def _feed(t, seed, step, vocab, sharding=None):
    b = loadgen.train_batch(t, seed, step, vocab)
    if sharding is None:
        return {k: jnp.asarray(b[k]) for k in ("tokens", "labels", "resets")}
    return {k: jax.device_put(b[k], sharding)
            for k in ("tokens", "labels", "resets")}


def _one_row(t):
    if t["rows"] != 1 or t["layout"].get("dp", 1) != 1:
        raise NotImplementedError(
            "the reference takes one row a step, so a layout over more "
            "than one row (dp > 1) is not checked yet")


def _ref_batches(t, seed, vocab):
    _one_row(t)
    out = []
    for i in range(CHECK_STEPS):
        b = loadgen.train_batch(t, seed, i, vocab)
        out.append({k: jnp.asarray(b[k][0, 0])
                    for k in ("tokens", "labels", "seg")})
    return out


def layout(ctx):
    """The plan of the cell's training layout, and for a layout over
    several chips the shardings of the state and of a batch.

    1 × 1 is one chip and the program's local plan. Otherwise the chips
    form the paper's (data, sequence) mesh and the program's manual DP×SP
    step runs over it: the state, which that plan replicates, is built in
    place on every chip, and each batch is split over (data, sequence)."""
    from repro.launch.mesh import DATA_AXIS, SEQ_AXIS, make_training_mesh
    from repro.sharding.rules import make_plan

    c, lay = ctx.config, ctx.traffic["layout"]
    dp, sp = lay.get("dp", 1), lay.get("sp", 1)
    if dp * sp != ctx.chips:
        raise ValueError(f"layout dp {dp} x sp {sp} does not fill the "
                         f"cell's {ctx.chips} chips")
    _one_row(ctx.traffic)
    if (dp, sp) == (1, 1):
        return make_plan(None, "train"), None, None
    mesh = make_training_mesh(dp, sp, devices=jax.devices()[:ctx.chips])
    plan = make_plan(mesh, "train", n_heads=c["num_attention_heads"],
                     n_kv_heads=c["num_key_value_heads"])
    if plan.zero1_axis is not None:
        raise ValueError("a sharded optimizer state is not built here")
    return (plan, NamedSharding(mesh, P()),
            NamedSharding(mesh, P(None, DATA_AXIS, SEQ_AXIS)))


def setup(ctx):
    from repro.optim import adamw
    from repro.train import step as program_step

    c, t = ctx.config, ctx.traffic
    cfg = model_config(c)
    run = run_config(c, t["layout"])
    plan, replicated, batch_sharding = layout(ctx)
    b1 = c["optimizer"]["b1"]

    def build(key):
        params = program_tree(_make(key, c, jnp.float32), c)
        return {"params": params, "opt": adamw.init(params),
                "step": jnp.zeros((), jnp.int32)}

    if replicated is None:
        state = jax.jit(build)(seed_key(ctx.seed))
    else:
        state = jax.jit(build, out_shardings=replicated)(seed_key(ctx.seed))
    step = jax.jit(program_step.make_train_step(cfg, run, plan),
                   donate_argnums=(0,))
    grad_norms = jax.jit(lambda m: ref_train.leaf_norms(
        flat_from_program(m, c), 1.0 / (1.0 - b1)))
    change_norms = jax.jit(lambda p, w0: ref_train.leaf_norms(
        {n: x - w0[n] for n, x in flat_from_program(p, c).items()}))

    prog = {"losses": []}
    for i in range(CHECK_STEPS):
        state, metrics = step(state, _feed(t, ctx.seed, i, c["vocab_size"],
                                           batch_sharding))
        prog["losses"].append(float(metrics["loss"]))
        if i == 0:
            # Adam's first moment after one step is (1 - b1) · g, the
            # clipped gradient the optimizer got
            prog["grad"] = {n: float(x) for n, x in
                            grad_norms(state["opt"].m).items()}
    w0 = make_weights(ctx.seed, c, jnp.float32, out_shardings=replicated)
    prog["change"] = {n: float(x) for n, x in
                      change_norms(state["params"], w0).items()}
    del w0
    return {"state": state, "step": step, "prog": prog, "next": CHECK_STEPS,
            "batch_sharding": batch_sharding}


def window(ctx, s):
    c, t = ctx.config, ctx.traffic
    state, step = s["state"], s["step"]
    i, n, skipped = s["next"], 0, 0
    pending = None
    t0 = time.perf_counter()
    with span("window"):
        while True:
            with span("data"):
                batch = _feed(t, ctx.seed, i, c["vocab_size"],
                              s.get("batch_sharding"))
            with span("step"):
                state, metrics = step(state, batch)
            i, n = i + 1, n + 1
            if pending is not None:
                with span("fence"):
                    skipped += int(float(pending))
            pending = metrics["skipped"]
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        with span("fence"):
            skipped += int(float(pending))
    window_s = time.perf_counter() - t0
    s["state"] = state
    tokens = n * t["rows"] * t["seq_len"]
    return {"window_s": window_s, "steps": n, "tokens": tokens,
            "attempted": n, "failed": skipped}


def end_to_end(ctx, w):
    return {"train_tokens_per_s": w["tokens"] / w["window_s"]}


def check(ctx, s, w):
    """Free the program's state, run the reference, read the gaps."""
    c, t = ctx.config, ctx.traffic
    prog = s.pop("prog")
    s.clear()
    batches = _ref_batches(t, ctx.seed, c["vocab_size"])
    t0 = time.perf_counter()
    losses, grad, change = ref_train.run(
        functools.partial(make_weights, ctx.seed, c, jnp.float32),
        batches, c)
    ref = {"losses": losses, "grad": grad, "change": change}
    return correct.train_readings(prog, ref), {
        "program": prog, "reference": ref,
        "reference_s": time.perf_counter() - t0}


def kernel_shapes(ctx):
    c, t = ctx.config, ctx.traffic
    s = t["seq_len"] // t["layout"].get("sp", 1)
    return {"lasp2_chunk": dict(bh=t["rows"] * c["num_attention_heads"],
                                s=s, dk=c["head_dim"], dv=c["head_dim"],
                                block=c["linear_attention"]["block_size"],
                                in_bytes=2)}
