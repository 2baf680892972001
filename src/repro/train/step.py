"""Train-step factory: grad accumulation (scan), AdamW, clipping, skip-on-
non-finite, optional cross-pod int8 gradient compression.

``train_step(state, batch)``:
  state = {"params", "opt": AdamState | Zero1AdamState, "step", ["err"]}
  batch = {"tokens"/"labels"/"resets": (A, B/A, S), [frames|img]: (A, ...)}
Returns (new_state, metrics). Designed for jit with donated state.

Two step flavours, selected by the plan:

* **GSPMD step** (the default): plain jit — XLA places the collectives
  from the plan's sharding constraints.
* **Manual DP×SP(×TP) step** (``plan.manual_axes``, docs/parallelism.md):
  the whole step runs inside ONE fully-manual shard_map over the
  ``(data, sequence)`` mesh — or ``(data, sequence, model)`` on 3D
  plans, where tokens shard over the combined (sequence, model) width —
  so every collective on the wire is explicit and HLO-countable
  (``repro.comm.budget.train_step_axis_budget``):

    - per LASP-2 layer: the strategy's state exchange over the
      sequence-carrying axes only (1 forward all-gather for
      "allgather"); hybrid layers under "ulysses" add the head-parallel
      All-to-All pair over ``model``,
    - per step: exactly ONE gradient reduction touching ``data`` — all
      microbatch-accumulated gradients plus the loss/token counters are
      raveled into a single fp32 vector and psum'd across the mesh,
    - ZeRO-1 (``plan.zero1_axis``): each rank Adam-updates its
      1/zero_deg flat parameter slice and ONE all-gather over the zero
      axes re-assembles the params (the all-gather-on-update path).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.compat import shard_map as _shard_map

from repro.comm import primitives as comm_primitives
from repro.configs.base import ModelConfig, RunConfig
from repro.launch.mesh import POD_AXIS
from repro.models import model as M
from repro.obs.scopes import scope
from repro.optim import adamw
from repro.optim.compression import compress_sync_tree
from repro.resilience import guard as health
from repro.sharding.rules import Parallelism, _axis_size

MOE_AUX_COEF = 0.01


def init_state(key, cfg: ModelConfig, run: RunConfig,
               plan: Optional[Parallelism] = None):
    """The train state. On a manual (DP×SP) plan it is built in place on
    every device of the mesh: an eager init lands on one device, where
    its replicated copy would double that device's share (8.7 GB for a
    4-layer full-width Linear-Llama3 state)."""
    if plan is not None and plan.manual_axes:
        def init(key_):
            return _init_state(key_, cfg, run, plan)
        shardings = jax.tree.map(
            lambda s: NamedSharding(plan.mesh, s),
            _state_specs(jax.eval_shape(init, key), plan))
        return jax.jit(init, out_shardings=shardings)(key)
    return _init_state(key, cfg, run, plan)


def _init_state(key, cfg: ModelConfig, run: RunConfig,
                plan: Optional[Parallelism]):
    params = M.init_params(key, cfg)
    if run.bf16_params:
        # §Perf: bf16 weight storage — halves FSDP gather traffic and
        # removes per-use f32→bf16 converts; Adam moments stay fp32 (the
        # usual production mixed-precision recipe).
        params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if (x.dtype == jnp.float32 and x.ndim >= 2) else x, params)
    if plan is not None and plan.zero1_axis is not None:
        opt = adamw.zero1_init(params, _axis_size(plan.mesh,
                                                  plan.zero1_axis))
    else:
        opt = adamw.init(params)
    state = {"params": params, "opt": opt,
             "step": jnp.zeros((), jnp.int32)}
    if run.guard:
        state["guard"] = health.guard_init(run.guard_window)
    if run.grad_compression:
        from repro.optim.compression import init_error_buffer
        state["err"] = init_error_buffer(params)
    return state


def make_loss_fn(cfg: ModelConfig, run: RunConfig, plan: Parallelism):
    def loss_fn(params, micro):
        kwargs = {}
        if "frames" in micro:
            kwargs["enc_frames"] = micro["frames"]
        if "img" in micro:
            kwargs["img_emb"] = micro["img"]
        logits, aux = M.forward(params, micro["tokens"], cfg, plan,
                                remat=run.remat, unroll=run.scan_unroll,
                                resets=micro.get("resets"), **kwargs)
        with scope("loss"):
            loss = M.lm_loss(logits, micro["labels"])
        return loss + MOE_AUX_COEF * aux, loss
    return loss_fn


def _accum_grads(loss_fn, params, batch, unroll=False, plan=None):
    """Scan over the leading microbatch dim, averaging grads in fp32.

    §Perf: the fp32 accumulators are CONSTRAINED to the parameter sharding
    (FSDP over "data", TP over "model"). Without this, XLA keeps the
    accumulator replicated and moves the FULL fp32 gradient per microbatch
    (measured as 14.9 GiB/layer of f32 all-gathers on qwen110b×train_4k);
    with it, each microbatch contributes a reduce-scatter into the shard —
    the ZeRO-2 gradient flow."""
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def constrain(tree):
        if plan is None or plan.mesh is None:
            return tree
        from jax.sharding import NamedSharding
        from repro.sharding.rules import param_specs
        specs = param_specs(tree, plan)
        return jax.tree.map(
            lambda x, sp: jax.lax.with_sharding_constraint(
                x, NamedSharding(plan.mesh, sp)),
            tree, specs, is_leaf=lambda x: hasattr(x, "shape"))

    def body(acc, micro):
        (total, ce), g = grad_fn(params, micro)
        acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
        return constrain(acc), ce

    zeros = constrain(jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params))
    grads, ces = jax.lax.scan(body, zeros, batch,
                              unroll=True if unroll else 1)
    a = ces.shape[0]
    grads = jax.tree.map(lambda g: g / a, grads)
    return grads, jnp.mean(ces)


def _cast_tree(params, dtype):
    """bf16 copies of matrix params (norm scales and 1-D params stay
    fp32). The cast sits OUTSIDE the microbatch scan, so FSDP gathers move
    bf16 (half the bytes) and the gather result is reusable across
    microbatches (§Perf hillclimb #1)."""
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if (x.dtype == jnp.float32 and x.ndim >= 2) else x, params)


# ---------------------------------------------------------------------------
# Manual 2D DP×SP step (data × sequence mesh).
# ---------------------------------------------------------------------------

def _local_objective_fn(cfg: ModelConfig, run: RunConfig, plan: Parallelism):
    """Per-rank objective for the manual step: UNNORMALIZED local CE sum
    (+ n-weighted MoE aux), so the cross-replica normalization can happen
    AFTER the single gradient reduction (the token count rides in the
    same packed psum)."""

    def objective(params, micro):
        if "frames" in micro or "img" in micro:
            raise NotImplementedError(
                "encoder/VLM aux inputs are not supported on the 2D DP×SP "
                "training plan yet")
        logits, aux = M.forward(params, micro["tokens"], cfg, plan,
                                remat=run.remat, unroll=run.scan_unroll,
                                resets=micro.get("resets"))
        with scope("loss"):
            ce_sum, n_valid, _ = M.lm_loss_sum(logits, micro["labels"])
        n = n_valid.astype(jnp.float32)
        # n-weighted aux: after global normalization this is the
        # token-weighted mean of the per-shard aux losses (== the global
        # aux when shards agree; the standard DP decomposition).
        obj = ce_sum + MOE_AUX_COEF * aux * n
        return obj, (ce_sum, n)

    return objective


def _state_specs(state, plan: Parallelism):
    """PartitionSpecs of the train state on a manual plan's mesh: params
    and counters replicated, ZeRO-1 Adam moments sharded over the
    optimizer-shard axes."""
    sspec = jax.tree.map(lambda _: P(), state)
    if plan.zero1_axis is not None:
        sspec["opt"] = adamw.Zero1AdamState(
            m=P(plan.zero1_axis), v=P(plan.zero1_axis), count=P())
    return sspec


def _make_manual_train_step(cfg: ModelConfig, run: RunConfig,
                            plan: Parallelism):
    if run.grad_compression:
        raise NotImplementedError(
            "grad_compression targets pod meshes; not supported on the "
            "2D DP×SP plan")
    mesh = plan.mesh
    axes = tuple(plan.manual_axes)
    dp_ax = plan.rules.get("batch")
    seq_ax = plan.sp.sp_axis if plan.sp is not None else None
    tp_ax = plan.sp.tp_axis if plan.sp is not None else None
    zero_ax = plan.zero1_axis
    zero_deg = _axis_size(mesh, zero_ax)
    dp = mesh.shape[dp_ax] if dp_ax is not None else 1
    world = 1
    for a in axes:
        world *= mesh.shape[a]
    objective = _local_objective_fn(cfg, run, plan)

    def body(state, batch):
        params = state["params"]
        if run.cast_params_once:
            compute_params = _cast_tree(params, jnp.dtype(cfg.dtype))
        else:
            compute_params = params

        grad_fn = jax.value_and_grad(objective, has_aux=True)

        def micro_body(acc, micro):
            (_, (ce, n)), g = grad_fn(compute_params, micro)
            acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                               acc, g)
            return acc, (ce, n)

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             compute_params)
        grads, (ces, ns) = jax.lax.scan(
            micro_body, zeros, batch, unroll=True if run.scan_unroll else 1)

        # THE single gradient reduction: flat grads ‖ [ce_sum, n_sum] in
        # one all-reduce across the whole mesh (data and sequence partial
        # sums combine in the same collective). With the guard on, one
        # extra fp32 scalar (this rank's loss-health indicator) rides in
        # the same vector — every rank reaches the same verdict with
        # ZERO additional collectives (docs/resilience.md).
        flat, unravel_grads = ravel_pytree(grads)
        flat = health.chaos_poison_nan(flat, state["step"],
                                       run.chaos_nan_steps)
        tail = [jnp.sum(ces), jnp.sum(ns)]
        if run.guard:
            # The piggybacked health scalar checks only the tiny local
            # loss vector. Gradient non-finiteness needs NO local pass:
            # NaN/Inf are absorbing under the psum, so the post-reduce
            # gnorm/ce checks below catch any rank's bad contribution —
            # a local isfinite sweep over the raveled grads would force
            # the concat to materialize twice (~5% more step bytes).
            local_bad = jnp.logical_not(jnp.all(jnp.isfinite(ces)))
            tail.append(local_bad.astype(jnp.float32))
        with scope("grad_reduce"):
            packed = jnp.concatenate([flat, jnp.stack(tail)])
            packed = comm_primitives.psum_packed(
                packed, axes if len(axes) > 1 else axes[0],
                group_size=world, tag="train.grads")
        k = len(tail)
        ce_tot = packed[-k]
        n_tot = jnp.maximum(packed[-k + 1], 1.0)  # all-masked batch → loss 0
        gflat = packed[:-k] / n_tot

        with scope("optimizer"):
            gnorm = jnp.sqrt(jnp.sum(gflat * gflat))
            if run.guard:
                nonfinite = (packed[-1] > 0) \
                    | jnp.logical_not(jnp.isfinite(gnorm)) \
                    | jnp.logical_not(jnp.isfinite(ce_tot)) \
                    | health.chaos_hit(state["step"], run.chaos_skip_steps)
                scale, finite, new_guard, ginfo = health.guard_verdict(
                    state["guard"], gnorm, nonfinite,
                    grad_clip=run.grad_clip,
                    spike_factor=run.guard_spike_factor)
                # where (not scale·0): NaN grads must not propagate as NaN·0
                gflat = jnp.where(finite, gflat * scale, 0.0)
            else:
                scale = jnp.minimum(
                    1.0, run.grad_clip / jnp.maximum(gnorm, 1e-9))
                finite = jnp.isfinite(gnorm)
                # Fault tolerance: a non-finite step is skipped, not applied.
                gflat = jnp.where(finite, gflat * scale, 0.0)
            lr = adamw.cosine_schedule(
                state["step"], base_lr=run.learning_rate,
                warmup_steps=run.warmup_steps, total_steps=run.total_steps,
                min_lr=run.min_lr)

            opt = state["opt"]
            if zero_ax is not None:
                # ZeRO-1: update this rank's 1/zero_deg flat slice, gather
                # params. On 3D plans ``zero_ax`` is the combined
                # (data, model) tuple — ``multi_axis_index`` linearizes it in
                # the same major-first order the all-gather concatenates.
                pflat, unravel_params = ravel_pytree(params)
                n_params = pflat.size
                padded = adamw.zero1_padded_size(params, zero_deg)
                shard = padded // zero_deg
                pad = padded - n_params

                def padded_slice(vec):
                    vec = jnp.concatenate(
                        [vec.astype(jnp.float32),
                         jnp.zeros((pad,), jnp.float32)])
                    ix = comm_primitives.multi_axis_index(zero_ax) * shard
                    return jax.lax.dynamic_slice(vec, (ix,), (shard,))

                g_sh = padded_slice(gflat)
                p_sh = padded_slice(pflat)
                d_sh = padded_slice(adamw.decay_mask(params))
                count = opt.count + 1
                new_p_sh, new_m, new_v = adamw.zero1_update_shard(
                    g_sh, opt.m, opt.v, p_sh, d_sh, count, lr=lr,
                    b1=run.adam_b1, b2=run.adam_b2,
                    weight_decay=run.weight_decay)
                new_p_sh = jnp.where(finite, new_p_sh, p_sh)
                new_m = jnp.where(finite, new_m, opt.m)
                new_v = jnp.where(finite, new_v, opt.v)
                count = jnp.where(finite, count, opt.count)
                # ZeRO-1's all-gather-on-update: the only other collective
                # touching the data axis.
                gathered = comm_primitives.allgather_states(
                    new_p_sh, zero_ax, axis_size=zero_deg, gather_axis=0,
                    tiled=True, tag="zero1.param_gather")
                new_params = unravel_params(gathered[:n_params])
                new_opt = adamw.Zero1AdamState(new_m, new_v, count)
            else:
                grads_tree = unravel_grads(gflat)
                new_params, new_opt = adamw.update(
                    grads_tree, opt, params, lr=lr, b1=run.adam_b1,
                    b2=run.adam_b2, weight_decay=run.weight_decay)
                new_params = jax.tree.map(
                    lambda nw, o: jnp.where(finite, nw, o), new_params, params)
                new_opt = jax.tree.map(
                    lambda nw, o: jnp.where(finite, nw, o), new_opt, opt)

        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = {"loss": ce_tot / n_tot, "grad_norm": gnorm, "lr": lr,
                   "skipped": (~finite).astype(jnp.float32)}
        if run.guard:
            new_state["guard"] = new_guard
            metrics.update(ginfo)
        return new_state, metrics

    def train_step(state, batch):
        rows = jax.tree.leaves(batch)[0].shape[1]
        seq = jax.tree.leaves(batch)[0].shape[2]
        sp = mesh.shape[seq_ax] if seq_ax is not None else 1
        tp = mesh.shape[tp_ax] if tp_ax is not None else 1
        if rows % dp or seq % (sp * tp):
            raise ValueError(
                f"DP×SP step needs microbatch rows ({rows}) divisible "
                f"by dp ({dp}) and seq len ({seq}) by sp×tp ({sp}×{tp})")
        # Tokens shard over the COMBINED (sequence, model) axes on 3D
        # plans — sequence-major, matching SPConfig.exchange_axes.
        token_ax = seq_ax if tp_ax is None else (seq_ax, tp_ax)
        bspec = jax.tree.map(lambda _: P(None, dp_ax, token_ax), batch)
        sspec = _state_specs(state, plan)
        mspec = {"loss": P(), "grad_norm": P(), "lr": P(), "skipped": P()}
        if run.guard:
            mspec.update({key: P() for key in health.GUARD_METRICS})
        return _shard_map(
            body, mesh=mesh, in_specs=(sspec, bspec),
            out_specs=(sspec, mspec), axis_names=set(axes),
            check_vma=False)(state, batch)

    return train_step


def make_train_step(cfg: ModelConfig, run: RunConfig, plan: Parallelism):
    if plan.manual_axes:
        return _make_manual_train_step(cfg, run, plan)
    loss_fn = make_loss_fn(cfg, run, plan)

    def train_step(state, batch):
        params = state["params"]
        if run.cast_params_once:
            compute_params = _cast_tree(params, jnp.dtype(cfg.dtype))
        else:
            compute_params = params

        if run.grad_compression and plan.mesh is not None \
                and POD_AXIS in plan.mesh.axis_names:
            # per-pod local grads → int8 error-feedback cross-pod sync
            def body(params_, batch_, err_):
                g, ce = _accum_grads(loss_fn, params_, batch_,
                                     run.scan_unroll, plan)
                g, new_err = compress_sync_tree(g, err_, pod_axis=POD_AXIS)
                return g, jax.lax.pmean(ce, POD_AXIS), new_err

            nb = jax.tree.map(lambda x: P(None, POD_AXIS), batch)
            grads, ce, new_err = _shard_map(
                body, mesh=plan.mesh,
                in_specs=(P(), nb, P()), out_specs=(P(), P(), P()),
                axis_names={POD_AXIS}, check_vma=False)(
                    compute_params, batch, state["err"])
        else:
            grads, ce = _accum_grads(loss_fn, compute_params, batch,
                                     run.scan_unroll, plan)
            new_err = state.get("err")
        if run.cast_params_once:
            # d(loss)/d(master fp32) == d(loss)/d(bf16 copy) cast back
            grads = jax.tree.map(
                lambda g, p: g.astype(jnp.float32)
                if g.dtype != p.dtype else g, grads, params)

        if run.chaos_nan_steps:
            bad = health.chaos_hit(state["step"], run.chaos_nan_steps)
            grads = jax.tree.map(
                lambda g: jnp.where(bad, jnp.full_like(g, jnp.nan), g),
                grads)
        with scope("optimizer"):
            if run.guard:
                gnorm = adamw.global_norm(grads)
                nonfinite = jnp.logical_not(jnp.isfinite(gnorm)) \
                    | jnp.logical_not(jnp.isfinite(ce)) \
                    | health.chaos_hit(state["step"], run.chaos_skip_steps)
                gscale, finite, new_guard, ginfo = health.guard_verdict(
                    state["guard"], gnorm, nonfinite,
                    grad_clip=run.grad_clip,
                    spike_factor=run.guard_spike_factor)
                grads = jax.tree.map(
                    lambda g: jnp.where(finite, g * gscale, jnp.zeros_like(g)),
                    grads)
            else:
                grads, gnorm = adamw.clip_by_global_norm(grads, run.grad_clip)
                finite = jnp.isfinite(gnorm)
                # Fault tolerance: a non-finite step is skipped, not applied.
                grads = jax.tree.map(
                    lambda g: jnp.where(finite, g, jnp.zeros_like(g)), grads)
            lr = adamw.cosine_schedule(
                state["step"], base_lr=run.learning_rate,
                warmup_steps=run.warmup_steps, total_steps=run.total_steps,
                min_lr=run.min_lr)
            new_params, new_opt = adamw.update(
                grads, state["opt"], params, lr=lr, b1=run.adam_b1,
                b2=run.adam_b2, weight_decay=run.weight_decay)
            new_params = jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new_params, params)
            new_opt = jax.tree.map(
                lambda n, o: jnp.where(finite, n, o), new_opt, state["opt"])
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        if new_err is not None:
            new_state["err"] = new_err
        metrics = {"loss": ce, "grad_norm": gnorm, "lr": lr,
                   "skipped": (~finite).astype(jnp.float32)}
        if run.guard:
            new_state["guard"] = new_guard
            metrics.update(ginfo)
        return new_state, metrics

    return train_step
