"""Distributed-correctness battery, run on 8 virtual host devices.

Invoked by tests/test_distributed.py in a subprocess (so the main pytest
process keeps its single default device — the dry-run is the only place
with 512). Each check compares a sharded computation against its
single-device oracle. Exits non-zero on the first failure.

Mesh axis names come from ``repro.launch.mesh`` (the single source of
truth): the SP batteries shard over ``SEQ_AXIS``, the DP×SP(×TP)
battery runs on a ``(DATA_AXIS, SEQ_AXIS[, MODEL_AXIS])`` mesh.
``REPRO_TEST_MESH=AxB`` or ``AxBxC`` (dp×sp[×tp], default ``2x4``)
picks that battery's mesh split — the CI matrix sweeps
``8x1 | 4x2 | 2x4 | 2x2x2``.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.compat import shard_map as _shard_map       # noqa: E402

from repro.core import linear_attention as la               # noqa: E402
from repro.core.baselines import (lasp1, megatron_sp_attention,  # noqa: E402
                                  ring_attention)
from repro.core.lasp2 import SPConfig, lasp2, lasp2_with_state  # noqa: E402
from repro.core.lasp2h import (allgather_context_attention,  # noqa: E402
                               sharded_decode_attention)
from repro.launch.mesh import (DATA_AXIS, MODEL_AXIS, POD_AXIS,  # noqa: E402
                               SEQ_AXIS, make_sp_mesh, make_test_mesh,
                               make_training_mesh)

PASSED = []
SKIPPED = []
# REPRO_2D_ONLY=1: run only the mesh-split-dependent 2D DP×SP section —
# the CI matrix legs other than the default split set this so the
# mesh-independent checks (identical on every leg) run exactly once.
_2D_ONLY = os.environ.get("REPRO_2D_ONLY") == "1"


def check(name, section="base"):
    def deco(fn):
        if _2D_ONLY and section != "2d":
            SKIPPED.append(name)
            return
        fn()
        PASSED.append(name)
        print(f"  ✓ {name}", flush=True)
    return deco


def _env_mesh():
    """(dp, sp, tp) split of the mesh battery, from
    ``REPRO_TEST_MESH=AxB`` (tp defaults to 1) or ``AxBxC``."""
    raw = os.environ.get("REPRO_TEST_MESH", "2x4")
    parts = [int(x) for x in raw.lower().split("x")]
    if len(parts) == 2:
        parts.append(1)
    if len(parts) != 3 or parts[0] * parts[1] * parts[2] != 8:
        raise SystemExit(
            f"REPRO_TEST_MESH={raw!r} must be AxB or AxBxC multiplying to 8")
    return tuple(parts)


mesh1d = make_sp_mesh(8)
sp = SPConfig(mesh=mesh1d, sp_axis=SEQ_AXIS)
key = jax.random.PRNGKey(1)
B, H, S, dk, dv = 2, 4, 512, 32, 64
ks = jax.random.split(key, 4)
q = jax.random.normal(ks[0], (B, H, S, dk)) * 0.3
k = jax.random.normal(ks[1], (B, H, S, dk)) * 0.3
v = jax.random.normal(ks[2], (B, H, S, dv)) * 0.5
log_a = -jnp.abs(jax.random.normal(ks[3], (B, H, S))) * 0.03


@check("lasp2 forward parity (decay + no-decay, both backwards)")
def _():
    for la_in in (jnp.zeros((B, H, S)), log_a):
        ref = la.sequential_oracle(q, k, v, la_in)
        for bwd in ("faithful", "autodiff"):
            o = jax.jit(lambda a, b, c, d, bwd=bwd: lasp2(
                a, b, c, d, sp=sp, backward=bwd))(q, k, v, la_in)
            np.testing.assert_allclose(o, ref.o, rtol=3e-4, atol=3e-4)


@check("lasp2 custom_vjp (Alg.3/4) grads == autodiff == oracle")
def _():
    def gradf(fn):
        return jax.jit(jax.grad(
            lambda q_, k_, v_: jnp.sum(jnp.sin(fn(q_, k_, v_))),
            argnums=(0, 1, 2)))
    g_or = gradf(lambda a, b, c: la.sequential_oracle(a, b, c, log_a).o)(
        q, k, v)
    g_f = gradf(lambda a, b, c: lasp2(a, b, c, log_a, sp=sp,
                                      backward="faithful"))(q, k, v)
    g_a = gradf(lambda a, b, c: lasp2(a, b, c, log_a, sp=sp,
                                      backward="autodiff"))(q, k, v)
    for go, gf, ga in zip(g_or, g_f, g_a):
        np.testing.assert_allclose(gf, go, rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(ga, go, rtol=1e-3, atol=1e-3)


@check("lasp2 data-dependent decay gradient (autodiff path)")
def _():
    g1 = jax.jit(jax.grad(lambda a: jnp.sum(jnp.sin(
        lasp2(q, k, v, a, sp=sp, backward="autodiff")))))(log_a)
    g2 = jax.jit(jax.grad(lambda a: jnp.sum(jnp.sin(
        la.sequential_oracle(q, k, v, a).o))))(log_a)
    np.testing.assert_allclose(g1, g2, rtol=2e-3, atol=2e-3)


@check("lasp2 bidirectional (Alg.1/3) fwd+bwd vs oracle")
def _():
    ref = la.sequential_oracle(q, k, v, None, causal=False)
    o = jax.jit(lambda a, b, c: lasp2(a, b, c, sp=sp, causal=False))(q, k, v)
    np.testing.assert_allclose(o, ref.o, rtol=3e-4, atol=3e-4)
    gn = jax.jit(jax.grad(lambda a, b, c: jnp.sum(jnp.sin(
        lasp2(a, b, c, sp=sp, causal=False))), argnums=(0, 1, 2)))(q, k, v)
    go = jax.jit(jax.grad(lambda a, b, c: jnp.sum(jnp.sin(
        la.sequential_oracle(a, b, c, None, causal=False).o)),
        argnums=(0, 1, 2)))(q, k, v)
    for a_, b_ in zip(gn, go):
        np.testing.assert_allclose(a_, b_, rtol=1e-3, atol=1e-3)


@check("lasp2_with_state: SP prefill state == oracle final state")
def _():
    ref = la.sequential_oracle(q, k, v, log_a)
    o, st = jax.jit(lambda a, b, c, d: lasp2_with_state(
        a, b, c, d, sp=sp))(q, k, v, log_a)
    np.testing.assert_allclose(o, ref.o, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(st, ref.state, rtol=3e-4, atol=3e-4)


@check("LASP-1 ring (Alg.5/6) == LASP-2 == oracle")
def _():
    ref = la.sequential_oracle(q, k, v, log_a)
    o = jax.jit(lambda a, b, c, d: lasp1(a, b, c, d, sp=sp))(q, k, v, log_a)
    np.testing.assert_allclose(o, ref.o, rtol=3e-4, atol=3e-4)


@check("lasp2 exactly ONE fwd AllGather of the packed (M_t, A_t)")
def _():
    import re
    txt = jax.jit(lambda a, b, c, d: lasp2(a, b, c, d, sp=sp)).lower(
        q, k, v, log_a).compile().as_text()
    ags = [l for l in txt.splitlines() if re.search(r"all-gather\(", l)]
    sizes = sorted(
        int(np.prod([int(x) for x in re.search(
            r"\[([\d,]+)\]", l).group(1).split(",")])) for l in ags)
    assert len(ags) == 1, f"expected 1 all-gather, got {len(ags)}"
    # the (W, B, H, dk*dv + 1) packed state-and-decay gather
    assert sizes[-1] == 8 * B * H * (dk * dv + 1)
    assert not re.search(r"all-to-all\(|collective-permute\(", txt)


@check("lasp2 kernel_backend=interpret: Pallas intra-chunk under shard_map")
def _():
    """The interpret-mode kernel-grad battery: the Pallas chunk kernel's
    custom_vjp runs INSIDE the SP shard_map — forward parity, faithful
    grads (pulling dO and dM through the kernel), data-dependent decay
    grads via autodiff, and the untouched collective budget (exactly one
    packed forward all-gather per layer)."""
    import re
    spk = SPConfig(mesh=mesh1d, sp_axis=SEQ_AXIS,
                   kernel_backend="interpret")
    ref = la.sequential_oracle(q, k, v, log_a)
    o = jax.jit(lambda a, b, c, d: lasp2(a, b, c, d, sp=spk,
                                         backward="faithful"))(q, k, v, log_a)
    np.testing.assert_allclose(o, ref.o, rtol=3e-4, atol=3e-4)
    g_f = jax.jit(jax.grad(lambda a, b, c: jnp.sum(jnp.sin(
        lasp2(a, b, c, log_a, sp=spk, backward="faithful"))),
        argnums=(0, 1, 2)))(q, k, v)
    g_o = jax.jit(jax.grad(lambda a, b, c: jnp.sum(jnp.sin(
        la.sequential_oracle(a, b, c, log_a).o)),
        argnums=(0, 1, 2)))(q, k, v)
    for gf, go in zip(g_f, g_o):
        np.testing.assert_allclose(gf, go, rtol=1e-3, atol=1e-3)
    ga = jax.jit(jax.grad(lambda a: jnp.sum(jnp.sin(
        lasp2(q, k, v, a, sp=spk, backward="autodiff")))))(log_a)
    gr = jax.jit(jax.grad(lambda a: jnp.sum(jnp.sin(
        la.sequential_oracle(q, k, v, a).o))))(log_a)
    np.testing.assert_allclose(ga, gr, rtol=2e-3, atol=2e-3)
    txt = jax.jit(lambda a, b, c, d: lasp2(a, b, c, d, sp=spk)).lower(
        q, k, v, log_a).compile().as_text()
    n_ag = len(re.findall(r"all-gather\(", txt))
    assert n_ag == 1, f"expected 1 fwd all-gather, got {n_ag}"
    assert not re.search(r"all-to-all\(|collective-permute\(", txt)


@check("LASP-1 emits W-1 sequential permute steps (ring), LASP-2 none")
def _():
    import re
    txt = jax.jit(lambda a, b, c, d: lasp1(a, b, c, d, sp=sp)).lower(
        q, k, v, log_a).compile().as_text()
    n = len(re.findall(r"collective-permute\(", txt))
    assert n == 7, f"ring should unroll to W-1=7 ppermutes, got {n}"
    assert not re.search(r"all-gather\(", txt)


# --- softmax side (LASP-2H) -------------------------------------------------

Hq, Hkv, dh = 8, 2, 32
qs = jax.random.normal(ks[0], (B, Hq, S, dh)) * 0.5
ks_ = jax.random.normal(ks[1], (B, Hkv, S, dh)) * 0.5
vs = jax.random.normal(ks[2], (B, Hkv, S, dh)) * 0.5


@check("LASP-2H AllGather-CP (Alg.7) == full attention (+grads)")
def _():
    ref = allgather_context_attention(qs, ks_, vs, sp=None)
    o = jax.jit(lambda a, b, c: allgather_context_attention(
        a, b, c, sp=sp))(qs, ks_, vs)
    np.testing.assert_allclose(o, ref, rtol=2e-4, atol=2e-4)
    g1 = jax.jit(jax.grad(lambda a: jnp.sum(jnp.sin(
        allgather_context_attention(a, ks_, vs, sp=sp)))))(qs)
    g0 = jax.jit(jax.grad(lambda a: jnp.sum(jnp.sin(
        allgather_context_attention(a, ks_, vs, sp=None)))))(qs)
    np.testing.assert_allclose(g1, g0, rtol=1e-3, atol=1e-3)


@check("LASP-2H trains through the flash kernel (interpret) in shard_map")
def _():
    """The sharded hybrid path dispatches through ops.flash_attention_op:
    the Pallas flash custom_vjp runs INSIDE the SP shard_map with the
    rank offset t·C as a traced q_offset — forward parity, grads, and
    the unchanged 2-gather (K, V) collective budget."""
    import re
    spk = SPConfig(mesh=mesh1d, sp_axis=SEQ_AXIS,
                   kernel_backend="interpret")
    for window in (None, 64):
        ref = allgather_context_attention(qs, ks_, vs, sp=None,
                                          sliding_window=window)
        o = jax.jit(lambda a, b, c, w=window: allgather_context_attention(
            a, b, c, sp=spk, sliding_window=w))(qs, ks_, vs)
        np.testing.assert_allclose(o, ref, rtol=2e-4, atol=2e-4)
    g1 = jax.jit(jax.grad(lambda a, b, c: jnp.sum(jnp.sin(
        allgather_context_attention(a, b, c, sp=spk))),
        argnums=(0, 1, 2)))(qs, ks_, vs)
    g0 = jax.jit(jax.grad(lambda a, b, c: jnp.sum(jnp.sin(
        allgather_context_attention(a, b, c, sp=None))),
        argnums=(0, 1, 2)))(qs, ks_, vs)
    for a_, b_ in zip(g1, g0):
        np.testing.assert_allclose(a_, b_, rtol=1e-3, atol=1e-3)
    txt = jax.jit(lambda a, b, c: allgather_context_attention(
        a, b, c, sp=spk)).lower(qs, ks_, vs).compile().as_text()
    n_ag = len(re.findall(r"all-gather\(", txt))
    assert n_ag == 2, f"expected the K and V gathers only, got {n_ag}"


@check("comm_dtype=bf16: same collectives, half the bytes, output parity")
def _():
    """The bf16 wire knob: collective *counts* are unchanged (1 packed
    state gather for LASP-2; K+V gathers for LASP-2H) while the
    CommRecord bytes halve — asserted via the dtype-aware budget — and
    outputs stay within bf16 payload tolerance of the fp32 exchange."""
    from repro.comm import tape, tape_summary
    from repro.comm.budget import (assert_budget, lasp2_budget,
                                   packed_state_bytes)
    sp_bf = SPConfig(mesh=mesh1d, sp_axis=SEQ_AXIS, comm_dtype="bf16")
    ref = la.sequential_oracle(q, k, v, log_a)
    o = jax.jit(lambda a, b, c, d: lasp2(a, b, c, d, sp=sp_bf))(
        q, k, v, log_a)
    np.testing.assert_allclose(o, ref.o, rtol=3e-2, atol=3e-2)
    with tape() as recs:
        txt = jax.jit(lambda a, b, c, d: lasp2(
            a, b, c, d, sp=sp_bf)).lower(q, k, v, log_a).compile().as_text()
    sb = packed_state_bytes(B, H, dk, dv, "bf16")
    assert sb == packed_state_bytes(B, H, dk, dv, "fp32") // 2
    # count from compiled HLO; byte ceiling from the dtype-true tape
    # (XLA-CPU float-normalization upcasts bf16 collectives in HLO)
    assert_budget(txt, lasp2_budget("allgather", 8, state_bytes=sb), 8,
                  records=recs)
    assert tape_summary(recs)["total_bytes"] == 7 * sb
    # LASP-2H K/V gathers in bf16: half the KV bytes, parity holds
    sph = SPConfig(mesh=mesh1d, sp_axis=SEQ_AXIS, comm_dtype="bf16")
    refh = allgather_context_attention(qs, ks_, vs, sp=None)
    with tape() as recs:
        oh = jax.jit(lambda a, b, c: allgather_context_attention(
            a, b, c, sp=sph))(qs, ks_, vs)
    np.testing.assert_allclose(oh, refh, rtol=2e-2, atol=2e-2)
    s = tape_summary(recs)
    kv_payload = B * Hkv * (S // 8) * dh * 2
    assert s["all-gather_count"] == 2
    assert s["total_bytes"] == 2 * 7 * kv_payload
    # the knob only ever NARROWS: bf16 activations under the default
    # comm_dtype="fp32" keep their native bf16-sized K/V gather
    # (widening would double the bytes the knob exists to halve)
    sp32 = SPConfig(mesh=mesh1d, sp_axis=SEQ_AXIS)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (qs, ks_, vs))
    with tape() as recs:
        jax.jit(lambda a, b, c: allgather_context_attention(
            a, b, c, sp=sp32)).lower(qb, kb, vb)
    assert tape_summary(recs)["total_bytes"] == 2 * 7 * kv_payload


@check("Ring Attention == Megatron-SP == full attention")
def _():
    ref = allgather_context_attention(qs, ks_, vs, sp=None)
    o1 = jax.jit(lambda a, b, c: ring_attention(a, b, c, sp=sp))(qs, ks_, vs)
    o2 = jax.jit(lambda a, b, c: megatron_sp_attention(
        a, b, c, sp=sp))(qs, ks_, vs)
    np.testing.assert_allclose(o1, ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(o2, ref, rtol=2e-4, atol=2e-4)


@check("sliding-window CP == sliding-window reference")
def _():
    for causal in (True, False):
        ref = allgather_context_attention(qs, ks_, vs, sp=None,
                                          causal=causal, sliding_window=64)
        o = jax.jit(lambda a, b, c, ca=causal: allgather_context_attention(
            a, b, c, sp=sp, causal=ca, sliding_window=64))(qs, ks_, vs)
        np.testing.assert_allclose(o, ref, rtol=2e-4, atol=2e-4)


@check("flash-decoding sharded decode == local decode (3 cache lens)")
def _():
    Sc = 512
    kc = jax.random.normal(ks[0], (B, Hkv, Sc, dh)) * 0.5
    vc = jax.random.normal(ks[1], (B, Hkv, Sc, dh)) * 0.5
    q1 = jax.random.normal(ks[2], (B, Hq, 1, dh)) * 0.5
    for clen in (Sc, 300, 37):
        ref = sharded_decode_attention(q1, kc, vc, clen, sp=None)
        o = jax.jit(lambda a, b, c, cl=clen: sharded_decode_attention(
            a, b, c, cl, sp=sp))(q1, kc, vc)
        np.testing.assert_allclose(o, ref, rtol=2e-4, atol=2e-4)


# --- model-level on a 2D mesh ----------------------------------------------

@check("sharded model forward == single-device forward (dense+SP)")
def _():
    from repro.configs import get_smoke
    from repro.models import model as M
    from repro.sharding.rules import make_plan

    mesh = make_test_mesh((4, 2), (DATA_AXIS, MODEL_AXIS))
    cfg = get_smoke("starcoder2-15b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg.vocab_size)
    ref, _ = jax.jit(lambda p, t: M.forward(p, t, cfg, remat="none"))(
        params, tokens)
    plan = make_plan(mesh, "prefill", global_batch=2,
                     n_kv_heads=cfg.n_kv_heads)
    out, _ = jax.jit(lambda p, t: M.forward(p, t, cfg, plan,
                                            remat="none"))(params, tokens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


@check("sharded train step == single-device train step (loss match)")
def _():
    from repro.configs import get_smoke
    from repro.configs.base import RunConfig
    from repro.data.pipeline import SyntheticLM
    from repro.sharding.rules import make_plan
    from repro.train.step import init_state, make_train_step

    cfg = get_smoke("linear-llama3-1b")
    run = RunConfig(num_microbatches=2, remat="none", total_steps=10)
    data = SyntheticLM(cfg.vocab_size, 64, 8, seed=3)
    batch = data.microbatched(0, 2)

    s0 = init_state(jax.random.PRNGKey(0), cfg, run)
    from repro.sharding.rules import local_plan
    _, m_ref = jax.jit(make_train_step(cfg, run, local_plan()))(s0, batch)

    mesh = make_test_mesh((4, 2), (DATA_AXIS, MODEL_AXIS))
    plan = make_plan(mesh, "train", global_batch=8,
                     n_kv_heads=cfg.n_kv_heads)
    s1 = init_state(jax.random.PRNGKey(0), cfg, run)
    _, m_sh = jax.jit(make_train_step(cfg, run, plan))(s1, batch)
    np.testing.assert_allclose(float(m_sh["loss"]), float(m_ref["loss"]),
                               rtol=2e-3, atol=2e-3)


@check("int8 error-feedback cross-pod grad sync ~= exact mean")
def _():
    from repro.optim.compression import compress_sync_tree
    mesh = make_test_mesh((2, 4), (POD_AXIS, DATA_AXIS))
    gs = jax.random.normal(ks[0], (2, 64, 64)) * 1e-3   # per-pod grads
    e0 = jnp.zeros((2, 64, 64))

    def body(g_, e_):
        s, e = compress_sync_tree(g_[0], e_[0], pod_axis=POD_AXIS)
        return s, e[None]

    synced, err = jax.jit(_shard_map(
        body, mesh=mesh, in_specs=(P(POD_AXIS), P(POD_AXIS)),
        out_specs=(P(), P(POD_AXIS)), axis_names={POD_AXIS},
        check_vma=False))(gs, e0)
    exact = jnp.mean(gs, axis=0)
    rel = float(jnp.max(jnp.abs(synced - exact))
                / (jnp.max(jnp.abs(exact)) + 1e-12))
    assert rel < 0.02, f"compression error too large: {rel}"
    # exactness identity: mean(g) == synced + mean(error feedback)
    np.testing.assert_allclose(np.asarray(synced + jnp.mean(err, 0)),
                               np.asarray(exact), rtol=1e-5, atol=1e-8)


@check("mini dry-run: lower+compile a smoke train cell on the 4x2 mesh")
def _():
    from repro.configs import get_smoke
    from repro.launch.cells import build_cell
    mesh = make_test_mesh((4, 2), (DATA_AXIS, MODEL_AXIS))
    cell = build_cell("hymba-1.5b", "train_4k", mesh,
                      cfg_override=get_smoke("hymba-1.5b"))
    compiled = cell.lower().compile()
    assert compiled.memory_analysis() is not None
    assert compiled.cost_analysis().get("flops", 0) > 0


@check("hybrid (LASP-2H) train step == flash custom_vjp == xla backend")
def _():
    """Model-level proof of the Pallas hybrid hot path: a 2-layer
    linear+softmax hybrid trains on a (1, 8) SP mesh with
    kernel_backend="interpret" — every softmax layer runs the flash
    custom_vjp inside the manual train-step shard_map with the traced
    rank offset — and its 2-step losses match the xla backend and the
    single-device oracle."""
    from repro.configs.base import (LayerSpec, LinearAttnConfig,
                                    ModelConfig, RunConfig)
    from repro.data.pipeline import SyntheticLM
    from repro.sharding.rules import local_plan, make_plan
    from repro.train.step import init_state, make_train_step

    cfg = ModelConfig(
        name="hybrid-smoke", family="hybrid", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=160, vocab_size=512,
        pattern=(LayerSpec(mixer="linear"), LayerSpec(mixer="softmax")),
        linear_attn=LinearAttnConfig(feature_map="identity", decay="none"))
    run = RunConfig(num_microbatches=1, remat="none", total_steps=10,
                    warmup_steps=2, learning_rate=1e-3)
    data = SyntheticLM(cfg.vocab_size, 64, 8, seed=5)

    def losses(backend, sharded):
        if sharded:
            plan = make_plan(make_training_mesh(1, 8), "train",
                             global_batch=8, n_kv_heads=cfg.n_kv_heads,
                             backend=backend)
        else:
            plan = local_plan(backend)
        state = init_state(jax.random.PRNGKey(0), cfg, run, plan)
        step = jax.jit(make_train_step(cfg, run, plan))
        out = []
        for i in range(2):
            state, m = step(state, data.microbatched(i, 1))
            out.append(float(m["loss"]))
        return out

    l_int = losses("interpret", sharded=True)
    l_xla = losses("xla", sharded=True)
    l_ref = losses(None, sharded=False)
    assert all(np.isfinite(l_int)), l_int
    np.testing.assert_allclose(l_int, l_xla, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(l_int, l_ref, rtol=2e-3, atol=2e-3)


# --- DP×SP(×TP) training (data × sequence × model mesh) ---------------------

from repro.comm.spec import CommSpec                         # noqa: E402
from repro.configs import get_smoke                          # noqa: E402
from repro.configs.base import RunConfig                     # noqa: E402
from repro.data.pipeline import SyntheticLM                  # noqa: E402
from repro.sharding.rules import local_plan, make_plan       # noqa: E402
from repro.train.step import init_state, make_train_step     # noqa: E402

DP, SP, TP = _env_mesh()
_TAG = f"({DP},{SP})" if TP == 1 else f"({DP},{SP},{TP})"
_cfg2d = get_smoke("linear-llama3-1b")
_data2d = SyntheticLM(_cfg2d.vocab_size, 64, 8, seed=3)


def _run_steps(dp, sp_deg, run, n_steps=3, zero1=True, comm_dtype="fp32",
               tp=1):
    """Train ``n_steps`` on a (dp, sp[, tp]) mesh; (1, 1) = single device."""
    if (dp, sp_deg, tp) == (1, 1, 1):
        plan = local_plan()
        mesh = None
    else:
        mesh = make_training_mesh(dp, sp_deg, tp)
        plan = make_plan(mesh, "train", global_batch=8,
                         n_kv_heads=_cfg2d.n_kv_heads,
                         n_heads=_cfg2d.n_heads, zero1=zero1,
                         comm=CommSpec(dtype=comm_dtype))
    state = init_state(jax.random.PRNGKey(0), _cfg2d, run, plan)
    step = jax.jit(make_train_step(_cfg2d, run, plan))
    losses = []
    for i in range(n_steps):
        state, m = step(state, _data2d.microbatched(i, run.num_microbatches))
        losses.append(float(m["loss"]))
    return state, losses


# microbatch rows (8 / A) must divide dp — dp=8 forces A=1
_A2D = 2 if (8 // 2) % DP == 0 else 1
_RUN2D = RunConfig(num_microbatches=_A2D, remat="none", total_steps=10,
                   warmup_steps=2, learning_rate=1e-3)


@check(f"{_TAG} DP×SP(×TP) == (1,8) SP-only == single device (3-step loss)", section="2d")
def _():
    _, l_ref = _run_steps(1, 1, _RUN2D)
    _, l_sp = _run_steps(1, 8, _RUN2D)
    _, l_2d = _run_steps(DP, SP, _RUN2D, tp=TP)
    # same global batch, same math — only the reduction grouping differs
    np.testing.assert_allclose(l_2d, l_sp, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(l_2d, l_ref, rtol=2e-3, atol=2e-3)


@check(f"--comm-dtype bf16 loss trajectory ~= fp32 on {_TAG}", section="2d")
def _():
    """Training with bf16 exchange payloads tracks the fp32-wire loss:
    the wire dtype only rounds the state gathers (combines stay fp32),
    so a 3-step trajectory stays within bf16 payload tolerance — the
    sanity check behind shipping --comm-dtype bf16 as a perf knob."""
    _, l_fp32 = _run_steps(DP, SP, _RUN2D, tp=TP)
    _, l_bf16 = _run_steps(DP, SP, _RUN2D, comm_dtype="bf16", tp=TP)
    np.testing.assert_allclose(l_bf16, l_fp32, rtol=2e-2, atol=2e-2)
    if SP * TP == 1:
        # no sequence sharding → no SP exchange → bit-identical
        np.testing.assert_allclose(l_bf16, l_fp32, rtol=0, atol=0)


@check(f"ZeRO-1 sharded AdamW == replicated AdamW on {_TAG}", section="2d")
def _():
    s_z, l_z = _run_steps(DP, SP, _RUN2D, n_steps=2, zero1=True, tp=TP)
    s_r, l_r = _run_steps(DP, SP, _RUN2D, n_steps=2, zero1=False, tp=TP)
    np.testing.assert_allclose(l_z, l_r, rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(s_z["params"]),
                    jax.tree.leaves(s_r["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    if DP * TP > 1:
        from repro.optim.adamw import Zero1AdamState
        assert isinstance(s_z["opt"], Zero1AdamState)


@check(f"non-finite step skipped on {_TAG}: params+opt.count frozen", section="2d")
def _():
    mesh = make_training_mesh(DP, SP, TP)
    plan = make_plan(mesh, "train", global_batch=8,
                     n_kv_heads=_cfg2d.n_kv_heads,
                     n_heads=_cfg2d.n_heads)
    state = init_state(jax.random.PRNGKey(0), _cfg2d, _RUN2D, plan)
    step = jax.jit(make_train_step(_cfg2d, _RUN2D, plan))
    state["params"]["embed"]["table"] = \
        state["params"]["embed"]["table"].at[0, 0].set(jnp.nan)
    before = np.asarray(state["params"]["embed"]["table"])
    new_state, metrics = step(state, _data2d.microbatched(0, _A2D))
    assert float(metrics["skipped"]) == 1.0
    np.testing.assert_array_equal(
        before[1:], np.asarray(new_state["params"]["embed"]["table"])[1:])
    assert int(new_state["opt"].count) == 0, \
        "skipped step must not advance the Adam step count"


@check(f"{_TAG} step HLO: per-axis collective budget holds exactly", section="2d")
def _():
    from repro.comm.budget import (assert_axis_budget,
                                   train_step_axis_budget)
    run = RunConfig(num_microbatches=1, remat="none", total_steps=10,
                    warmup_steps=2, scan_unroll=True)
    mesh = make_training_mesh(DP, SP, TP)
    plan = make_plan(mesh, "train", global_batch=8,
                     n_kv_heads=_cfg2d.n_kv_heads,
                     n_heads=_cfg2d.n_heads)
    state = init_state(jax.random.PRNGKey(0), _cfg2d, run, plan)
    step = make_train_step(_cfg2d, run, plan)
    txt = jax.jit(step).lower(
        state, _data2d.microbatched(0, 1)).compile().as_text()
    # SyntheticLM packs documents → resets → the autodiff backward:
    # per layer 1 fwd all-gather + 1 bwd reduce-scatter, sequence-only;
    # 1 packed gradient all-reduce; 1 ZeRO-1 param all-gather over data.
    budget = train_step_axis_budget(
        mesh, n_sp_layers=_cfg2d.n_layers, microbatches=1,
        backward="autodiff", zero1=plan.zero1_axis is not None)
    assert_axis_budget(txt, mesh, budget)


@check(f"{_TAG} flight recorder: tape == expected bytes, drift flags",
       section="2d")
def _():
    """The compile-time flight recorder (docs/observability.md) on a
    REAL (DP,SP) train step: the CommRecord tape captured while lowering
    is the 'expected' collective view, the compiled HLO the 'measured'
    one. The snapshot's expected bytes must equal the tape total and the
    genuine program must not flag drift (autodiff's extra collectives
    are tolerated by design); an injected fake tape record must."""
    from repro.comm import tape
    from repro.comm.primitives import CommRecord, tape_summary
    from repro.obs import FlightRecorder, InMemorySink

    run = RunConfig(num_microbatches=1, remat="none", total_steps=10,
                    warmup_steps=2)
    mesh = make_training_mesh(DP, SP, TP)
    plan = make_plan(mesh, "train", global_batch=8,
                     n_kv_heads=_cfg2d.n_kv_heads,
                     n_heads=_cfg2d.n_heads)
    state = init_state(jax.random.PRNGKey(0), _cfg2d, run, plan)
    step = jax.jit(make_train_step(_cfg2d, run, plan))
    with tape() as records:
        lowered = step.lower(state, _data2d.microbatched(0, 1))
    hlo = lowered.compile().as_text()

    sink = InMemorySink()
    fr = FlightRecorder(sink)
    snap = fr.on_compile(records=records, hlo_text=hlo, total_devices=8)
    expect = tape_summary(records)
    assert snap.expected_bytes_per_step == expect["total_bytes"]
    assert snap.expected_steps_per_step == expect["total_steps"]
    if SP > 1:
        # sequence sharding ⇒ the layers' state gathers are on the tape
        assert snap.tape_counts.get("all-gather", 0) >= 1
        assert snap.hlo_counts.get("all-gather", 0) >= \
            snap.tape_counts["all-gather"]
    assert snap.drift == [], snap.drift
    (rec,) = sink.by_kind("compile")
    assert rec["expected_collective_bytes"] == expect["total_bytes"]

    # inject drift: a collective the compiled program does not carry
    bad = list(records) + [CommRecord("all-to-all", 10, 70, 1, 8)]
    snap2 = FlightRecorder(InMemorySink()).on_compile(
        records=bad, hlo_text=hlo, total_devices=8)
    assert any("all-to-all" in d for d in snap2.drift), \
        "injected tape record must flag drift"


@check(f"{_TAG} instrumented train: step records on the training mesh",
       section="2d")
def _():
    """train(sink=...) on the DP×SP mesh: the AOT-compiled instrumented
    path matches the uninstrumented losses and every step record carries
    the throughput + comm fields the report renders."""
    from repro.obs import InMemorySink
    from repro.train.loop import train

    mesh = make_training_mesh(DP, SP, TP)
    plan = make_plan(mesh, "train", global_batch=8,
                     n_kv_heads=_cfg2d.n_kv_heads,
                     n_heads=_cfg2d.n_heads)
    sink = InMemorySink()
    kw = dict(log_every=10 ** 9, log_fn=lambda *_: None, max_steps=2)
    _, hist = train(_cfg2d, _RUN2D, _data2d, plan=plan, sink=sink, **kw)
    _, ref = train(_cfg2d, _RUN2D, _data2d, plan=plan, **kw)
    np.testing.assert_allclose([h["loss"] for h in hist],
                               [h["loss"] for h in ref], rtol=0, atol=0)
    (comp,) = sink.by_kind("compile")
    assert comp["drift"] == []
    if SP > 1:
        assert comp["expected_collective_bytes"] > 0
    steps = sink.by_kind("step")
    assert len(steps) == 2
    for r in steps:
        assert {"step_s", "data_s", "wall_s", "tokens_per_s", "mfu",
                "expected_collective_bytes", "hlo_collective_bytes",
                "straggler"} <= set(r)
        assert r["tokens"] == 8 * 64


@check(f"{_TAG} compiled-program sanitizer: SAN201-205 clean",
       section="2d")
def _():
    """The static-analysis layer-2 invariants (docs/static_analysis.md)
    hold on this leg's mesh split: no host transfers, no f64, bf16 on
    the sequence-axis wire, donation aliased, deterministic lowering."""
    from repro.analysis.sanitizer import sanitize_train_step

    findings = sanitize_train_step(DP, SP, TP, comm_dtype="bf16")
    assert not findings, "\n".join(str(f) for f in findings)


@check(f"{_TAG} guard ON: axis budget unchanged, clean losses bitwise",
       section="2d")
def _():
    """The resilience tentpole invariant (docs/resilience.md): the
    numerical health guard adds ZERO collectives — the guarded step
    compiles to exactly the same per-axis collective budget as the
    unguarded one (the health scalar rides the packed gradient
    all-reduce) — and on clean steps the guarded loss trajectory is
    bit-identical to guard-off."""
    from repro.comm.budget import (assert_axis_budget,
                                   train_step_axis_budget)
    base = dict(num_microbatches=1, remat="none", total_steps=10,
                warmup_steps=2, scan_unroll=True)
    run_g = RunConfig(guard=True, **base)
    mesh = make_training_mesh(DP, SP, TP)
    plan = make_plan(mesh, "train", global_batch=8,
                     n_kv_heads=_cfg2d.n_kv_heads,
                     n_heads=_cfg2d.n_heads)
    state = init_state(jax.random.PRNGKey(0), _cfg2d, run_g, plan)
    txt = jax.jit(make_train_step(_cfg2d, run_g, plan)).lower(
        state, _data2d.microbatched(0, 1)).compile().as_text()
    budget = train_step_axis_budget(
        mesh, n_sp_layers=_cfg2d.n_layers, microbatches=1,
        backward="autodiff", zero1=plan.zero1_axis is not None)
    assert_axis_budget(txt, mesh, budget)   # same budget as guard-off

    _, l_plain = _run_steps(DP, SP, RunConfig(**base), tp=TP)
    _, l_guard = _run_steps(DP, SP, run_g, tp=TP)
    np.testing.assert_allclose(l_guard, l_plain, rtol=0, atol=0)


@check(f"{_TAG} SIGTERM mid-run → resume: bitwise trajectory parity",
       section="2d")
def _():
    """Preemption path end-to-end on the training mesh: SIGTERM delivered
    during step 3's data fetch → the loop finishes the step, saves, and
    exits; the resumed run (guard state restored from the checkpoint)
    recomputes steps 4..5 bitwise-identical to an uninterrupted run."""
    import tempfile

    from repro.resilience import chaos
    from repro.train.loop import train

    mesh = make_training_mesh(DP, SP, TP)
    plan = make_plan(mesh, "train", global_batch=8,
                     n_kv_heads=_cfg2d.n_kv_heads,
                     n_heads=_cfg2d.n_heads)
    run = RunConfig(num_microbatches=_A2D, remat="none", total_steps=6,
                    warmup_steps=2, learning_rate=1e-3, guard=True)
    kw = dict(log_every=10 ** 9, log_fn=lambda *_: None)
    _, ref = train(_cfg2d, run, _data2d, plan=plan, **kw)
    with tempfile.TemporaryDirectory() as td:
        data = chaos.InterruptData(_data2d, at_step=3)
        _, h1 = train(_cfg2d, run, data, plan=plan, ckpt_dir=td,
                      ckpt_every=2, **kw)
        assert [h["step"] for h in h1] == [0, 1, 2, 3]
        _, h2 = train(_cfg2d, run, _data2d, plan=plan, ckpt_dir=td,
                      ckpt_every=2, **kw)
        assert [h["step"] for h in h2] == [4, 5]
    np.testing.assert_allclose([h["loss"] for h in h1 + h2],
                               [h["loss"] for h in ref], rtol=0, atol=0)


@check(f"{_TAG} corrupt latest → fallback restore onto a different mesh",
       section="2d")
def _():
    """Checkpoint hardening across mesh shapes: after the latest
    checkpoint is corrupted on disk, ``restore_latest_valid`` falls back
    to the older verified step, and the path-matched {"params"} subtree
    device_puts onto a DIFFERENT mesh split (elastic resharding — params
    are saved as global host arrays, so any valid plan can load them)."""
    import tempfile

    from jax.sharding import NamedSharding

    from repro.checkpoint.manager import CheckpointManager
    from repro.resilience import chaos
    from repro.sharding.rules import param_specs
    from repro.train.loop import train

    mesh = make_training_mesh(DP, SP, TP)
    plan = make_plan(mesh, "train", global_batch=8,
                     n_kv_heads=_cfg2d.n_kv_heads,
                     n_heads=_cfg2d.n_heads)
    run = RunConfig(num_microbatches=1, remat="none", total_steps=4,
                    warmup_steps=2, learning_rate=1e-3, guard=True)
    with tempfile.TemporaryDirectory() as td:
        train(_cfg2d, run, _data2d, plan=plan, ckpt_dir=td, ckpt_every=2,
              log_every=10 ** 9, log_fn=lambda *_: None)
        mgr = CheckpointManager(td)
        assert mgr.latest_step() == 4
        zeros = {"params": jax.tree.map(
            jnp.zeros_like,
            init_state(jax.random.PRNGKey(0), _cfg2d, run)["params"])}
        oracle = mgr.restore(2, zeros)
        chaos.corrupt_checkpoint(td)            # corrupts latest (step 4)

        alt = (1, 8, 1) if (DP, SP, TP) == (8, 1, 1) else (8, 1, 1)
        mesh2 = make_training_mesh(*alt)
        plan2 = make_plan(mesh2, "train", global_batch=8,
                          n_kv_heads=_cfg2d.n_kv_heads,
                          n_heads=_cfg2d.n_heads)
        specs = param_specs(zeros["params"], plan2)
        shard = {"params": jax.tree.map(
            lambda x, s: NamedSharding(mesh2, s), zeros["params"], specs)}
        step, out, rejected = mgr.restore_latest_valid(zeros, shard)
    assert step == 2
    assert [s for s, _ in rejected] == [4]
    leaf = jax.tree.leaves(out["params"])[0]
    assert leaf.sharding.mesh.shape == mesh2.shape
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), out["params"], oracle["params"])


# --- 3D DP×SP×TP + ulysses head-parallel All-to-All (docs/parallelism.md) ---
# Fixed (1,4,2)/(2,2,2) meshes independent of the env split, so these run
# once (base section) on the default leg; the 2x2x2 CI leg re-runs the
# whole mesh-split-dependent section above on a real 3D mesh.

from repro.configs.base import (LayerSpec, LinearAttnConfig,  # noqa: E402
                                ModelConfig)

_cfg3d = ModelConfig(
    name="hybrid-smoke", family="hybrid", n_layers=2, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=160, vocab_size=512,
    pattern=(LayerSpec(mixer="linear"), LayerSpec(mixer="softmax")),
    linear_attn=LinearAttnConfig(feature_map="identity", decay="none"))
_data3d = SyntheticLM(_cfg3d.vocab_size, 64, 8, seed=5)
_RUN3D = RunConfig(num_microbatches=1, remat="none", total_steps=10,
                   warmup_steps=2, learning_rate=1e-3)


def _plan3d(dims, strategy="allgather"):
    mesh = make_training_mesh(*dims)
    return mesh, make_plan(mesh, "train", global_batch=8,
                           n_kv_heads=_cfg3d.n_kv_heads,
                           n_heads=_cfg3d.n_heads,
                           comm=CommSpec(strategy=strategy))


def _run_hybrid(dims, strategy="allgather", n_steps=3):
    if dims == (1, 1, 1):
        plan = local_plan()
    else:
        _, plan = _plan3d(dims, strategy)
    state = init_state(jax.random.PRNGKey(0), _cfg3d, _RUN3D, plan)
    step = jax.jit(make_train_step(_cfg3d, _RUN3D, plan))
    losses = []
    for i in range(n_steps):
        state, m = step(state, _data3d.microbatched(i, 1))
        losses.append(float(m["loss"]))
    return losses


@check("3D ulysses (1,4,2)/(2,2,2) == (1,8,1) allgather == single device")
def _():
    """The tentpole parity proof: the hybrid model trains identically
    whether the softmax layers reach full-sequence context by gathering
    K/V over the sequence axis (allgather CP) or by All-to-All head
    repartition over the model axis (ulysses), through autodiff, on
    every verified 3D split — and both match the single-device oracle."""
    l_ref = _run_hybrid((1, 1, 1))
    l_ag = _run_hybrid((1, 8, 1), "allgather")
    np.testing.assert_allclose(l_ag, l_ref, rtol=2e-3, atol=2e-3)
    for dims in ((1, 4, 2), (2, 2, 2)):
        l_u = _run_hybrid(dims, "ulysses")
        np.testing.assert_allclose(l_u, l_ag, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(l_u, l_ref, rtol=2e-3, atol=2e-3)


@check("ulysses fwd HLO: exactly 2 model-axis All-to-Alls per hybrid layer")
def _():
    """Forward-only lowering of the hybrid model under the (1,4,2)
    ulysses plan: the one hybrid layer costs exactly two model-axis
    All-to-Alls (seq→head in, head→seq out) — no gathers or permutes
    ride along on the model axis."""
    from repro.launch.hlo_analysis import collective_axis_counts
    from repro.models import model as M

    mesh, plan = _plan3d((1, 4, 2), "ulysses")
    params = M.init_params(jax.random.PRNGKey(0), _cfg3d)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0,
                                _cfg3d.vocab_size)

    def fwd(p, t):
        logits, _ = M.forward(p, t, _cfg3d, plan, remat="none")
        return logits

    txt = jax.jit(_shard_map(
        fwd, mesh=mesh, in_specs=(P(), P(None, (SEQ_AXIS, MODEL_AXIS))),
        out_specs=P(None, (SEQ_AXIS, MODEL_AXIS), None),
        axis_names=set(plan.manual_axes),
        check_vma=False)).lower(params, tokens).compile().as_text()
    counts = collective_axis_counts(txt, mesh)
    n_hybrid = sum(1 for s in _cfg3d.pattern if s.mixer == "softmax")
    assert counts.get(("all-to-all", (MODEL_AXIS,)), 0) == 2 * n_hybrid, \
        counts
    # model-ONLY traffic is the a2a pair and nothing else (the linear
    # layer's state gather spans the combined (sequence, model) token
    # axis — that is sequence-parallel traffic, not head-parallel)
    for (op, axes), n in counts.items():
        if axes == (MODEL_AXIS,) and op != "all-to-all":
            raise AssertionError(
                f"unexpected model-axis collective {op} x{n}: {counts}")


@check("3D ulysses step HLO: per-axis budget holds on (1,4,2) + (2,2,2)")
def _():
    """Full train-step per-axis ceiling on both CI-verified 3D splits:
    4 model-axis All-to-Alls per hybrid layer per step (2 fwd + 2 bwd
    from the mirrored custom_vjp pair), the linear layers' gathers on
    the combined (sequence, model) token axis, ZeRO-1 over
    (data, model) — nothing else."""
    from repro.comm.budget import (assert_axis_budget,
                                   train_step_axis_budget)
    from repro.launch.hlo_analysis import collective_axis_counts

    run = RunConfig(num_microbatches=1, remat="none", total_steps=10,
                    warmup_steps=2, scan_unroll=True)
    for dims in ((1, 4, 2), (2, 2, 2)):
        mesh, plan = _plan3d(dims, "ulysses")
        state = init_state(jax.random.PRNGKey(0), _cfg3d, run, plan)
        txt = jax.jit(make_train_step(_cfg3d, run, plan)).lower(
            state, _data3d.microbatched(0, 1)).compile().as_text()
        budget = train_step_axis_budget(
            mesh, n_sp_layers=1, n_hybrid_layers=1,
            comm_strategy="ulysses", microbatches=1,
            backward="autodiff", zero1=plan.zero1_axis is not None)
        assert_axis_budget(txt, mesh, budget)
        counts = collective_axis_counts(txt, mesh)
        assert counts.get(("all-to-all", (MODEL_AXIS,)), 0) == 4, \
            (dims, counts)


@check("ulysses hybrid wire bytes < allgather K/V bytes at tp=2 (tape)")
def _():
    """The reason ulysses exists: on the (2,2,2) split the hybrid
    layer's forward exchange (2 All-to-Alls + the residual 2-wide K/V
    sequence gathers) moves fewer wire bytes than gathering K/V across
    all 4 context ranks. Forward-only lowerings so both tapes cover the
    same legs (allgather's autodiff backward is JAX-generated, untaped).
    Holds at the smoke config's 2:1 GQA ratio — see
    docs/communication.md for where extreme GQA flips it."""
    from repro.comm import tape
    from repro.models import model as M

    params = M.init_params(jax.random.PRNGKey(0), _cfg3d)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0,
                                _cfg3d.vocab_size)

    def hybrid_bytes(strategy, prefix):
        mesh, plan = _plan3d((2, 2, 2), strategy)

        def fwd(p, t):
            logits, _ = M.forward(p, t, _cfg3d, plan, remat="none")
            return logits

        with tape() as recs:
            jax.jit(_shard_map(
                fwd, mesh=mesh,
                in_specs=(P(), P(DATA_AXIS, (SEQ_AXIS, MODEL_AXIS))),
                out_specs=P(DATA_AXIS, (SEQ_AXIS, MODEL_AXIS), None),
                axis_names=set(plan.manual_axes),
                check_vma=False)).lower(params, tokens)
        return sum(r.traffic_bytes for r in recs
                   if r.tag.startswith(prefix))

    uly = hybrid_bytes("ulysses", "ulysses.")
    ag = hybrid_bytes("allgather", "lasp2h.")
    assert 0 < uly < ag, (uly, ag)


if __name__ == "__main__":
    extra = f" ({len(SKIPPED)} base checks skipped: 2D-only)" \
        if SKIPPED else ""
    print(f"ALL {len(PASSED)} DISTRIBUTED CHECKS PASSED{extra}")
