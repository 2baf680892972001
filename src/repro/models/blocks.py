"""Transformer-layer bodies: mixers (softmax/linear/mamba2/hymba/cross) and
layer glue, with train/prefill (full-sequence) and decode (single-token +
cache) entry points.

Interface per mixer ``<kind>``:
  ``<kind>_init(key, cfg, spec) -> params``
  ``<kind>_apply(params, x, ctx) -> y``                  (full sequence)
  ``<kind>_decode(params, x, cache, ctx) -> (y, cache)`` (one token)
  ``<kind>_cache(cfg, spec, batch, max_len) -> cache``

``ctx`` is a :class:`Ctx` carrying the plan (sharding / SP), config,
positions, and modality inputs. All mixers consume/produce ``(B, S, d)``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.compat import shard_map as _shard_map

from repro.configs.base import LayerSpec, MambaConfig, ModelConfig
from repro.core import linear_attention as la_core
from repro.core.lasp2 import lasp2
from repro.core.lasp2h import (allgather_context_attention,
                               ring_decode_attention,
                               sharded_decode_attention)
from repro.kernels import ops
from repro.models.layers import dense_init, mlp_apply, mlp_init, normal, \
    rmsnorm, rmsnorm_init, rope
from repro.obs.scopes import scope
from repro.sharding.rules import Parallelism


@dataclass
class Ctx:
    cfg: ModelConfig
    plan: Parallelism
    positions: Any = None          # (S,) or (B, S) global positions
    img_emb: Any = None            # (B, n_img, d) stub patch embeddings
    enc_out: Any = None            # (B, n_frames, d) encoder output
    is_global: Any = None          # hymba per-layer flag (traced scalar)
    causal: bool = True
    decode_pos: Any = None         # scalar position during decode
    resets: Any = None             # (B, S) document-start flags (packing)


def _heads_split(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim).transpose(0, 2, 1, 3)


def _heads_merge(x):
    b, h, s, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * dh)


# ===========================================================================
# Softmax (GQA) attention mixer
# ===========================================================================

def softmax_init(key, cfg: ModelConfig, spec: LayerSpec):
    d, dh = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], d, cfg.n_heads * dh),
         "wk": dense_init(ks[1], d, cfg.n_kv_heads * dh),
         "wv": dense_init(ks[2], d, cfg.n_kv_heads * dh),
         "wo": dense_init(ks[3], cfg.n_heads * dh, d)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * dh,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * dh,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * dh,), jnp.float32)
    return p


def _qkv(p, x, cfg, positions=None):
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = _heads_split(q, cfg.n_heads, cfg.head_dim)
    k = _heads_split(k, cfg.n_kv_heads, cfg.head_dim)
    v = _heads_split(v, cfg.n_kv_heads, cfg.head_dim)
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def softmax_apply(params, x, ctx: Ctx, *, window=None, kv_override=None):
    cfg, plan = ctx.cfg, ctx.plan
    q, k, v = _qkv(params, x, cfg, ctx.positions)
    if kv_override is not None:
        k, v = kv_override
    q = plan.act(q, "batch", "heads", "seq", None)
    sp = plan.sp_for(q.shape[-2])
    s_len = q.shape[-2]
    banded_ok = (plan.banded_windows and isinstance(window, int)
                 and ctx.causal and s_len % window == 0
                 and not (sp is not None and sp.manual)
                 and (sp is None or (s_len // sp.degree) % window == 0))
    if banded_ok:
        # §Perf: banded sliding-window attention — O(S·2w) scores instead
        # of O(S²). Under SP the chunked form shifts only the O(w·d) halo
        # across shards; see banded_attention_chunked for why neither the
        # naive global block shift nor shard_map ppermute is used.
        from repro.core.lasp2h import banded_attention_chunked
        nc = sp.degree if sp is not None else 1
        o = banded_attention_chunked(q, k, v, window, nc)
    elif sp is not None and sp.comm.strategy == "ulysses":
        # LASP-2H × Ulysses: All-to-All head-parallel repartition instead
        # of the K/V gather (docs/communication.md §Ulysses).
        from repro.core.lasp2h import ulysses_context_attention
        o = ulysses_context_attention(
            q, k, v, sp=sp, causal=ctx.causal, sliding_window=window)
    elif sp is not None:
        # LASP-2H: AllGather-based context parallelism (paper Alg. 7).
        o = allgather_context_attention(
            q, k, v, sp=sp, causal=ctx.causal, sliding_window=window)
    else:
        o = ops.flash_attention_op(q, k, v, causal=ctx.causal,
                                   sliding_window=window,
                                   backend=plan.backend)
    o = _heads_merge(o)
    return o @ params["wo"].astype(x.dtype)


def softmax_ring_len(spec: LayerSpec, max_len: int) -> int:
    """Ring-buffer length for a softmax layer's decode KV cache.

    Sliding-window layers (the softmax layers of LASP-2H hybrids) only ever
    attend the last ``window`` tokens, so the cache holds exactly that many
    slots — constant in context length. Full-attention layers need the
    whole history."""
    if spec.sliding_window:
        return min(max_len, spec.sliding_window)
    return max_len


def _decode_positions(ctx: Ctx, batch: int):
    """Per-row decode positions (B,) — scalar positions broadcast (all rows
    at the same offset); vectors pass through (continuous batching)."""
    pos = ctx.decode_pos
    return jnp.broadcast_to(jnp.atleast_1d(pos), (batch,)).astype(jnp.int32)


def softmax_cache(cfg: ModelConfig, spec: LayerSpec, batch, max_len,
                  dtype=jnp.bfloat16, ring=None):
    r = ring if ring is not None else softmax_ring_len(spec, max_len)
    shape = (batch, cfg.n_kv_heads, r, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "kpos": jnp.full((batch, r), -1, jnp.int32)}


def softmax_prefill_cache(params, x, ctx: Ctx, max_len, ring=None):
    """Compute K/V for the prompt and place them in a fresh ring cache.

    Ring slot ``i`` receives the prompt token at the highest position
    ``p <= last`` with ``p % ring == i`` (the same ``slot = pos % ring``
    rule decode uses), tagged with its absolute position in ``kpos``.
    Handles per-row position offsets (left-padded length-bucketed prefill):
    padding columns carry negative positions and land as empty slots."""
    cfg = ctx.cfg
    _, k, v = _qkv(params, x, cfg, ctx.positions)
    b, s = x.shape[0], k.shape[2]
    r = ring if ring is not None else softmax_ring_len(ctx._spec, max_len)
    pos2d = jnp.broadcast_to(jnp.atleast_2d(ctx.positions),
                             (b, s)).astype(jnp.int32)
    last = pos2d[:, -1]                                   # (B,)
    i = jnp.arange(r)[None, :]                            # (1, R)
    p_i = last[:, None] - jnp.mod(last[:, None] - i, r)   # (B, R)
    col = jnp.clip(p_i - pos2d[:, :1], 0, s - 1)          # position -> column
    valid = p_i >= 0
    idx = col[:, None, :, None]
    kr = jnp.take_along_axis(k, idx, axis=2)
    vr = jnp.take_along_axis(v, idx, axis=2)
    kpos = jnp.where(valid, p_i, -1)
    kr = ctx.plan.act(kr, "batch", "kv_heads", "cache_seq", None)
    vr = ctx.plan.act(vr, "batch", "kv_heads", "cache_seq", None)
    return {"k": kr.astype(jnp.bfloat16), "v": vr.astype(jnp.bfloat16),
            "kpos": kpos}


def softmax_decode(params, x, cache, ctx: Ctx, *, window=None):
    cfg, plan = ctx.cfg, ctx.plan
    posv = _decode_positions(ctx, x.shape[0])             # (B,)
    q, k, v = _qkv(params, x, cfg, None)
    q = rope(q, posv[:, None], cfg.rope_theta)
    k = rope(k, posv[:, None], cfg.rope_theta)
    r = cache["k"].shape[2]
    hit = jnp.arange(r)[None, :] == jnp.mod(posv, r)[:, None]   # (B, R)
    kc = jnp.where(hit[:, None, :, None], k.astype(cache["k"].dtype),
                   cache["k"])
    vc = jnp.where(hit[:, None, :, None], v.astype(cache["v"].dtype),
                   cache["v"])
    kpos = jnp.where(hit, posv[:, None], cache["kpos"])
    kc = plan.act(kc, "batch", "kv_heads", "cache_seq", None)
    vc = plan.act(vc, "batch", "kv_heads", "cache_seq", None)
    sp = None
    if plan.decode_cache_axis is not None:
        from repro.core.lasp2 import SPConfig
        sp = SPConfig(mesh=plan.mesh, sp_axis=plan.decode_cache_axis)
    o = ring_decode_attention(q, kc, vc, kpos, posv,
                              sliding_window=window, sp=sp)
    o = _heads_merge(o)
    y = o @ params["wo"].astype(x.dtype)
    return y, {"k": kc, "v": vc, "kpos": kpos}


# ===========================================================================
# Linear attention mixer (the paper's module; LASP-2 under SP)
# ===========================================================================

def linear_init(key, cfg: ModelConfig, spec: LayerSpec):
    p = softmax_init(key, cfg, spec)
    if cfg.linear_attn.decay == "data":
        kg = jax.random.fold_in(key, 7)
        p["wdt"] = dense_init(kg, cfg.d_model, cfg.n_heads, scale=0.01)
    return p


def _linear_qkv(params, x, ctx: Ctx):
    cfg = ctx.cfg
    lac = cfg.linear_attn
    q, k, v = _qkv(params, x, cfg,
                   ctx.positions if lac.feature_map != "taylor" else None)
    # GQA → full heads for the linear recurrence (state is per q-head)
    rep = cfg.n_heads // cfg.n_kv_heads
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    q = la_core.feature_map(q, lac.feature_map)
    k = la_core.feature_map(k, lac.feature_map)
    q = q * (q.shape[-1] ** -0.5)
    if lac.decay == "data":
        gate = (x @ params["wdt"].astype(x.dtype)).astype(jnp.float32)
        log_a = jax.nn.log_sigmoid(gate).transpose(0, 2, 1)   # (B,H,S)
    elif lac.decay == "none":
        log_a = None
    else:
        b, _, s, _ = q.shape
        log_a = jnp.broadcast_to(
            la_core.decay_log_a(lac.decay, heads=cfg.n_heads, s=s)[None],
            (b, cfg.n_heads, s))
    if ctx.resets is not None:
        # Document packing (paper §A.4.2): zero the state at doc starts.
        b_, _, s_, _ = q.shape
        base = log_a if log_a is not None \
            else jnp.zeros((b_, cfg.n_heads, s_), jnp.float32)
        log_a = jnp.where(ctx.resets[:, None, :], la_core.RESET_LOG_A, base)
    return q, k, v, log_a


def linear_apply(params, x, ctx: Ctx):
    cfg, plan = ctx.cfg, ctx.plan
    lac = cfg.linear_attn
    q, k, v, log_a = _linear_qkv(params, x, ctx)
    q = plan.act(q, "batch", "heads", "seq", None)
    sp = plan.sp_for(q.shape[-2])
    if sp is not None:
        o = lasp2(q, k, v, log_a, sp=sp, causal=ctx.causal,
                  block_size=lac.block_size,
                  backward="autodiff" if lac.decay == "data"
                  or ctx.resets is not None else lac.backward)
    elif ctx.causal:
        o, _, _ = ops.linear_attention_op(q, k, v, log_a,
                                          block_size=lac.block_size,
                                          backend=plan.backend)
    else:
        o = lasp2(q, k, v, log_a, sp=None, causal=False)
    o = _heads_merge(o.astype(x.dtype))
    return o @ params["wo"].astype(x.dtype)


def linear_cache(cfg: ModelConfig, spec: LayerSpec, batch, max_len):
    lac = cfg.linear_attn
    dk = cfg.head_dim
    if lac.feature_map == "taylor":
        dk = 1 + dk + dk * dk
    # Constant-size memory state — the paper's selling point: no KV cache.
    # The cumulative log decay rides along (fp32 scalar per head): it is
    # what prefill's chunk summaries emit and keeps the recurrent decode a
    # pure continuation of the chunked scan.
    return {"m": jnp.zeros((batch, cfg.n_heads, dk, cfg.head_dim),
                           jnp.float32),
            "log_decay": jnp.zeros((batch, cfg.n_heads), jnp.float32)}


def linear_decode(params, x, cache, ctx: Ctx):
    # ctx.positions carries the decode position → RoPE offset inside _qkv.
    q, k, v, log_a = _linear_qkv(params, x, ctx)   # S == 1
    o, m, ld = ops.linear_decode_op(
        q[..., 0, :], k[..., 0, :], v[..., 0, :],
        log_a[..., 0] if log_a is not None else None,
        cache["m"], cache["log_decay"], backend=ctx.plan.backend)
    o = _heads_merge(o[:, :, None, :].astype(x.dtype))
    y = o @ params["wo"].astype(x.dtype)
    return y, {"m": m, "log_decay": ld}


# ===========================================================================
# Mamba-2 (SSD) mixer — chunked decayed linear attention under the hood
# ===========================================================================

def _mamba_dims(cfg: ModelConfig, spec: LayerSpec):
    mb = cfg.mamba or MambaConfig()
    d_in = (mb.expand * cfg.d_model) if spec.mixer == "mamba2" \
        else cfg.d_model
    nh = d_in // mb.headdim
    return mb, d_in, nh


def mamba2_init(key, cfg: ModelConfig, spec: LayerSpec):
    mb, d_in, nh = _mamba_dims(cfg, spec)
    d = cfg.d_model
    ks = jax.random.split(key, 10)
    gd = mb.ngroups * mb.d_state
    p = {
        "wx": dense_init(ks[0], d, d_in),
        "wz": dense_init(ks[1], d, d_in),
        "wb": dense_init(ks[2], d, gd),
        "wc": dense_init(ks[3], d, gd),
        "wdt": dense_init(ks[4], d, nh, scale=0.01),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks[5], (nh,), jnp.float32,
                                       jnp.log(1e-3), jnp.log(1e-1))))),
        "a_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "conv_x": normal(ks[6], (mb.d_conv, d_in), 0.2),
        "conv_b": normal(ks[7], (mb.d_conv, gd), 0.2),
        "conv_c": normal(ks[8], (mb.d_conv, gd), 0.2),
        "gnorm": rmsnorm_init(d_in),
        "wo": dense_init(ks[9], d_in, d),
    }
    return p


def _causal_conv(x, w, cache=None):
    """Depthwise causal conv. x: (B, S, C); w: (K, C).

    Returns (y (B,S,C), new_cache (B, K-1, C)) — cache carries the last
    K-1 inputs for streaming decode.
    """
    k = w.shape[0]
    if cache is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = cache.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i].astype(x.dtype)
            for i in range(k))
    new_cache = xp[:, -(k - 1):, :] if k > 1 else None
    return jax.nn.silu(y), new_cache


def _mamba_core(p, x, ctx: Ctx, conv_caches=None):
    """Shared full-sequence/decode body. Returns q,k,v,log_a,(xh),caches."""
    cfg = ctx.cfg
    mb, d_in, nh = _mamba_dims(cfg, ctx._spec)
    dt_ = x.dtype
    xs = x @ p["wx"].astype(dt_)
    bs = x @ p["wb"].astype(dt_)
    cs = x @ p["wc"].astype(dt_)
    cc = conv_caches or {"x": None, "b": None, "c": None}
    xs, ccx = _causal_conv(xs, p["conv_x"], cc["x"])
    bs, ccb = _causal_conv(bs, p["conv_b"], cc["b"])
    cs, ccc = _causal_conv(cs, p["conv_c"], cc["c"])
    dt = jax.nn.softplus((x @ p["wdt"].astype(dt_)).astype(jnp.float32)
                         + p["dt_bias"])                     # (B,S,nh)
    log_a = (-jnp.exp(p["a_log"]) * dt).transpose(0, 2, 1)   # (B,nh,S)
    if ctx.resets is not None:
        log_a = jnp.where(ctx.resets[:, None, :], la_core.RESET_LOG_A,
                          log_a)
    xh = _heads_split(xs, nh, mb.headdim)                    # (B,nh,S,hd)
    v = xh * dt.transpose(0, 2, 1)[..., None].astype(dt_)
    rep = nh // mb.ngroups
    k = jnp.repeat(_heads_split(bs, mb.ngroups, mb.d_state), rep, axis=1)
    q = jnp.repeat(_heads_split(cs, mb.ngroups, mb.d_state), rep, axis=1)
    caches = {"x": ccx, "b": ccb, "c": ccc}
    return q, k, v, log_a, xh, caches


def mamba2_apply(params, x, ctx: Ctx):
    cfg, plan = ctx.cfg, ctx.plan
    mb, d_in, nh = _mamba_dims(cfg, ctx._spec)
    q, k, v, log_a, xh, _ = _mamba_core(params, x, ctx)
    q = plan.act(q, "batch", "heads", "seq", None)
    sp = plan.sp_for(q.shape[-2])
    if sp is not None:
        # SSD *is* decayed linear attention — LASP-2 applies exactly.
        y = lasp2(q, k, v, log_a, sp=sp,
                  block_size=cfg.linear_attn.block_size,
                  backward="autodiff")
    else:
        y, _, _ = ops.linear_attention_op(
            q, k, v, log_a, block_size=cfg.linear_attn.block_size,
            backend=plan.backend)
    y = y + params["d_skip"][None, :, None, None].astype(y.dtype) * xh
    y = _heads_merge(y.astype(x.dtype))
    z = x @ params["wz"].astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rmsnorm(params["gnorm"], y, cfg.norm_eps)
    return y @ params["wo"].astype(x.dtype)


def mamba2_cache(cfg: ModelConfig, spec: LayerSpec, batch, max_len):
    mb, d_in, nh = _mamba_dims(cfg, spec)
    gd = mb.ngroups * mb.d_state
    return {
        "m": jnp.zeros((batch, nh, mb.d_state, mb.headdim), jnp.float32),
        "log_decay": jnp.zeros((batch, nh), jnp.float32),
        "conv_x": jnp.zeros((batch, mb.d_conv - 1, d_in), jnp.bfloat16),
        "conv_b": jnp.zeros((batch, mb.d_conv - 1, gd), jnp.bfloat16),
        "conv_c": jnp.zeros((batch, mb.d_conv - 1, gd), jnp.bfloat16),
    }


def mamba2_decode(params, x, cache, ctx: Ctx):
    cfg = ctx.cfg
    conv_caches = {"x": cache["conv_x"], "b": cache["conv_b"],
                   "c": cache["conv_c"]}
    q, k, v, log_a, xh, cc = _mamba_core(params, x, ctx, conv_caches)
    y, m, ld = ops.linear_decode_op(
        q[..., 0, :], k[..., 0, :], v[..., 0, :], log_a[..., 0],
        cache["m"], cache["log_decay"], backend=ctx.plan.backend)
    y = y[:, :, None, :]
    y = y.astype(x.dtype) + params["d_skip"][None, :, None, None
                                             ].astype(x.dtype) * xh
    y = _heads_merge(y)
    z = x @ params["wz"].astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rmsnorm(params["gnorm"], y, cfg.norm_eps)
    y = y @ params["wo"].astype(x.dtype)
    new_cache = {"m": m, "log_decay": ld,
                 "conv_x": cc["x"].astype(jnp.bfloat16),
                 "conv_b": cc["b"].astype(jnp.bfloat16),
                 "conv_c": cc["c"].astype(jnp.bfloat16)}
    return y, new_cache


# ===========================================================================
# Hymba: parallel softmax-attention + SSM heads in one mixer
# ===========================================================================

def hymba_init(key, cfg: ModelConfig, spec: LayerSpec):
    k1, k2 = jax.random.split(key)
    return {"attn": softmax_init(k1, cfg, spec),
            "ssm": mamba2_init(k2, cfg, spec)}


def hymba_window(spec: LayerSpec, ctx: Ctx):
    """Static window when the pattern position is statically marked
    (enables the banded §Perf path); traced fallback when per-group
    flags are in play (single-position dynamic patterns)."""
    win = spec.sliding_window or 2048
    if ctx.is_global is not None:                 # dynamic mode
        return jnp.where(ctx.is_global, 1 << 30, win)
    return None if spec.is_global else win        # static mode


def hymba_apply(params, x, ctx: Ctx):
    window = hymba_window(ctx._spec, ctx)
    a = softmax_apply(params["attn"], x, ctx, window=window)
    s = mamba2_apply(params["ssm"], x, ctx)
    return 0.5 * (a + s)


def hymba_cache(cfg: ModelConfig, spec: LayerSpec, batch, max_len):
    # hymba's global/local switch can be a per-group traced flag (dynamic
    # single-position patterns), so the ring must cover the full length;
    # statically-local layers still get the windowed ring via the mask.
    return {"attn": softmax_cache(cfg, spec, batch, max_len, ring=max_len),
            "ssm": mamba2_cache(cfg, spec, batch, max_len)}


def hymba_decode(params, x, cache, ctx: Ctx):
    window = hymba_window(ctx._spec, ctx)
    a, ca = softmax_decode(params["attn"], x, cache["attn"], ctx,
                           window=window)
    s, cs = mamba2_decode(params["ssm"], x, cache["ssm"], ctx)
    return 0.5 * (a + s), {"attn": ca, "ssm": cs}


# ===========================================================================
# Cross-attention mixer (VLM image layers, Whisper decoder cross)
# ===========================================================================

def cross_init(key, cfg: ModelConfig, spec: LayerSpec):
    p = softmax_init(key, cfg, spec)
    p["gate"] = jnp.zeros((), jnp.float32)
    return p


def _cross_kv(params, memory, cfg):
    dt = memory.dtype
    k = _heads_split(memory @ params["wk"].astype(dt), cfg.n_kv_heads,
                     cfg.head_dim)
    v = _heads_split(memory @ params["wv"].astype(dt), cfg.n_kv_heads,
                     cfg.head_dim)
    return k, v


def cross_apply(params, x, ctx: Ctx):
    cfg, plan = ctx.cfg, ctx.plan
    memory = ctx.img_emb if ctx.img_emb is not None else ctx.enc_out
    dt = x.dtype
    q = _heads_split(x @ params["wq"].astype(dt), cfg.n_heads, cfg.head_dim)
    k, v = _cross_kv(params, memory.astype(dt), cfg)
    # memory is replicated across the SP group; each device attends its own
    # query chunk locally — no sequence communication needed.
    o = ops.flash_attention_op(q, k, v, causal=False, backend=plan.backend)
    o = _heads_merge(o)
    y = o @ params["wo"].astype(dt)
    return jnp.tanh(params["gate"]).astype(dt) * y


def cross_cache(cfg: ModelConfig, spec: LayerSpec, batch, max_len):
    n_mem = cfg.n_image_tokens or (cfg.encoder.n_frames if cfg.encoder else 0)
    shape = (batch, cfg.n_kv_heads, max(n_mem, 1), cfg.head_dim)
    return {"k": jnp.zeros(shape, jnp.bfloat16),
            "v": jnp.zeros(shape, jnp.bfloat16)}


def cross_prefill_cache(params, memory, cfg):
    k, v = _cross_kv(params, memory, cfg)
    return {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}


def cross_decode(params, x, cache, ctx: Ctx):
    cfg = ctx.cfg
    dt = x.dtype
    q = _heads_split(x @ params["wq"].astype(dt), cfg.n_heads, cfg.head_dim)
    o = sharded_decode_attention(q, cache["k"], cache["v"],
                                 cache["k"].shape[2], sp=None)
    o = _heads_merge(o.astype(dt))
    y = o @ params["wo"].astype(dt)
    return jnp.tanh(params["gate"]).astype(dt) * y, cache


# ===========================================================================
# MoE MLP (token-choice top-k with capacity; EP over the "model" axis)
# ===========================================================================

def moe_init(key, cfg: ModelConfig):
    moe = cfg.moe
    d, ff, e = cfg.d_model, cfg.d_ff, moe.num_experts
    ks = jax.random.split(key, 5)
    p = {"router": dense_init(ks[0], d, e, scale=0.02),
         "experts": {
             "w1": normal(ks[1], (e, d, ff), d ** -0.5),
             "w3": normal(ks[2], (e, d, ff), d ** -0.5),
             "w2": normal(ks[3], (e, ff, d), ff ** -0.5)}}
    if moe.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, ff * moe.n_shared_experts)
    return p


def _token_manual_axes(plan: Parallelism):
    """Mesh axes that shard the token (batch/seq) dims of activations."""
    axes = []
    for rule in (plan.rules.get("batch"), plan.rules.get("seq")):
        if rule is None:
            continue
        axes.extend(rule if isinstance(rule, (tuple, list)) else [rule])
    return tuple(dict.fromkeys(axes))


def moe_apply(params, x, ctx: Ctx):
    """Capacity-based token-choice routing (drop on overflow).

    §Perf (hillclimb #2): when the token dims are sharded and the expert
    weights are not FSDP-split, the dispatch runs inside a shard_map over
    the token axes — routing/scatter/combine are shard-LOCAL and only the
    expert computation crosses shards (auto-sharded over "model"). The
    naive global scatter instead makes GSPMD all-reduce the full
    (E·cap, d) buffer across data shards — measured 4.4 TB/step on
    moonshot×prefill_32k. Per-shard capacity semantics (standard practice).
    """
    cfg, plan = ctx.cfg, ctx.plan
    manual = _token_manual_axes(plan)
    if manual and plan.mesh is not None and plan.fsdp_axis is None:
        from repro.sharding.rules import fit_spec
        xspec = fit_spec(plan.mesh, x.shape,
                         P(plan.rules.get("batch"), plan.rules.get("seq"),
                           None))
        manual = _token_manual_axes(
            type(plan)(mesh=plan.mesh,
                       rules={"batch": xspec[0], "seq": xspec[1]}))
    if manual and plan.mesh is not None and plan.fsdp_axis is None:
        import copy
        import dataclasses as _dc
        pspec = jax.tree.map(lambda _: P(), params)
        # inside the shard_map only auto (non-manual) axes may appear in
        # sharding constraints — strip manual axes from the local rules
        def _strip(rule):
            if rule is None:
                return None
            axes = rule if isinstance(rule, (tuple, list)) else (rule,)
            kept = tuple(a for a in axes if a not in manual)
            return kept[0] if len(kept) == 1 else (kept or None)
        local_plan_ = _dc.replace(
            plan, rules={k: _strip(v) for k, v in plan.rules.items()})
        local_ctx = copy.copy(ctx)
        local_ctx.plan = local_plan_

        def body(params_, x_):
            y, aux = _moe_dispatch(params_, x_, local_ctx)
            return y, jax.lax.pmean(aux, manual)

        y, aux = _shard_map(
            body, mesh=plan.mesh, in_specs=(pspec, xspec),
            out_specs=(xspec, P()), axis_names=set(manual),
            check_vma=False)(params, x)
        return y, aux
    return _moe_dispatch(params, x, ctx)


def _moe_dispatch(params, x, ctx: Ctx):
    cfg, plan = ctx.cfg, ctx.plan
    moe = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = moe.num_experts, moe.top_k
    cap = int(moe.capacity_factor * t * k / e)
    cap = max(cap, k)

    xf = x.reshape(t, d)
    logits = (xf @ params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                  # (t, k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    flat_e = idx.reshape(-1)                             # (t*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (t*k, e)
    pos = jnp.cumsum(onehot, axis=0) - onehot            # slot per item
    slot = jnp.sum(pos * onehot, axis=-1)                # (t*k,)
    keep = slot < cap
    dest = jnp.where(keep, flat_e * cap + slot, e * cap)

    items = jnp.repeat(xf, k, axis=0)                    # (t*k, d)
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].add(items)
    buf = buf[:e * cap].reshape(e, cap, d)
    buf = plan.act(buf, "experts", None, None)

    dt_ = x.dtype
    h = jnp.einsum("ecd,edf->ecf", buf, params["experts"]["w1"].astype(dt_))
    g = jnp.einsum("ecd,edf->ecf", buf, params["experts"]["w3"].astype(dt_))
    h = jax.nn.silu(h) * g
    out = jnp.einsum("ecf,efd->ecd", h, params["experts"]["w2"].astype(dt_))
    out = plan.act(out, "experts", None, None)

    out_flat = jnp.concatenate(
        [out.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)], axis=0)
    y = out_flat[dest] * (gate.reshape(-1, 1).astype(x.dtype)
                          * keep[:, None].astype(x.dtype))
    y = y.reshape(t, k, d).sum(axis=1)

    # router z-loss / load-balance aux (stashed for the train loop)
    me = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1))
    ce = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(me * ce) \
        + moe.router_z_coef * jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2)
    y = y.reshape(b, s, d)
    if "shared" in params:
        y = y + mlp_apply(params["shared"], x, plan)
    return y, aux


# ===========================================================================
# Layer glue
# ===========================================================================

def layer_init(key, cfg: ModelConfig, spec: LayerSpec):
    ks = jax.random.split(key, 4)
    mix_init = {"softmax": softmax_init, "linear": linear_init,
                "mamba2": mamba2_init, "hymba": hymba_init,
                "cross": cross_init}[spec.mixer]
    p = {"ln1": rmsnorm_init(cfg.d_model),
         "mixer": mix_init(ks[0], cfg, spec)}
    if spec.mlp == "dense":
        p["ln2"] = rmsnorm_init(cfg.d_model)
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                            act=getattr(cfg, "mlp_act", "swiglu"))
    elif spec.mlp == "moe":
        p["ln2"] = rmsnorm_init(cfg.d_model)
        p["mlp"] = moe_init(ks[1], cfg)
    return p


def layer_apply(params, x, ctx: Ctx, spec: LayerSpec):
    ctx._spec = spec
    mix_apply = {"softmax": softmax_apply, "linear": linear_apply,
                 "mamba2": mamba2_apply, "hymba": hymba_apply,
                 "cross": cross_apply}[spec.mixer]
    with scope(f"mixer.{spec.mixer}"):
        h = rmsnorm(params["ln1"], x, ctx.cfg.norm_eps)
        if spec.mixer == "softmax":
            y = mix_apply(params["mixer"], h, ctx,
                          window=spec.sliding_window)
        else:
            y = mix_apply(params["mixer"], h, ctx)
    x = x + y
    aux = 0.0
    if "mlp" in params:
        with scope("mlp"):
            h = rmsnorm(params["ln2"], x, ctx.cfg.norm_eps)
            if spec.mlp == "moe":
                y, aux = moe_apply(params["mlp"], h, ctx)
            else:
                y = mlp_apply(params["mlp"], h, ctx.plan,
                              act=getattr(ctx.cfg, "mlp_act", "swiglu"))
        x = x + y
    x = ctx.plan.act(x, "batch", "residual_seq", None)
    return x, aux


def layer_cache(cfg: ModelConfig, spec: LayerSpec, batch, max_len):
    mk = {"softmax": softmax_cache, "linear": linear_cache,
          "mamba2": mamba2_cache, "hymba": hymba_cache,
          "cross": cross_cache}[spec.mixer]
    return {"mixer": mk(cfg, spec, batch, max_len)}


def _softmax_prefill(params, x, ctx: Ctx, spec: LayerSpec, max_len):
    y = softmax_apply(params, x, ctx, window=spec.sliding_window)
    cache = softmax_prefill_cache(params, x, ctx, max_len,
                                  ring=softmax_ring_len(spec, max_len))
    return y, cache


def _linear_prefill(params, x, ctx: Ctx, spec: LayerSpec, max_len):
    from repro.core.lasp2 import lasp2_with_state
    cfg, plan = ctx.cfg, ctx.plan
    q, k, v, log_a = _linear_qkv(params, x, ctx)
    b, h = q.shape[0], q.shape[1]
    sp = plan.sp_for(q.shape[-2])
    if sp is not None:
        o, m = lasp2_with_state(q, k, v, log_a, sp=sp,
                                block_size=cfg.linear_attn.block_size)
    else:
        o, m, _ = ops.linear_attention_op(
            q, k, v, log_a, block_size=cfg.linear_attn.block_size,
            backend=plan.backend)
    y = _heads_merge(o.astype(x.dtype)) @ params["wo"].astype(x.dtype)
    ld = (jnp.sum(log_a.astype(jnp.float32), axis=-1) if log_a is not None
          else jnp.zeros((b, h), jnp.float32))
    return y, {"m": m, "log_decay": ld}


def _mamba2_prefill(params, x, ctx: Ctx, spec: LayerSpec, max_len):
    from repro.core.lasp2 import lasp2_with_state
    cfg, plan = ctx.cfg, ctx.plan
    q, k, v, log_a, xh, cc = _mamba_core(params, x, ctx)
    sp = plan.sp_for(q.shape[-2])
    if sp is not None:
        y, m = lasp2_with_state(q, k, v, log_a, sp=sp,
                                block_size=cfg.linear_attn.block_size)
    else:
        y, m, _ = ops.linear_attention_op(
            q, k, v, log_a, block_size=cfg.linear_attn.block_size,
            backend=plan.backend)
    y = y + params["d_skip"][None, :, None, None].astype(y.dtype) * xh
    y = _heads_merge(y.astype(x.dtype))
    z = x @ params["wz"].astype(x.dtype)
    y = rmsnorm(params["gnorm"], y * jax.nn.silu(z), cfg.norm_eps)
    y = y @ params["wo"].astype(x.dtype)
    cache = {"m": m,
             "log_decay": jnp.sum(log_a.astype(jnp.float32), axis=-1),
             "conv_x": cc["x"].astype(jnp.bfloat16),
             "conv_b": cc["b"].astype(jnp.bfloat16),
             "conv_c": cc["c"].astype(jnp.bfloat16)}
    return y, cache


def _hymba_prefill(params, x, ctx: Ctx, spec: LayerSpec, max_len):
    window = hymba_window(spec, ctx)
    a = softmax_apply(params["attn"], x, ctx, window=window)
    ca = softmax_prefill_cache(params["attn"], x, ctx, max_len,
                               ring=max_len)
    s, cs = _mamba2_prefill(params["ssm"], x, ctx, spec, max_len)
    return 0.5 * (a + s), {"attn": ca, "ssm": cs}


def _cross_prefill(params, x, ctx: Ctx, spec: LayerSpec, max_len):
    y = cross_apply(params, x, ctx)
    memory = ctx.img_emb if ctx.img_emb is not None else ctx.enc_out
    cache = cross_prefill_cache(params, memory.astype(x.dtype), ctx.cfg)
    return y, cache


def layer_prefill(params, x, ctx: Ctx, spec: LayerSpec, max_len):
    ctx._spec = spec
    mix_pre = {"softmax": _softmax_prefill, "linear": _linear_prefill,
               "mamba2": _mamba2_prefill, "hymba": _hymba_prefill,
               "cross": _cross_prefill}[spec.mixer]
    h = rmsnorm(params["ln1"], x, ctx.cfg.norm_eps)
    y, mc = mix_pre(params["mixer"], h, ctx, spec, max_len)
    x = x + y
    if "mlp" in params:
        h = rmsnorm(params["ln2"], x, ctx.cfg.norm_eps)
        if spec.mlp == "moe":
            y, _ = moe_apply(params["mlp"], h, ctx)
        else:
            y = mlp_apply(params["mlp"], h, ctx.plan,
                          act=getattr(ctx.cfg, "mlp_act", "swiglu"))
        x = x + y
    x = ctx.plan.act(x, "batch", "residual_seq", None)
    return x, {"mixer": mc}


def layer_decode(params, x, cache, ctx: Ctx, spec: LayerSpec):
    ctx._spec = spec
    mix_dec = {"softmax": softmax_decode, "linear": linear_decode,
               "mamba2": mamba2_decode, "hymba": hymba_decode,
               "cross": cross_decode}[spec.mixer]
    h = rmsnorm(params["ln1"], x, ctx.cfg.norm_eps)
    if spec.mixer == "softmax":
        y, mc = mix_dec(params["mixer"], h, cache["mixer"], ctx,
                        window=spec.sliding_window)
    else:
        y, mc = mix_dec(params["mixer"], h, cache["mixer"], ctx)
    x = x + y
    if "mlp" in params:
        h = rmsnorm(params["ln2"], x, ctx.cfg.norm_eps)
        if spec.mlp == "moe":
            y, _ = moe_apply(params["mlp"], h, ctx)
        else:
            y = mlp_apply(params["mlp"], h, ctx.plan,
                          act=getattr(ctx.cfg, "mlp_act", "swiglu"))
        x = x + y
    return x, {"mixer": mc}
