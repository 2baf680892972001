"""Pattern-based model builder: init / forward / prefill / decode.

The layer stack is ``cfg.pattern`` repeated ``cfg.n_groups`` times; params
for each pattern position are stacked over groups and the stack is applied
with ``lax.scan`` — keeping HLO size (and compile time) independent of
depth, which is what makes 80–100-layer dry-runs tractable.

Whisper-style encoder stacks and VLM image embeddings enter through
``aux_inputs`` (stub frontends per the assignment: ``input_specs`` provides
precomputed frame/patch embeddings).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import blocks
from repro.models.blocks import Ctx
from repro.models.layers import (embed_init, embed_lookup, logits_out,
                                 rmsnorm, rmsnorm_init, sinusoidal_positions)
from repro.obs.scopes import scope
from repro.sharding.rules import Parallelism, local_plan


def hymba_global_flags(cfg: ModelConfig):
    """Hymba keeps full attention in the first / middle / last layers.

    Used only for single-position dynamic hymba patterns; multi-position
    patterns mark globals statically via ``LayerSpec.is_global`` (which
    enables the banded sliding-window fast path — §Perf)."""
    if not (len(cfg.pattern) == 1 and cfg.pattern[0].mixer == "hymba"):
        return None
    n = cfg.n_layers
    flags = jnp.zeros((cfg.n_groups, len(cfg.pattern)), bool)
    for layer in (0, n // 2, n - 1):
        g, p = divmod(layer, len(cfg.pattern))
        flags = flags.at[g, p].set(True)
    return flags


def _stack_init(key, cfg: ModelConfig, specs, n_groups: int):
    """Stacked layer params: one pytree per pattern position, leading dim G."""
    out = []
    for i, spec in enumerate(specs):
        keys = jax.random.split(jax.random.fold_in(key, i), n_groups)
        out.append(jax.vmap(lambda k: blocks.layer_init(k, cfg, spec))(keys))
    return out


def init_params(key, cfg: ModelConfig):
    k_embed, k_blocks, k_enc = jax.random.split(key, 3)
    params = {
        "embed": embed_init(k_embed, cfg.padded_vocab, cfg.d_model,
                            tie=cfg.tie_embeddings),
        "groups": _stack_init(k_blocks, cfg, cfg.pattern, cfg.n_groups),
        "final_norm": rmsnorm_init(cfg.d_model),
    }
    if cfg.encoder is not None:
        enc_spec = (LayerSpec(mixer="softmax", mlp="dense"),)
        params["encoder"] = {
            "groups": _stack_init(k_enc, cfg, enc_spec,
                                  cfg.encoder.n_layers),
            "final_norm": rmsnorm_init(cfg.d_model),
        }
    return params


def _apply_stack(groups, x, ctx: Ctx, specs, flags=None, remat="full",
                 unroll=False):
    """Scan the stacked layers. Returns (x, summed aux losses)."""

    def apply_one(i, spec, p, x_):
        return blocks.layer_apply(p, x_, ctx, spec)

    if remat == "full" and len(specs) > 1:
        # nested per-layer remat: without it the whole multi-position body
        # recomputes as ONE block and its transient live-set scales with
        # the pattern length (measured 20 GiB vs 6 GiB on hymba×train_4k)
        apply_one = jax.checkpoint(
            apply_one, policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=(0, 1))

    def body(carry, xs):
        x_ = carry
        layer_params = xs[:-1] if flags is not None else xs
        f = xs[-1] if flags is not None else None
        aux = 0.0
        for i, spec in enumerate(specs):
            if f is not None:
                ctx.is_global = f[i]
            x_, a = apply_one(i, spec, layer_params[i], x_)
            aux = aux + a
        return x_, aux

    if remat == "full":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        # §Perf: keep matmul outputs — avoids recomputing attention scores
        # and projections in the backward pass at the cost of activation
        # memory (measured per-cell; see EXPERIMENTS.md §Perf).
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots)
    xs = tuple(groups) + ((flags,) if flags is not None else ())
    with scope("layers"):
        x, auxs = jax.lax.scan(body, x, xs, unroll=True if unroll else 1)
    return x, jnp.sum(auxs)


def forward(params, tokens, cfg: ModelConfig, plan: Optional[Parallelism]
            = None, *, img_emb=None, enc_frames=None, causal=True,
            remat="full", resets=None, unroll=False):
    """Full-sequence forward → (logits, aux). tokens: (B, S) int32."""
    plan = plan or local_plan()
    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    with scope("embed"):
        x = embed_lookup(params["embed"], tokens, dtype)
        x = plan.act(x, "batch", "residual_seq", None)
    positions = jnp.arange(s)
    if plan.sp is not None and plan.sp.manual and plan.sp.degree > 1:
        # Inside the train step's fully-manual shard_map ``s`` is the
        # per-rank sequence chunk; RoPE needs absolute positions. On a 3D
        # mesh the chunk index spans the combined (sequence, model) axes.
        positions = plan.sp.chunk_index() * s + positions

    enc_out = None
    if cfg.encoder is not None:
        if enc_frames is None:
            raise ValueError("whisper-style model needs enc_frames")
        enc_out = encode(params, enc_frames, cfg, plan, remat=remat,
                         unroll=unroll)

    flags = hymba_global_flags(cfg) \
        if any(sp.mixer == "hymba" for sp in cfg.pattern) else None
    ctx = Ctx(cfg=cfg, plan=plan, positions=positions, img_emb=img_emb,
              enc_out=enc_out, causal=causal, resets=resets)
    x, aux = _apply_stack(params["groups"], x, ctx, cfg.pattern,
                          flags=flags, remat=remat, unroll=unroll)
    with scope("head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = logits_out(params["embed"], x, plan, cfg.vocab_size)
    return logits, aux


def encode(params, frames, cfg: ModelConfig, plan, *, remat="none",
           unroll=False):
    """Whisper-style bidirectional encoder over (stub) frame embeddings."""
    dtype = jnp.dtype(cfg.dtype)
    x = frames.astype(dtype)
    x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(dtype)[None]
    enc_spec = (LayerSpec(mixer="softmax", mlp="dense"),)
    ctx = Ctx(cfg=cfg, plan=plan, positions=None, causal=False)
    x, _ = _apply_stack(params["encoder"]["groups"], x, ctx, enc_spec,
                        remat=remat, unroll=unroll)
    return rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def lm_loss_sum(logits, labels):
    """Unnormalized masked CE: ``(ce_sum, n_valid, lse)`` over positions
    with label >= 0. Shared by :func:`lm_loss` (local normalization) and
    the manual 2D DP×SP step (``repro.train.step``), which sums across
    shards BEFORE normalizing — keeping the two loss paths one math."""
    lf = logits.astype(jnp.float32)
    mask = labels >= 0
    lab = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, lab[..., None], axis=-1)[..., 0]
    ce_sum = jnp.sum((lse - gold) * mask)
    return ce_sum, jnp.sum(mask), lse * mask


def lm_loss(logits, labels, *, z_coef=0.0):
    """Mean CE over positions with label >= 0 (+ optional z-loss)."""
    ce_sum, n_valid, lse_masked = lm_loss_sum(logits, labels)
    n = jnp.maximum(n_valid, 1)
    loss = ce_sum / n
    if z_coef:
        loss = loss + z_coef * jnp.sum(lse_masked ** 2) / n
    return loss


# ---------------------------------------------------------------------------
# Decode (single token, cached)
# ---------------------------------------------------------------------------

def pad_safe(cfg: ModelConfig) -> bool:
    """True if left-padded (length-bucketed) prefill is exact for this
    config: every mixer is recurrent (state reset erases filler) and MLPs
    are position-wise (no cross-token routing). With qkv biases, filler
    columns turn nonzero after the first linear layer, so a downstream
    mamba causal-conv could leak them into the first real tokens — exclude
    that combination."""
    mixers = {sp.mixer for sp in cfg.pattern}
    if not all(sp.mixer in ("linear", "mamba2") and sp.mlp != "moe"
               for sp in cfg.pattern):
        return False
    return not (cfg.qkv_bias and "mamba2" in mixers)

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Decode cache: per linear/SSM layer a constant-size fp32 state (+
    cumulative log decay), per softmax layer a ring-buffer KV cache (ring =
    sliding window for the windowed layers of LASP-2H hybrids). ``pos`` is
    per-row — rows of a continuously-batched decode sit at different
    offsets."""
    caches = []
    for spec in cfg.pattern:
        c = blocks.layer_cache(cfg, spec, batch, max_len)
        caches.append(jax.tree.map(
            lambda x: jnp.zeros((cfg.n_groups,) + x.shape, x.dtype), c))
    return {"layers": caches, "pos": jnp.zeros((batch,), jnp.int32)}


def decode_step(params, token, cache, cfg: ModelConfig,
                plan: Optional[Parallelism] = None, *, img_emb=None,
                enc_out=None, unroll=False):
    """One decode step. token: (B,) int32 → (logits (B, V), new cache).

    ``cache["pos"]`` may be a scalar (legacy, all rows aligned) or a (B,)
    vector of per-row positions (continuous batching). No prefix re-scan:
    linear/SSM layers advance their recurrent state by one
    ``recurrent_step``, softmax layers write one ring slot."""
    plan = plan or local_plan()
    dtype = jnp.dtype(cfg.dtype)
    pos = cache["pos"]
    x = embed_lookup(params["embed"], token[:, None], dtype)
    x = plan.act(x, "batch", None, None)

    # RoPE positions: (1,) broadcast for scalar pos, else per-row (B, 1).
    positions = pos[None] if jnp.ndim(pos) == 0 else pos[:, None]
    flags = hymba_global_flags(cfg) \
        if any(sp.mixer == "hymba" for sp in cfg.pattern) else None
    ctx = Ctx(cfg=cfg, plan=plan, positions=positions,
              img_emb=img_emb, enc_out=enc_out, causal=True,
              decode_pos=pos)

    def body(carry, xs):
        x_ = carry
        n = len(cfg.pattern)
        layer_params = xs[:n]
        layer_caches = xs[n:2 * n]
        f = xs[-1] if flags is not None else None
        new_caches = []
        for i, spec in enumerate(cfg.pattern):
            if f is not None:
                ctx.is_global = f[i]
            x_, nc = blocks.layer_decode(layer_params[i], x_,
                                         layer_caches[i], ctx, spec)
            new_caches.append(nc)
        return x_, tuple(new_caches)

    xs = tuple(params["groups"]) + tuple(cache["layers"]) \
        + ((flags,) if flags is not None else ())
    x, new_layer_caches = jax.lax.scan(body, x, xs,
                                       unroll=True if unroll else 1)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], x, plan, cfg.vocab_size)
    new_cache = {"layers": list(new_layer_caches), "pos": pos + 1}
    return logits[:, 0, :], new_cache


# ---------------------------------------------------------------------------
# Prefill (full prompt → cache)
# ---------------------------------------------------------------------------

def prefill(params, tokens, cfg: ModelConfig,
            plan: Optional[Parallelism] = None, *, max_len=None,
            img_emb=None, enc_frames=None, unroll=False, pad_lens=None):
    """Run the prompt, returning (logits of last position, decode cache).

    Implemented as forward + a per-layer cache-extraction pass; the mixers'
    prefill paths reuse the exact same chunked-scan kernels as forward
    (tested equal to running decode token-by-token), and the final
    per-layer recurrent states land directly in the cache.

    ``pad_lens`` (B,) enables length-bucketed batched prefill for pure
    linear/SSM stacks: row ``b`` is LEFT-padded with ``pad_lens[b]`` filler
    tokens, per-row positions start at ``-pad_lens[b]`` so real tokens sit
    at 0..L-1, and a state reset (``RESET_LOG_A``) at the first real token
    erases the filler's contribution to the recurrent state. Only valid
    when no layer does softmax attention over the text sequence (softmax
    layers would attend the filler).
    """
    plan = plan or local_plan()
    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    max_len = max_len or s
    x = embed_lookup(params["embed"], tokens, dtype)
    x = plan.act(x, "batch", "seq", None)
    resets = None
    if pad_lens is not None:
        if not pad_safe(cfg):
            raise ValueError(
                "pad_lens prefill requires a pure linear/SSM stack with "
                "dense MLPs (softmax layers would attend the filler; MoE "
                "routing lets filler tokens steal expert capacity)")
        cols = jnp.arange(s)[None, :]
        positions = cols - pad_lens[:, None]                     # (B, S)
        resets = cols == pad_lens[:, None]
        # Zero filler embeddings so the mamba causal-conv sees the same
        # zeros it would for an unpadded sequence start; linear-state
        # leakage is erased by the reset at the first real token.
        x = jnp.where((cols >= pad_lens[:, None])[..., None], x, 0)
    else:
        positions = jnp.arange(s)

    enc_out = None
    if cfg.encoder is not None:
        enc_out = encode(params, enc_frames, cfg, plan)

    flags = hymba_global_flags(cfg) \
        if any(sp.mixer == "hymba" for sp in cfg.pattern) else None
    ctx = Ctx(cfg=cfg, plan=plan, positions=positions, img_emb=img_emb,
              enc_out=enc_out, causal=True, resets=resets)

    def body(carry, xs):
        x_ = carry
        layer_params = xs[:-1] if flags is not None else xs
        f = xs[-1] if flags is not None else None
        caches = []
        for i, spec in enumerate(cfg.pattern):
            if f is not None:
                ctx.is_global = f[i]
            x_, c = blocks.layer_prefill(layer_params[i], x_, ctx, spec,
                                         max_len)
            caches.append(c)
        return x_, tuple(caches)

    xs = tuple(params["groups"]) + ((flags,) if flags is not None else ())
    x, layer_caches = jax.lax.scan(body, x, xs,
                                   unroll=True if unroll else 1)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_out(params["embed"], x[:, -1:, :], plan, cfg.vocab_size)
    pos = jnp.full((b,), s, jnp.int32)
    if pad_lens is not None:
        pos = pos - pad_lens            # per-row true prompt lengths
    cache = {"layers": list(layer_caches), "pos": pos}
    return logits[:, 0, :], cache
