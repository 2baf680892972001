"""Plain reference of the benchmark's configurations, in float32.

Straight ``jax.numpy`` over the weight dict of ``bench.weights``: no
kernel, no cache, no batching, nothing imported from the program. Every
matrix product runs at ``precision="highest"`` (on a TPU a float32 product
is otherwise computed in bf16 passes).

The architecture, per layer (pre-norm residual):

    h = rmsnorm(x) · ln1
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv      (split into heads)
    q, k = rope(q), rope(k);  q = q / sqrt(dh)
    o_t = Σ_{s ≤ t, doc(s) = doc(t)} (q_t · k_s) v_s   (basic linear attention)
    x = x + o Wo
    h = rmsnorm(x) · ln2
    x = x + (silu(h W1) ⊙ h W3) W2

and ``logits = rmsnorm(x) · final_norm · lm_headᵀ`` over the vocabulary
(the padded rows of the head are left out).

Departures from the published Qwen1.5-1.8B, all of them the paper's or
the deployment's: softmax attention is replaced by linear attention
(identity feature map, no decay, no normalisation) as the Linear-X recipe
does; RoPE is applied to q and k before the linear attention, with the
rotate-half convention and positions counted over the whole packed row;
documents packed into a row do not see each other (the state is reset at
each document start); the vocabulary is this chip's slice.

Linear attention is computed in chunks of ``CHUNK`` tokens: a masked
score matrix inside the chunk and a carried ``dk × dv`` state between
chunks, so 65k-token rows fit. The loss is taken over blocks of rows.

``precision="fp8"`` is the control: every matrix product takes its two
operands through float8 (e4m3, one scale per tensor) first, with the
gradient passed straight through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 256
LOSS_ROWS = 2048


def _q8(x):
    """Quantise-dequantise through float8 e4m3 with one scale per tensor;
    the gradient passes straight through."""
    def q(t):
        s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 448.0
        return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q(x) - x)


def _ein(spec, a, b, precision):
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, pos, theta):
    """x: (S, H, dh); pos: (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def linear_attention(q, k, v, seg, precision):
    """o_t = Σ_{s ≤ t, seg_s = seg_t} (q_t·k_s) v_s. q, k: (S, H, dk);
    v: (S, H, dv); seg: (S,) document ids. S is a multiple of CHUNK."""
    s, h, dk = q.shape
    dv = v.shape[-1]
    n = s // CHUNK
    qc, kc, vc = (t.reshape(n, CHUNK, h, t.shape[-1]) for t in (q, k, v))
    sc = seg.reshape(n, CHUNK)
    causal = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))

    def chunk(carry, xs):
        m, seg_m = carry
        qi, ki, vi, si = xs
        mask = causal & (si[:, None] == si[None, :])
        a = _ein("ihd,jhd->hij", qi, ki, precision) * mask
        o = _ein("hij,jhd->ihd", a, vi, precision)
        inter = _ein("ihk,hkv->ihv", qi, m, precision)
        o = o + jnp.where((si == seg_m)[:, None, None], inter, 0.0)
        last = si[-1]
        kin = ki * (si == last)[:, None, None]
        m = jnp.where(last == seg_m, m, 0.0) + _ein("jhk,jhv->hkv", kin, vi,
                                                    precision)
        return (m, last), o

    m0 = jnp.zeros((h, dk, dv), jnp.float32)
    _, o = jax.lax.scan(chunk, (m0, seg[0]), (qc, kc, vc, sc))
    return o.reshape(s, h, dv)


def _layer(c, precision, x, lw, pos, seg):
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    hq, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    s = x.shape[0]
    mm = functools.partial(_ein, "sd,df->sf", precision=precision)
    h = rmsnorm(x, lw["ln1"], eps)
    q, k, v = mm(h, lw["wq"]), mm(h, lw["wk"]), mm(h, lw["wv"])
    if c["qkv_bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = rope(q.reshape(s, hq, dh), pos, theta) * dh ** -0.5
    k = rope(k.reshape(s, hkv, dh), pos, theta)
    v = v.reshape(s, hkv, dh)
    if hkv != hq:
        k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    o = linear_attention(q, k, v, seg, precision).reshape(s, hq * dh)
    x = x + mm(o, lw["wo"])
    h = rmsnorm(x, lw["ln2"], eps)
    return x + mm(jax.nn.silu(mm(h, lw["w1"])) * mm(h, lw["w3"]), lw["w2"])


_LAYER = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2", "w1",
          "w3", "w2")


def hidden(w, tokens, pos, seg, c, precision="fp32"):
    """Final normed hidden states (S, d) of one row, in float32."""
    if any(m != "linear" for m in c["layer_pattern"]):
        raise NotImplementedError("the reference holds linear layers only")
    s = tokens.shape[0]
    pad = -s % CHUNK
    if pad:   # causal: rows appended at the end change nothing before
        tokens = jnp.pad(tokens, (0, pad))
        pos = jnp.pad(pos, (0, pad))
        seg = jnp.pad(seg, (0, pad), constant_values=-1)
    f32 = lambda t: t.astype(jnp.float32)
    x = f32(w["embed"])[tokens]
    layers = {n: f32(w[n]) for n in _LAYER if n in w}
    body = jax.checkpoint(
        lambda x_, lw: (_layer(c, precision, x_, lw, pos, seg), None))
    x, _ = jax.lax.scan(body, x, layers)
    return rmsnorm(x, f32(w["final_norm"]), c["rms_norm_eps"])[:s]


def logits(w, h, c, precision="fp32"):
    head = w["lm_head"][:c["vocab_size"]].astype(jnp.float32)
    return _ein("sd,vd->sv", h, head, precision)


def loss(w, tokens, labels, seg, c, precision="fp32"):
    """Mean cross-entropy over positions with ``labels >= 0``."""
    s = tokens.shape[0]
    h = hidden(w, tokens, jnp.arange(s), seg, c, precision)
    rows = min(LOSS_ROWS, s)
    n = -(-s // rows)
    pad = n * rows - s
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(n, rows, -1)
    lb = jnp.pad(labels, (0, pad), constant_values=-1).reshape(n, rows)

    @jax.checkpoint
    def block(hl):
        h_, l_ = hl
        lg = logits(w, h_, c, precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, jnp.maximum(l_, 0)[:, None], -1)[:, 0]
        return jnp.sum((lse - gold) * (l_ >= 0))

    total = jnp.sum(jax.lax.map(block, (hb, lb)))
    return total / jnp.maximum(jnp.sum(labels >= 0), 1)
