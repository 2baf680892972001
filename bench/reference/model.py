"""Plain reference of the benchmark's configurations, in float32.

Straight ``jax.numpy`` over the weight dict of ``bench.weights``: no
kernel, no cache, no batching, nothing imported from the program. Every
matrix product runs at ``precision="highest"`` (on a TPU a float32 product
is otherwise computed in bf16 passes).

The architecture, per layer (pre-norm residual), with the mixer and the
MLP of the layer's kinds (``bench/kinds``, one module each):

    x = x + mixer(rmsnorm(x) · ln1)
    x = x + mlp(rmsnorm(x) · ln2)

and ``logits = rmsnorm(x) · final_norm · lm_headᵀ`` over the vocabulary
(the padded rows of the head are left out). The pattern's positions are
applied in order, each layer under ``jax.checkpoint``, and the pattern is
scanned over its repeats. In a row longer than ``ROWS`` the MLP runs in
blocks of rows under ``jax.checkpoint`` (the linear mixer in blocks of
tokens with its state carried), and the loss always in blocks of
``LOSS_ROWS``, so that a 65k-token row fits one chip.

Departures from the published configurations, all of them the paper's
or the deployment's, are noted in each kind; the vocabulary is this
chip's slice.

``precision="fp8"`` is the control: every matrix product takes its two
operands through float8 (e4m3, one scale per tensor) first, with the
gradient passed straight through.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import kinds
from bench.weights import layer_leaves, leaf_name

CHUNK = 256
LOSS_ROWS = 2048
ROWS = 8192


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


def _rows(f, x):
    """``f`` of a position-wise part over the rows of ``x``: at once up to
    ``ROWS`` rows, else in blocks of ``ROWS`` under ``jax.checkpoint``, so
    that a long row's intermediates are held a block at a time."""
    s = x.shape[0]
    if s <= ROWS:
        return f(x)
    n = -(-s // ROWS)
    xb = jnp.pad(x, ((0, n * ROWS - s), (0, 0))).reshape(n, ROWS, -1)
    return jax.lax.map(jax.checkpoint(f), xb).reshape(n * ROWS, -1)[:s]


def _layer(c, spec, precision, x, lw, pos, seg):
    """One layer: the mixer and the MLP of its kinds, each pre-norm with a
    residual."""
    eps = c["rms_norm_eps"]
    mixer, mlp = kinds.kind(spec["mixer"]), kinds.kind(spec["mlp"])
    x = x + mixer.apply(c, lw, rmsnorm(x, lw["ln1"], eps), pos, seg,
                        precision)
    if "ln2" not in lw:
        return x
    return _rows(lambda x_: x_ + mlp.apply(c, lw, rmsnorm(x_, lw["ln2"],
                                                          eps), precision),
                 x)


def hidden(w, tokens, pos, seg, c, precision="fp32"):
    """Final normed hidden states (S, d) of one row, in float32."""
    s = tokens.shape[0]
    pad = -s % CHUNK
    if pad:   # causal: rows appended at the end change nothing before
        tokens = jnp.pad(tokens, (0, pad))
        pos = jnp.pad(pos, (0, pad))
        seg = jnp.pad(seg, (0, pad), constant_values=-1)
    f32 = lambda t: t.astype(jnp.float32)
    x = f32(w["embed"])[tokens]
    specs = kinds.pattern(c)
    layers = tuple({n: f32(w[leaf_name(c, p, n)]) for n in layer}
                   for p, layer in enumerate(layer_leaves(c)))

    def body(x_, lws):
        for spec, lw in zip(specs, lws):
            x_ = jax.checkpoint(lambda x1, lw1, spec=spec: _layer(
                c, spec, precision, x1, lw1, pos, seg))(x_, lw)
        return x_, None

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
