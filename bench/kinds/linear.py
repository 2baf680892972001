"""Basic linear attention, the Linear-X recipe's mixer, with q/k/v biases
and RoPE (paper §4.1).

    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv      (split into heads)
    q, k = rope(q), rope(k);  q = q / sqrt(dh)
    o_t = Σ_{s ≤ t, doc(s) = doc(t)} (q_t · k_s) v_s
    out = o Wo

Identity feature map, no decay, no normalisation. RoPE uses the
rotate-half convention with positions counted over the whole packed row;
documents packed into a row do not see each other (the state is reset at
each document start). KV heads, where fewer, are repeated to the query
heads.

Linear attention is computed in chunks of ``CHUNK`` tokens: a masked
score matrix inside the chunk and a carried ``dk × dv`` state between
chunks, so 65k-token rows fit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import model as R
from bench.reference.model import CHUNK, _ein, rope

PROGRAM = "linear"

_LEAVES = ("wq", "wk", "wv", "wo", "bq", "bk", "bv")


def weights(c: dict) -> dict:
    d = c["hidden_size"]
    hq = c["num_attention_heads"] * c["head_dim"]
    hkv = c["num_key_value_heads"] * c["head_dim"]
    out = {"wq": ((d, hq), "matrix"), "wk": ((d, hkv), "matrix"),
           "wv": ((d, hkv), "matrix"), "wo": ((hq, d), "matrix")}
    if c["qkv_bias"]:
        out.update(bq=((hq,), "bias"), bk=((hkv,), "bias"),
                   bv=((hkv,), "bias"))
    return out


def to_program(lw: dict) -> dict:
    return {n: lw[n] for n in _LEAVES if n in lw}


def from_program(tree: dict) -> dict:
    return {n: tree[n] for n in _LEAVES if n in tree}


def linear_attention(q, k, v, seg, precision, carry=None):
    """o_t = Σ_{s ≤ t, seg_s = seg_t} (q_t·k_s) v_s. q, k: (S, H, dk);
    v: (S, H, dv); seg: (S,) document ids. S is a multiple of CHUNK.

    ``carry``, the state and the document of the last token before the
    row, continues an earlier part of it; the carry after the row is
    returned with the output."""
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

    if carry is None:
        carry = (jnp.zeros((h, dk, dv), jnp.float32), seg[0])
    carry, o = jax.lax.scan(chunk, carry, (qc, kc, vc, sc))
    return carry, o.reshape(s, h, dv)


def _mix(c, lw, h, pos, seg, precision, carry=None):
    theta = c["rope_theta"]
    hq, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    s = h.shape[0]
    mm = functools.partial(_ein, "sd,df->sf", precision=precision)
    q, k, v = mm(h, lw["wq"]), mm(h, lw["wk"]), mm(h, lw["wv"])
    if c["qkv_bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    q = rope(q.reshape(s, hq, dh), pos, theta) * dh ** -0.5
    k = rope(k.reshape(s, hkv, dh), pos, theta)
    v = v.reshape(s, hkv, dh)
    if hkv != hq:
        k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    carry, o = linear_attention(q, k, v, seg, precision, carry)
    return carry, mm(o.reshape(s, hq * dh), lw["wo"])


def apply(c, lw, h, pos, seg, precision):
    """A row longer than ``bench.reference.model.ROWS`` runs in blocks of
    that many tokens, each under ``jax.checkpoint`` with the state carried
    from block to block, so that the backward pass holds one block's
    intermediates at a time."""
    s = h.shape[0]
    if s <= R.ROWS:
        return _mix(c, lw, h, pos, seg, precision)[1]
    pad = -s % R.ROWS
    if pad:   # causal: rows appended at the end change nothing before
        h = jnp.pad(h, ((0, pad), (0, 0)))
        pos = jnp.pad(pos, (0, pad))
        seg = jnp.pad(seg, (0, pad), constant_values=-1)
    heads, dh = c["num_attention_heads"], c["head_dim"]
    carry = (jnp.zeros((heads, dh, dh), jnp.float32), seg[0])
    blocks = tuple(t.reshape((-1, R.ROWS) + t.shape[1:])
                   for t in (h, pos, seg))
    _, o = jax.lax.scan(jax.checkpoint(
        lambda c_, xs: _mix(c, lw, *xs, precision, carry=c_)), carry, blocks)
    return o.reshape(-1, o.shape[-1])[:s]


def matmul_params(c: dict) -> int:
    d = c["hidden_size"]
    hq = c["num_attention_heads"] * c["head_dim"]
    hkv = c["num_key_value_heads"] * c["head_dim"]
    return d * hq + 2 * d * hkv + hq * d


def mixing_flops(c: dict, seq_len: int = None) -> int:
    """Chunked with the program's block ``C``, per token and head (``dk``,
    ``dv`` the head widths): scores q kᵀ inside the block 2·C·dk, scores·v
    2·C·dv, q·M (state read) 2·dk·dv, kᵀv (state update) 2·dk·dv. Does
    not depend on the row's length."""
    C = c["linear_attention"]["block_size"]
    dh, h = c["head_dim"], c["num_attention_heads"]
    return h * (2 * C * (dh + dh) + 4 * dh * dh)


def decode_mixing_flops(c: dict, context: int = None) -> int:
    """One recurrent step: the state update and read, 4·dk·dv a head."""
    dh, h = c["head_dim"], c["num_attention_heads"]
    return h * 4 * dh * dh
