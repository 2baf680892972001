"""Full causal softmax attention, the standard-attention layer of the
LASP-2 paper's 1/4 hybrid (arXiv:2502.07563 §4; LASP-2H, §3.5), with q/k/v
biases and RoPE as the linear layers have them:

    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv      (split into heads)
    q, k = rope(q), rope(k);  q = q / sqrt(dh)
    o_t = Σ_{s ≤ t, doc(s) = doc(t)} softmax_s(q_t · k_s) v_s
    out = o Wo

No sliding window. Keys are masked to the query's document: the true
semantics of a packed row. (The program's softmax layer attends across
documents today, a known defect; a row of one document, as the hybrid
cell's traffic has, makes the two agree.) KV heads, where fewer, are
repeated to the query heads.

The reference computes the scores of ``QUERY_BLOCK`` queries at a time
against every key of the row, masked, each block under
``jax.checkpoint``, so that a 65,536-token row holds one block's scores
at a time. It computes the masked half too: blocks that read only the
keys they may see (a key length per group of queries) leave cotangents
of several lengths, which a v5e's compiler could not fit beside the
rest of the 65,536-token step (18.5 GB of 15.75).

Counts. ``mixing_flops`` is the causal work a token needs beyond its
projections: scores and the weighted sum of values, 2·dh each per key
seen, 2·h·dh·(S+1) per token on average over a row of S. The flash
kernels' (FLOPs, bytes) per call (``kernel_work``) count the blocks the
causal mask needs, not the grid a kernel walks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference.model import _ein, rope

PROGRAM = "softmax"

_LEAVES = ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
QUERY_BLOCK = 128
# a masked score, finite so that a row with no key it may see stays finite
_MASKED = -1e30


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


def _attend(c, lw, k, v, seg, precision, xs):
    """One block of queries: its projection, its scores against every key,
    masked to the causal past of its own document, and the output
    projection of the weighted values."""
    hq, dh = c["num_attention_heads"], c["head_dim"]
    hb, pb, ib, sb = xs
    mm = functools.partial(_ein, "sd,df->sf", precision=precision)
    qb = mm(hb, lw["wq"])
    if c["qkv_bias"]:
        qb = qb + lw["bq"]
    qb = rope(qb.reshape(-1, hq, dh), pb, c["rope_theta"]) * dh ** -0.5
    sc = _ein("ihd,jhd->hij", qb, k, precision)
    mask = (jnp.arange(k.shape[0])[None, :] <= ib[:, None]) & \
        (seg[None, :] == sb[:, None])
    p = jax.nn.softmax(jnp.where(mask[None], sc, _MASKED), axis=-1)
    o = _ein("hij,jhd->ihd", p, v, precision)
    return mm(o.reshape(-1, hq * dh), lw["wo"])


def apply(c, lw, h, pos, seg, precision):
    """Keys and values for the whole row, then the queries in blocks of
    ``QUERY_BLOCK``, each under ``jax.checkpoint``."""
    hq, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    s = h.shape[0]
    mm = functools.partial(_ein, "sd,df->sf", precision=precision)
    k, v = mm(h, lw["wk"]), mm(h, lw["wv"])
    if c["qkv_bias"]:
        k, v = k + lw["bk"], v + lw["bv"]
    k = rope(k.reshape(s, hkv, dh), pos, c["rope_theta"])
    v = v.reshape(s, hkv, dh)
    if hkv != hq:
        k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    rows = (h, pos, jnp.arange(s), seg)
    pad = -s % QUERY_BLOCK
    if pad:   # rows appended at the end see keys, and are cut off
        rows = tuple(jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                     for t in rows)
    xs = tuple(t.reshape((-1, QUERY_BLOCK) + t.shape[1:]) for t in rows)
    o = jax.lax.map(jax.checkpoint(functools.partial(
        _attend, c, lw, k, v, seg, precision)), xs)
    return o.reshape(s + pad, -1)[:s]


def matmul_params(c: dict) -> int:
    d = c["hidden_size"]
    hq = c["num_attention_heads"] * c["head_dim"]
    hkv = c["num_key_value_heads"] * c["head_dim"]
    return d * hq + 2 * d * hkv + hq * d


def mixing_flops(c: dict, seq_len: int = None) -> int:
    """Per token, averaged over a row of ``seq_len`` tokens: token ``t``
    sees ``t + 1`` keys, and each key costs a score (2·dh) and its share
    of the weighted sum (2·dh) a head; the mean of ``t + 1`` is
    ``(S + 1) / 2``. Depends on the row's length, so refuses none."""
    if seq_len is None:
        raise ValueError("causal softmax attention's work per token depends "
                         "on the row's length: give seq_len")
    dh, h = c["head_dim"], c["num_attention_heads"]
    return 2 * h * dh * (seq_len + 1)


def decode_mixing_flops(c: dict, context: int = None) -> int:
    """One decode step after ``context`` tokens: a score and a weighted
    value per cached key, 4·dh a head."""
    if context is None:
        raise ValueError("a decode step's attention depends on the context: "
                         "give context")
    dh, h = c["head_dim"], c["num_attention_heads"]
    return 4 * h * dh * context


# ---------------------------------------------------------------------------
# The flash kernels, per call
# ---------------------------------------------------------------------------

KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")


def needed_blocks(*, sq: int, sk: int, q_offset: int, block_q: int = 128,
                  block_k: int = 128) -> tuple:
    """(query-key block pairs the causal mask needs, key blocks any query
    needs) for ``sq`` queries at global positions ``q_offset`` onwards
    against ``sk`` keys from position 0: key block ``j`` is needed by
    query block ``i`` when its first key lies at or before the block's
    last query."""
    nk = -(-sk // block_k)
    pairs, widest = 0, 0
    for i in range(-(-sq // block_q)):
        last = q_offset + min(sq, (i + 1) * block_q) - 1
        n = min(nk, last // block_k + 1)
        pairs += n
        widest = max(widest, n)
    return pairs, widest


def kernel_work(name: str, *, bh: int, sq: int, sk: int, dh: int,
                q_offset: int, block_q: int = 128, block_k: int = 128,
                in_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of one call of a flash kernel on ``bh`` heads (MHA:
    as many KV heads), counted on the blocks the causal mask needs.
    ``in_bytes``: width of q/k/v/o and their gradients (bf16: 2); the
    softmax statistics (lse, and delta = rowsum(dO·o)) are fp32.

    flash_attention_fwd  per needed block pair q·kᵀ and p·v, 4·bq·bk·dh;
        reads q and the needed keys and values, writes o and lse.
    flash_attention_bwd_dq  recomputes the scores, forms dO·vᵀ and ds·k,
        6·bq·bk·dh; reads q, the needed k and v, dO, lse and delta,
        writes dq.
    flash_attention_bwd_dkv  recomputes the scores, forms pᵀ·dO, dO·vᵀ
        and dsᵀ·q, 8·bq·bk·dh; reads q, the needed k and v, dO, lse and
        delta, writes dk and dv of every key (zeros past the needed
        ones).
    """
    pairs, widest = needed_blocks(sq=sq, sk=sk, q_offset=q_offset,
                                  block_q=block_q, block_k=block_k)
    keys = min(sk, widest * block_k)
    per_pair = {"flash_attention_fwd": 4, "flash_attention_bwd_dq": 6,
                "flash_attention_bwd_dkv": 8}
    if name not in per_pair:
        raise KeyError(f"no work count for kernel {name!r}")
    flops = bh * pairs * per_pair[name] * block_q * block_k * dh
    q_side = bh * sq * dh * in_bytes
    kv = 2 * bh * keys * dh * in_bytes
    stats = bh * sq * 4
    if name == "flash_attention_fwd":
        nbytes = q_side + kv + q_side + stats
    elif name == "flash_attention_bwd_dq":
        nbytes = 2 * q_side + kv + 2 * stats + q_side
    else:
        nbytes = 2 * q_side + kv + 2 * stats + 2 * bh * sk * dh * in_bytes
    return flops, nbytes


def rank_work(c: dict, t: dict, rank: int) -> dict:
    """{kernel: (FLOPs, bytes)} of one call on sequence-parallel rank
    ``rank`` of the traffic's layout: its ``seq_len / sp`` queries at
    offset ``rank · seq_len / sp`` against the gathered keys."""
    sp = t["layout"].get("sp", 1)
    local = t["seq_len"] // sp
    shape = dict(bh=t["rows"] // t["layout"].get("dp", 1)
                 * c["num_attention_heads"], sq=local, sk=t["seq_len"],
                 dh=c["head_dim"], q_offset=rank * local)
    return {k: kernel_work(k, **shape) for k in KERNELS}
