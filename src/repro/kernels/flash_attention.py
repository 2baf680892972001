"""Pallas TPU kernels: blockwise online-softmax (flash) GQA attention, fwd+bwd.

Used by the standard-attention layers of hybrid models (LASP-2H's local
compute after the K/V AllGather — paper Alg. 7 line 7) and by prefill.

Forward grid = ``(B, Hq, nq, kv_band)``; the kv axis is the innermost
sequential axis; ``(m, l, acc)`` live in VMEM scratch and are reset when
the band index is 0. The per-row softmax statistics ``lse = m + log l``
are written out as a second output, a ``(B, Hq, 1, Sq)`` array in
``(1, block_q)`` rows (``repro.kernels.layout``) — the backward
residuals of the standard flash scheme (Dao 2023; Lightning Attention-2
keeps the same tile loop resident on-chip for its backward, the pattern
followed here). A traced ``q_offset`` reaches the kernels as a scalar
in SMEM.

Causal grid trimming: the kv grid axis is a *band*, not the full kv
extent — for each q block the index maps offset by that block's first
needed kv block (``sliding_window`` lower bound) and clamp to its last
needed one (causal diagonal / ``kv_len``), so blocks strictly above the
diagonal are never fetched from HBM: the band is sized to the widest
per-q-block extent, clamped steps re-serve the already-resident diagonal
block (Pallas issues a copy only when the block index changes), and
their compute is skipped. With a sliding window the band is narrower
than the kv axis, so sub-window blocks are not even scheduled; fully
right-padded kv blocks (``kv_len``) are likewise never scheduled.

The backward follows FlashAttention-2's two-pass scheme:

* ``dq`` — same grid/band as the forward; ``p = exp(s - lse)`` is
  recomputed blockwise from the saved stats, ``ds = p (dO·V^T − delta)``
  with ``delta_i = dO_i·o_i`` precomputed rowwise, and ``dq += ds K``
  accumulates in VMEM scratch across the kv band.
* ``dk/dv`` — kv-major grid ``(B, Hkv, nkv, rep, q_band)`` iterating the
  *transposed* band (the reverse orientation of the forward loop): each
  kv tile stays resident while the q-head group (``rep`` = GQA ratio)
  and its q band stream by, so dk/dv are accumulated across the whole
  q-head group in fp32 scratch and written once — KV tiles are fetched
  once per group instead of once per q head.

GQA is expressed in the K/V index maps (``hq // rep``), so KV tiles are
fetched once per q-head group without materializing repeated heads.

:func:`flash_attention` wraps the three pallas_calls in a
``jax.custom_vjp`` — what ``repro.kernels.ops.flash_attention_op``
dispatches to, making the hybrid (LASP-2H) softmax path trainable on the
Pallas backends. ``q_offset`` may be a traced scalar (the SP rank offset
``t·C`` inside ``shard_map``): masking then uses the runtime value and
the band conservatively covers the full kv extent.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import col_to_row, row_to_col

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def mask_value(dtype) -> float:
    """Finite large-negative for masked logits, derived from ``dtype``'s
    ``finfo`` so reduced-precision score dtypes (bf16/fp16) cannot
    overflow to ``-inf``/NaN the way a ``-1e30`` literal does in fp16."""
    return float(jnp.finfo(jnp.dtype(dtype)).min) * 0.5


# ---------------------------------------------------------------------------
# Static band extents + shared masking.
# ---------------------------------------------------------------------------

def _kv_band(*, nq: int, nkv_real: int, block_q: int, block_k: int,
             q_offset: Optional[int], causal: bool, sliding_window):
    """Per-q-block kv block extents ``[lo(iq), hi(iq)]`` + band width.

    ``lo``/``hi`` accept traced block indices (static python constants
    baked in); ``width`` is the static kv grid-axis length. A traced
    ``q_offset`` (``None`` here) degrades to the untrimmed full extent —
    masking alone carries correctness there.
    """
    if q_offset is None:
        return (lambda iq: 0), (lambda iq: nkv_real - 1), max(nkv_real, 1)

    def lo(iq):
        if sliding_window is None:
            return 0
        return jnp.maximum(
            0, (q_offset + iq * block_q - (sliding_window - 1)) // block_k)

    def hi(iq):
        h = nkv_real - 1
        if causal:
            h = jnp.minimum(h, (q_offset + (iq + 1) * block_q - 1)
                            // block_k)
        return h

    def lo_py(iq):
        if sliding_window is None:
            return 0
        return max(0, (q_offset + iq * block_q - (sliding_window - 1))
                   // block_k)

    def hi_py(iq):
        h = nkv_real - 1
        if causal:
            h = min(h, (q_offset + (iq + 1) * block_q - 1) // block_k)
        return h

    width = max(max((hi_py(i) - lo_py(i) + 1 for i in range(nq)),
                    default=1), 1)
    return lo, hi, min(width, max(nkv_real, 1))


def _q_band(*, nq: int, nkv: int, block_q: int, block_k: int,
            q_offset: Optional[int], causal: bool, sliding_window):
    """Transposed band for the dk/dv pass: per-kv-block q extents."""
    if q_offset is None:
        return (lambda ik: 0), (lambda ik: nq - 1), max(nq, 1)

    def lo(ik):
        if not causal:
            return 0
        return jnp.maximum(0, (ik * block_k - q_offset) // block_q)

    def hi(ik):
        h = nq - 1
        if sliding_window is not None:
            h = jnp.minimum(h, (ik * block_k + block_k - 2 + sliding_window
                                - q_offset) // block_q)
        return h

    def lo_py(ik):
        return max(0, (ik * block_k - q_offset) // block_q) if causal else 0

    def hi_py(ik):
        h = nq - 1
        if sliding_window is not None:
            h = min(h, (ik * block_k + block_k - 2 + sliding_window
                        - q_offset) // block_q)
        return h

    width = max(max((hi_py(i) - lo_py(i) + 1 for i in range(nkv)),
                    default=1), 1)
    return lo, hi, min(width, max(nq, 1))


def _block_mask(qoff, q_start, k_start, block_q, block_k, *, causal,
                sliding_window, kv_len):
    """(block_q, block_k) validity mask in *global* coordinates.

    Query row i of a block sits at global position ``qoff + q_start + i``
    (``qoff = sk - sq`` for prefill-with-cache / ring-decode shapes, the
    SP rank offset under LASP-2H; key positions are global already).
    ``kv_len`` masks right-padded keys (awkward-length dispatch).
    """
    qpos = qoff + q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < kv_len
    if causal:
        mask &= qpos >= kpos
    if sliding_window is not None:
        mask &= (qpos - kpos) < sliding_window
    return mask


def _block_needed(qoff, q_start, k_start, block_q, block_k, *, causal,
                  sliding_window, kv_len):
    """Block-granularity version of :func:`_block_mask` (any pair valid)."""
    needed = jnp.asarray(k_start < kv_len)
    if causal:
        needed &= k_start <= qoff + q_start + block_q - 1
    if sliding_window is not None:
        needed &= (qoff + q_start - (k_start + block_k - 1)) \
            < sliding_window
    return needed


# ---------------------------------------------------------------------------
# Forward kernel.
# ---------------------------------------------------------------------------

def _fwd_kernel(qoff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, sliding_window,
                q_offset, kv_len, kv_lo, kv_hi, kv_band, block_q, block_k):
    iq = pl.program_id(2)
    ikb = pl.program_id(3)
    neg = mask_value(jnp.float32)

    @pl.when(ikb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, neg)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qoff = q_offset if q_offset is not None else qoff_ref[0]
    lo = kv_lo(iq)
    ik = jnp.clip(lo + ikb, 0, jnp.maximum(kv_hi(iq), 0))
    q_start = iq * block_q
    k_start = ik * block_k
    # in_extent kills the clamped band tail (repeats of the diagonal
    # block, already accumulated); the positional predicate kills
    # dynamically-dead blocks when q_offset is traced (band untrimmed).
    needed = jnp.logical_and(
        lo + ikb <= kv_hi(iq),
        _block_needed(qoff, q_start, k_start, block_q, block_k,
                      causal=causal, sliding_window=sliding_window,
                      kv_len=kv_len))

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)       # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)       # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)       # (bk, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (bq, bk)
        mask = _block_mask(qoff, q_start, k_start, block_q, block_k,
                           causal=causal, sliding_window=sliding_window,
                           kv_len=kv_len)
        s = jnp.where(mask, s, neg)

        m_prev = m_scr[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ikb == kv_band - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)             # (bq, 1)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = col_to_row(m_scr[...] + jnp.log(l))


def _fwd_call(q, k, v, qoff_arr, *, causal, sliding_window, scale,
              q_offset, kv_len, block_q, block_k, interpret):
    b, hq, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = hq // hkv
    nq = sq // block_q
    nkv_real = -(-kv_len // block_k)
    kv_lo, kv_hi, kv_band = _kv_band(
        nq=nq, nkv_real=nkv_real, block_q=block_q, block_k=block_k,
        q_offset=q_offset, causal=causal, sliding_window=sliding_window)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        sliding_window=sliding_window, q_offset=q_offset, kv_len=kv_len,
        kv_lo=kv_lo, kv_hi=kv_hi, kv_band=kv_band, block_q=block_q,
        block_k=block_k)

    def kv_im(b_, h, iq, ikb, rep_=rep):
        ik = jnp.clip(kv_lo(iq) + ikb, 0, jnp.maximum(kv_hi(iq), 0))
        return (b_, h // rep_, ik, 0)

    return pl.pallas_call(
        kernel,
        grid=(b, hq, nq, kv_band),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b_, h, iq, ikb: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, dh), kv_im),
            pl.BlockSpec((1, 1, block_k, dh), kv_im),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b_, h, iq, ikb: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b_, h, iq, ikb: (b_, h, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qoff_arr, q, k, v)


# ---------------------------------------------------------------------------
# Backward kernels: dq pass (q-major, forward band) and dk/dv pass
# (kv-major, transposed band, GQA-group accumulation).
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr, *, scale, causal,
                   sliding_window, q_offset, kv_len, kv_lo, kv_hi, kv_band,
                   block_q, block_k):
    iq = pl.program_id(2)
    ikb = pl.program_id(3)

    @pl.when(ikb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    qoff = q_offset if q_offset is not None else qoff_ref[0]
    lo = kv_lo(iq)
    ik = jnp.clip(lo + ikb, 0, jnp.maximum(kv_hi(iq), 0))
    q_start = iq * block_q
    k_start = ik * block_k
    needed = jnp.logical_and(
        lo + ikb <= kv_hi(iq),
        _block_needed(qoff, q_start, k_start, block_q, block_k,
                      causal=causal, sliding_window=sliding_window,
                      kv_len=kv_len))

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)        # (bk, dh)
        do = do_ref[0, 0].astype(jnp.float32)      # (bq, dh)
        lse = row_to_col(lse_ref[0, 0])            # (bq, 1)
        delta = row_to_col(delta_ref[0, 0])        # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = _block_mask(qoff, q_start, k_start, block_q, block_k,
                           causal=causal, sliding_window=sliding_window,
                           kv_len=kv_len)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)            # (bq, bk)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bq, bk)
        ds = p * (dp - delta)
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ikb == kv_band - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale,
                    causal, sliding_window, q_offset, kv_len, q_lo, q_hi,
                    q_band, rep, block_q, block_k):
    ik = pl.program_id(2)
    ig = pl.program_id(3)
    iqb = pl.program_id(4)

    @pl.when(jnp.logical_and(ig == 0, iqb == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    qoff = q_offset if q_offset is not None else qoff_ref[0]
    lo = q_lo(ik)
    iq = jnp.clip(lo + iqb, 0, jnp.maximum(q_hi(ik), 0))
    q_start = iq * block_q
    k_start = ik * block_k
    needed = jnp.logical_and(
        lo + iqb <= q_hi(ik),
        _block_needed(qoff, q_start, k_start, block_q, block_k,
                      causal=causal, sliding_window=sliding_window,
                      kv_len=kv_len))

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)        # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)        # (bk, dh)
        do = do_ref[0, 0].astype(jnp.float32)      # (bq, dh)
        lse = row_to_col(lse_ref[0, 0])            # (bq, 1)
        delta = row_to_col(delta_ref[0, 0])        # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = _block_mask(qoff, q_start, k_start, block_q, block_k,
                           causal=causal, sliding_window=sliding_window,
                           kv_len=kv_len)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)            # (bq, bk)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bk, dh)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bq, bk)
        ds = p * (dp - delta)
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bk, dh)

    @pl.when(jnp.logical_and(ig == rep - 1, iqb == q_band - 1))
    def _finalize():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, qoff_arr, o, lse, do, *, causal, sliding_window,
              scale, q_offset, kv_len, block_q, block_k, interpret):
    b, hq, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = hq // hkv
    nq, nkv = sq // block_q, sk // block_k
    nkv_real = -(-kv_len // block_k)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]            # (b, hq, 1, sq) rows

    kv_lo, kv_hi, kv_band = _kv_band(
        nq=nq, nkv_real=nkv_real, block_q=block_q, block_k=block_k,
        q_offset=q_offset, causal=causal, sliding_window=sliding_window)

    def kv_im(b_, h, iq, ikb, rep_=rep):
        ik = jnp.clip(kv_lo(iq) + ikb, 0, jnp.maximum(kv_hi(iq), 0))
        return (b_, h // rep_, ik, 0)

    q_im = lambda b_, h, iq, ikb: (b_, h, iq, 0)
    stat_im = lambda b_, h, iq, ikb: (b_, h, 0, iq)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            sliding_window=sliding_window, q_offset=q_offset,
            kv_len=kv_len, kv_lo=kv_lo, kv_hi=kv_hi, kv_band=kv_band,
            block_q=block_q, block_k=block_k),
        grid=(b, hq, nq, kv_band),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, dh), q_im),
            pl.BlockSpec((1, 1, block_k, dh), kv_im),
            pl.BlockSpec((1, 1, block_k, dh), kv_im),
            pl.BlockSpec((1, 1, block_q, dh), q_im),
            pl.BlockSpec((1, 1, 1, block_q), stat_im),
            pl.BlockSpec((1, 1, 1, block_q), stat_im),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh), q_im),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qoff_arr, q, k, v, do, lse, delta)

    q_lo, q_hi, q_band = _q_band(
        nq=nq, nkv=nkv, block_q=block_q, block_k=block_k,
        q_offset=q_offset, causal=causal, sliding_window=sliding_window)

    def qg_im(b_, g, ik, ig, iqb, rep_=rep):
        iq = jnp.clip(q_lo(ik) + iqb, 0, jnp.maximum(q_hi(ik), 0))
        return (b_, g * rep_ + ig, iq, 0)

    def statg_im(b_, g, ik, ig, iqb, rep_=rep):
        iq = jnp.clip(q_lo(ik) + iqb, 0, jnp.maximum(q_hi(ik), 0))
        return (b_, g * rep_ + ig, 0, iq)

    kvg_im = lambda b_, g, ik, ig, iqb: (b_, g, ik, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            sliding_window=sliding_window, q_offset=q_offset,
            kv_len=kv_len, q_lo=q_lo, q_hi=q_hi, q_band=q_band, rep=rep,
            block_q=block_q, block_k=block_k),
        grid=(b, hkv, nkv, rep, q_band),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, dh), qg_im),
            pl.BlockSpec((1, 1, block_k, dh), kvg_im),
            pl.BlockSpec((1, 1, block_k, dh), kvg_im),
            pl.BlockSpec((1, 1, block_q, dh), qg_im),
            pl.BlockSpec((1, 1, 1, block_q), statg_im),
            pl.BlockSpec((1, 1, 1, block_q), statg_im),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, dh), kvg_im),
            pl.BlockSpec((1, 1, block_k, dh), kvg_im),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, sk, dh), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, sk, dh), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dh), jnp.float32),
            pltpu.VMEM((block_k, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qoff_arr, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Differentiable entry point (custom_vjp over the three Pallas passes).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, qoff_arr, causal, sliding_window, scale, q_offset,
           kv_len, block_q, block_k, interpret):
    o, _ = _fwd_call(q, k, v, qoff_arr, causal=causal,
                     sliding_window=sliding_window, scale=scale,
                     q_offset=q_offset, kv_len=kv_len, block_q=block_q,
                     block_k=block_k, interpret=interpret)
    return o


def _flash_vjp_fwd(q, k, v, qoff_arr, causal, sliding_window, scale,
                   q_offset, kv_len, block_q, block_k, interpret):
    o, lse = _fwd_call(q, k, v, qoff_arr, causal=causal,
                       sliding_window=sliding_window, scale=scale,
                       q_offset=q_offset, kv_len=kv_len, block_q=block_q,
                       block_k=block_k, interpret=interpret)
    return o, (q, k, v, qoff_arr, o, lse)


def _flash_vjp_bwd(causal, sliding_window, scale, q_offset, kv_len,
                   block_q, block_k, interpret, res, do):
    q, k, v, qoff_arr, o, lse = res
    dq, dk, dv = _bwd_call(
        q, k, v, qoff_arr, o, lse, do, causal=causal,
        sliding_window=sliding_window, scale=scale, q_offset=q_offset,
        kv_len=kv_len, block_q=block_q, block_k=block_k,
        interpret=interpret)
    # q_offset is integer data — its cotangent is the symbolic float0 zero
    return dq, dk, dv, np.zeros(qoff_arr.shape, jax.dtypes.float0)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True, sliding_window=None,
                    scale=None, q_offset=None, kv_len: Optional[int] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K, interpret: bool = False):
    """GQA flash attention (differentiable). q: (B,Hq,S,dh), k/v: (B,Hkv,Sk,dh).

    ``q_offset``: global position of query row 0 (keys are global
    already). Defaults to ``sk - sq`` — the prefill-with-cache convention
    shared with the XLA mask fallback in ``repro.kernels.ops``. A python
    int keeps the causal band trimming static; a traced scalar (the SP
    rank offset under LASP-2H) is supported with the untrimmed band.

    ``kv_len``: number of valid (unpadded) key positions, for callers
    that right-pad ``sk`` to a block multiple. Defaults to ``sk``.

    Gradients flow to q/k/v through the two-pass Pallas backward
    (``jax.custom_vjp``).
    """
    b, hq, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if scale is None:
        scale = dh ** -0.5
    if q_offset is None:
        q_offset = sk - sq
    if kv_len is None:
        kv_len = sk
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"sq={sq}, sk={sk} not divisible by blocks "
                         f"({block_q}, {block_k})")
    if isinstance(q_offset, (int, np.integer)):
        q_off_static, qoff_arr = int(q_offset), \
            jnp.full((1,), int(q_offset), jnp.int32)
    else:   # traced (SP rank offset): band untrimmed, masked at runtime
        q_off_static = None
        qoff_arr = jnp.asarray(q_offset, jnp.int32).reshape(1)
    return _flash(q, k, v, qoff_arr, causal, sliding_window, float(scale),
                  q_off_static, int(kv_len), block_q, block_k, interpret)
