"""Pallas TPU kernels: blockwise online-softmax (flash) GQA attention, fwd+bwd.

Used by the standard-attention layers of hybrid models (LASP-2H's local
compute after the K/V AllGather — paper Alg. 7 line 7) and by prefill.

Forward grid = ``(B, Hq, nq, kv_band)``; the kv axis is the innermost
sequential axis; ``(m, l, acc)`` live in VMEM scratch and are reset when
the band index is 0. The running max ``m`` and sum ``l`` are lane-dense
``(block_q, 128)`` tiles, each row's value repeated across the lanes, so
they meet a ``(block_q, block_k)`` score tile without a lane broadcast.
The per-row softmax statistics ``lse = m + log l`` are written out as a
second output, a ``(B, Hq, 1, Sq)`` array in ``(1, block_q)`` rows
(``repro.kernels.layout``) — the backward residuals of the standard flash
scheme (Dao 2023; Lightning Attention-2 keeps the same tile loop resident
on-chip for its backward, the pattern followed here). A traced
``q_offset`` reaches the kernels as a scalar in SMEM.

Tiles: :func:`pick_tiles` takes ``(block_q, block_k)`` from what a call
sees — the query and key lengths (a multiple of 128 after the padding in
``repro.kernels.ops``), the sliding window and the input dtype: the
largest 128-multiple that divides each length, up to ``MAX_TILE_Q`` /
``MAX_TILE_K`` (``MAX_TILE_F32`` for fp32 inputs), and with a sliding
window at most a quarter of it, so that band trimming still cuts whole
tiles. A grid step has a fixed cost (about 0.2 µs on a v5e) and re-reads
the resident operand's partner from HBM, so a tile of several hundred
rows pays both once for many 128 × 128 block pairs; 1024 × 1024 tiles
compile for a v5e within the default scoped VMEM. Explicit ``block_q`` /
``block_k`` override the pick.

The block step keeps the fp32 softmax math and feeds the MXU as cheaply
as that allows. Products of two stored bf16 tiles (``q·kᵀ``; ``dO·vᵀ``
in the backward) go to the MXU as bf16 with fp32 accumulation: a product
of two bf16 values is exact in fp32. Products of an in-kernel fp32 tile
with a stored bf16 one (``p·v``, ``ds·k``, ``pᵀ·dO``, ``dsᵀ·q``) split the
fp32 side into two bf16 terms, ``hi = bf16(x)`` and ``lo = bf16(x −
hi)``: 16 significant bits, the result a three-pass fp32 product gives
when one side is exactly bf16. ``exp``, the running statistics and the
accumulators stay fp32, and fp32 inputs keep fp32 products (at the
compiler's default precision, as before). The element
mask is built only on tiles that cross the causal diagonal, the window's
edge or ``kv_len`` — a scalar test per tile picks the masked or the
unmasked step.

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
  accumulates in VMEM scratch across the kv band. The q tile is resident
  across the band, so ``lse`` and ``delta`` are turned from rows into
  lane-dense columns once, at the first band step, into scratch.
* ``dk/dv`` — kv-major grid ``(B, Hkv, nkv, rep, q_band)`` iterating the
  *transposed* band (the reverse orientation of the forward loop): each
  kv tile stays resident while the q-head group (``rep`` = GQA ratio)
  and its q band stream by, so dk/dv are accumulated across the whole
  q-head group in fp32 scratch and written once — KV tiles are fetched
  once per group instead of once per q head. The step works on the
  transposed scores ``sᵀ = k·qᵀ`` ``(block_k, block_q)``: ``lse`` and
  ``delta`` then broadcast down the sublanes as the ``(1, block_q)`` rows
  they arrive in, and ``dv += pᵀ·dO``, ``dk += dsᵀ·q`` are plain products.

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

LANES = 128
# Largest tiles a call picks, for bf16 and for fp32 inputs. Chosen on a
# v5e by scripts/flash_tile_sweep.py at one LASP-2H rank's shape (PERF.md
# §6): 1024 × 1024 beat 512 × 512 by 9 % a live block pair; fp32 was
# swept to 512.
MAX_TILE_Q = 1024
MAX_TILE_K = 1024
MAX_TILE_F32 = 512

_NT = (((1,), (1,)), ((), ()))     # x @ y^T
_NN = (((1,), (0,)), ((), ()))     # x @ y


def mask_value(dtype) -> float:
    """Finite large-negative for masked logits, derived from ``dtype``'s
    ``finfo`` so reduced-precision score dtypes (bf16/fp16) cannot
    overflow to ``-inf``/NaN the way a ``-1e30`` literal does in fp16."""
    return float(jnp.finfo(jnp.dtype(dtype)).min) * 0.5


# ---------------------------------------------------------------------------
# Tiles.
# ---------------------------------------------------------------------------

def _tile(n: int, cap: int) -> int:
    """Largest multiple of 128 that divides ``n`` and is at most ``cap``;
    ``n`` itself when it is at most 128 (one tile spans the array), and
    128 when no multiple of 128 divides it (the caller pads)."""
    if n <= LANES:
        return n
    tile = LANES
    for t in range(2 * LANES, min(n, cap) + 1, LANES):
        if n % t == 0:
            tile = t
    return tile


def pick_tiles(sq: int, sk: int, *, sliding_window=None,
               dtype=jnp.bfloat16):
    """``(block_q, block_k)`` for ``sq`` queries against ``sk`` keys, each a
    multiple of 128 or at most 128. bf16 inputs take tiles up to
    ``MAX_TILE_Q`` × ``MAX_TILE_K``, other dtypes up to ``MAX_TILE_F32``.
    A sliding window caps both tiles at a quarter of the window (128 at
    least): a q tile's kv band then spans about the window and two tiles
    more, so trimming keeps about four fifths of the computed pairs live."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        cap_q, cap_k = MAX_TILE_Q, MAX_TILE_K
    else:
        cap_q = cap_k = MAX_TILE_F32
    if sliding_window is not None:
        w = max(LANES, sliding_window // 4 // LANES * LANES)
        cap_q, cap_k = min(cap_q, w), min(cap_k, w)
    return _tile(sq, cap_q), _tile(sk, cap_k)


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
                sliding_window, kv_len, transposed=False):
    """(block_q, block_k) validity mask in *global* coordinates —
    ``(block_k, block_q)``, keys down the sublanes, when ``transposed``.

    Query row i of a block sits at global position ``qoff + q_start + i``
    (``qoff = sk - sq`` for prefill-with-cache / ring-decode shapes, the
    SP rank offset under LASP-2H; key positions are global already).
    ``kv_len`` masks right-padded keys (awkward-length dispatch).
    """
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    qpos = qoff + q_start + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                     q_axis)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
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


def _block_full(qoff, q_start, k_start, block_q, block_k, *, causal,
                sliding_window, kv_len):
    """Every pair of the block valid: the block needs no element mask."""
    full = jnp.asarray(k_start + block_k <= kv_len)
    if causal:
        full &= k_start + block_k - 1 <= qoff + q_start
    if sliding_window is not None:
        full &= (qoff + q_start + block_q - 1 - k_start) < sliding_window
    return full


def _steps(step, needed, full):
    """Run ``step(masked)`` on a needed block: unmasked when it is full."""
    pl.when(jnp.logical_and(needed, full))(lambda: step(False))
    pl.when(jnp.logical_and(needed, jnp.logical_not(full)))(
        lambda: step(True))


# ---------------------------------------------------------------------------
# Block-step helpers.
# ---------------------------------------------------------------------------

def _load(ref):
    """A stored tile as the block step takes it: bf16 as it is, any other
    dtype as fp32."""
    x = ref[0, 0]
    return x if x.dtype == jnp.bfloat16 else x.astype(jnp.float32)


def _dot(x, y, dims):
    """``x·y`` over ``dims`` with fp32 accumulation. Two bf16 tiles go to
    the MXU as they are. An fp32 ``x`` against a bf16 ``y`` goes as the two
    bf16 terms ``hi = bf16(x)`` and ``lo = bf16(x − hi)``; two fp32 tiles as
    fp32."""
    if x.dtype == jnp.float32 and y.dtype == jnp.bfloat16:
        hi = x.astype(jnp.bfloat16)
        lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return (jax.lax.dot_general(hi, y, dims,
                                    preferred_element_type=jnp.float32)
                + jax.lax.dot_general(lo, y, dims,
                                      preferred_element_type=jnp.float32))
    return jax.lax.dot_general(x, y, dims,
                               preferred_element_type=jnp.float32)


def _lanes(x, n):
    """A lane-dense ``(r, 128)`` statistic (each row one value) as ``(r, n)``."""
    w = x.shape[1]
    if n == w:
        return x
    if n < w:
        return x[:, :n]
    if n % w == 0:
        return jnp.tile(x, (1, n // w))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _chunks(n):
    """Static slices of 128 rows, or one of all ``n`` rows when ``n`` is no
    multiple of 128: each row/column move then masks a small square."""
    c = LANES if n % LANES == 0 else n
    return [slice(j, j + c) for j in range(0, n, c)]


def _store_row(ref, col):
    """Lane-dense ``(n, 128)`` column into the ``(1, n)`` row of ``ref``."""
    for sl in _chunks(col.shape[0]):
        ref[0, 0, :, sl] = col_to_row(col[sl, :1])


def _load_col(ref, scr):
    """The ``(1, n)`` row of ``ref`` into the lane-dense ``(n, 128)``
    scratch ``scr``."""
    for sl in _chunks(scr.shape[0]):
        col = row_to_col(ref[0, 0, :, sl])
        scr[sl, :] = jnp.broadcast_to(col, (col.shape[0], scr.shape[1]))


# ---------------------------------------------------------------------------
# Forward kernel.
# ---------------------------------------------------------------------------

def _fwd_kernel(qoff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, sliding_window,
                q_offset, kv_len, kv_lo, kv_hi, kv_band, block_q, block_k):
    iq = pl.program_id(2)
    ikb = pl.program_id(3)
    neg = mask_value(jnp.float32)
    dh = acc_scr.shape[1]

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
    geo = dict(causal=causal, sliding_window=sliding_window, kv_len=kv_len)
    # in_extent kills the clamped band tail (repeats of the diagonal
    # block, already accumulated); the positional predicate kills
    # dynamically-dead blocks when q_offset is traced (band untrimmed).
    needed = jnp.logical_and(
        lo + ikb <= kv_hi(iq),
        _block_needed(qoff, q_start, k_start, block_q, block_k, **geo))

    def step(masked):
        s = _dot(_load(q_ref), _load(k_ref), _NT) * scale     # (bq, bk)
        if masked:
            mask = _block_mask(qoff, q_start, k_start, block_q, block_k,
                               **geo)
            s = jnp.where(mask, s, neg)
        m_prev = m_scr[...]                                 # (bq, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))
        if masked:      # rows with no valid key yet have m_new == neg
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * _lanes(corr, dh) + _dot(
            p, _load(v_ref), _NN)
        m_scr[...] = m_new

    _steps(step, needed,
           _block_full(qoff, q_start, k_start, block_q, block_k, **geo))

    @pl.when(ikb == kv_band - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)             # (bq, 128)
        o_ref[0, 0] = (acc_scr[...] / _lanes(l, dh)).astype(o_ref.dtype)
        _store_row(lse_ref, m_scr[...] + jnp.log(l))


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
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
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
                   delta_ref, dq_ref, dq_scr, lse_scr, delta_scr, *, scale,
                   causal, sliding_window, q_offset, kv_len, kv_lo, kv_hi,
                   kv_band, block_q, block_k):
    iq = pl.program_id(2)
    ikb = pl.program_id(3)

    @pl.when(ikb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        _load_col(lse_ref, lse_scr)
        _load_col(delta_ref, delta_scr)

    qoff = q_offset if q_offset is not None else qoff_ref[0]
    lo = kv_lo(iq)
    ik = jnp.clip(lo + ikb, 0, jnp.maximum(kv_hi(iq), 0))
    q_start = iq * block_q
    k_start = ik * block_k
    geo = dict(causal=causal, sliding_window=sliding_window, kv_len=kv_len)
    needed = jnp.logical_and(
        lo + ikb <= kv_hi(iq),
        _block_needed(qoff, q_start, k_start, block_q, block_k, **geo))

    def step(masked):
        k = _load(k_ref)
        s = _dot(_load(q_ref), k, _NT) * scale                 # (bq, bk)
        p = jnp.exp(s - _lanes(lse_scr[...], block_k))
        if masked:
            p = jnp.where(_block_mask(qoff, q_start, k_start, block_q,
                                      block_k, **geo), p, 0.0)
        dp = _dot(_load(do_ref), _load(v_ref), _NT)             # (bq, bk)
        ds = p * (dp - _lanes(delta_scr[...], block_k))
        dq_scr[...] = dq_scr[...] + _dot(ds, k, _NN)

    _steps(step, needed,
           _block_full(qoff, q_start, k_start, block_q, block_k, **geo))

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
    geo = dict(causal=causal, sliding_window=sliding_window, kv_len=kv_len)
    needed = jnp.logical_and(
        lo + iqb <= q_hi(ik),
        _block_needed(qoff, q_start, k_start, block_q, block_k, **geo))

    def step(masked):
        # Transposed orientation: keys down the sublanes, queries along
        # the lanes, so the (1, bq) lse/delta rows broadcast as they are.
        q, do = _load(q_ref), _load(do_ref)
        st = _dot(_load(k_ref), q, _NT) * scale                 # (bk, bq)
        pt = jnp.exp(st - lse_ref[0, 0])
        if masked:
            pt = jnp.where(_block_mask(qoff, q_start, k_start, block_q,
                                       block_k, transposed=True, **geo),
                           pt, 0.0)
        dv_scr[...] = dv_scr[...] + _dot(pt, do, _NN)          # (bk, dh)
        dpt = _dot(_load(v_ref), do, _NT)                       # (bk, bq)
        dst = pt * (dpt - delta_ref[0, 0])
        dk_scr[...] = dk_scr[...] + _dot(dst, q, _NN)          # (bk, dh)

    _steps(step, needed,
           _block_full(qoff, q_start, k_start, block_q, block_k, **geo))

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
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32),
                        pltpu.VMEM((block_q, LANES), jnp.float32),
                        pltpu.VMEM((block_q, LANES), jnp.float32)],
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
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None, interpret: bool = False):
    """GQA flash attention (differentiable). q: (B,Hq,S,dh), k/v: (B,Hkv,Sk,dh).

    ``q_offset``: global position of query row 0 (keys are global
    already). Defaults to ``sk - sq`` — the prefill-with-cache convention
    shared with the XLA mask fallback in ``repro.kernels.ops``. A python
    int keeps the causal band trimming static; a traced scalar (the SP
    rank offset under LASP-2H) is supported with the untrimmed band.

    ``kv_len``: number of valid (unpadded) key positions, for callers
    that right-pad ``sk`` to a block multiple. Defaults to ``sk``.

    ``block_q`` / ``block_k``: tile sizes; ``None`` takes
    :func:`pick_tiles`'s for these lengths, window and dtype.

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
    auto_q, auto_k = pick_tiles(sq, sk, sliding_window=sliding_window,
                                dtype=q.dtype)
    block_q = auto_q if block_q is None else min(block_q, sq)
    block_k = auto_k if block_k is None else min(block_k, sk)
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
