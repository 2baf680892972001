"""Pallas TPU kernels: intra-chunk decayed causal linear attention, fwd+bwd.

This is the compute hot-spot of LASP-2 (paper Alg. 2 lines 5–8): each
device's local sequence chunk is processed block-by-block, carrying the
``dk × dv`` memory state in VMEM scratch across the (sequential) block grid
dimension. The cross-device part (the AllGather of chunk states) lives in
``repro.core.lasp2``; this kernel is the per-device "intra" workhorse it
overlaps with.

TPU adaptation of the paper's Triton kernel:

* blocks are ``(BLOCK, dk/dv)`` tiles, MXU-aligned (128 lanes); the three
  matmuls per block (``QK^T``, ``scores·V``, ``K^T V``) hit the MXU with
  fp32 accumulation via ``preferred_element_type``;
* the memory state is fp32 in VMEM *scratch* that persists across the
  sequential grid axis — the HBM↔VMEM traffic per block is just the
  q/k/v/o tiles (the GPU version instead re-materializes through SMEM);
* decay math is log-space fp32; all reweighting factors are <= 1
  (see ``repro.core.linear_attention``).

The backward follows Lightning Attention-2's two-pass scheme, decay
generalized (paper Alg. 4's local lines):

* ``dq`` — a forward-order pass re-carrying the prefix state ``M`` in VMEM
  scratch (``dq_i = dO_i M_i^T``, split into the intra-block score matrix
  and the carried inter-block term);
* ``dk/dv/dlog_a`` — a reverse-order pass (reversed block index maps on
  the sequential grid axis) carrying the *suffix* state gradient
  ``N_j = Σ_{i≥j} e^{L_i−L_j} q_i^T dO_i + e^{L_S−L_j} dM``, seeded with
  the end-of-chunk state cotangent ``dM`` — the faithful SP backward
  (Alg. 4) pulls on both ``o`` *and* ``state``, so the kernel accepts
  both cotangents. The decay gradient uses the log-space identity
  ``∂L/∂log a_m = Σ_{i≥m} (dO_i·o_i − k_i·dk_i) + ⟨state, dM⟩ + dA``
  (suffix-accumulated in scratch; the constant term is added by the
  ``custom_vjp`` wrapper).

:func:`lasp2_chunk` wraps forward+backward in ``jax.custom_vjp`` — this
is what ``repro.kernels.ops.linear_attention_op`` dispatches to, making
the Pallas path trainable end-to-end.

Layout: inputs are flattened to ``(BH, S, d)``; grid = ``(BH, S//BLOCK)``
with ``dimension_semantics=("parallel", "arbitrary")`` so distinct
batch·head programs parallelize across cores while blocks run in order.
``log_a`` and its gradient move as ``(BH, 1, S)`` arrays in ``(1, BLOCK)``
rows (``repro.kernels.layout``); the total log decay is a plain sum
outside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import col_to_row, cumsum_col, suffix_sum_row, tri

DEFAULT_BLOCK = 128


def _decay_mat(cb):
    """D_ij = exp(cb_i - cb_j) for i >= j else 0 (all factors <= 1);
    ``cb`` is the ``(c, 1)`` cumulative log decay column."""
    row, col = tri(cb.shape[0])
    diff = cb - col_to_row(cb)
    return jnp.where(row >= col, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)


def _kernel(q_ref, k_ref, v_ref, la_ref, o_ref, state_ref, state_scratch,
            *, nblocks: int):
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _init():
        state_scratch[...] = jnp.zeros_like(state_scratch)

    q = q_ref[0].astype(jnp.float32)          # (C, dk)
    k = k_ref[0].astype(jnp.float32)          # (C, dk)
    v = v_ref[0].astype(jnp.float32)          # (C, dv)
    la = la_ref[0].astype(jnp.float32)        # (1, C)

    cb = cumsum_col(la)                       # (C, 1) inclusive log decay
    a_blk = jnp.sum(la, axis=1, keepdims=True)  # (1, 1)
    dmat = _decay_mat(cb)

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * dmat            # (C, C)
    o_intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                    # (C, dv)
    # inter (within-device, previous blocks): (q ⊙ b) @ S_carry
    state = state_scratch[...]
    o_inter = jax.lax.dot_general(
        q * jnp.exp(cb), state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = (o_intra + o_inter).astype(o_ref.dtype)

    # state update: S <- exp(A) S + (k ⊙ exp(A - cb))^T v
    kw = k * jnp.exp(a_blk - cb)
    s_new = jnp.exp(a_blk) * state + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_scratch[...] = s_new

    @pl.when(blk == nblocks - 1)
    def _finalize():
        state_ref[0] = s_new


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def lasp2_chunk_fwd(q, k, v, log_a, *, block_size: int = DEFAULT_BLOCK,
                    interpret: bool = False):
    """Chunked decayed causal linear attention (forward), Pallas TPU.

    q, k: (BH, S, dk); v: (BH, S, dv); log_a: (BH, S).
    Returns (o (BH, S, dv), state (BH, dk, dv) fp32, log_decay (BH,) fp32).
    """
    bh, s, dk = q.shape
    dv = v.shape[-1]
    if s % block_size:
        raise ValueError(f"S={s} must be divisible by block={block_size}")
    nb = s // block_size

    grid = (bh, nb)
    kernel = functools.partial(_kernel, nblocks=nb)
    o, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_size, dk), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, block_size, dk), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, block_size, dv), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, 1, block_size), lambda b, t: (b, 0, t)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_size, dv), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, t: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="lasp2_chunk_fwd",
    )(q, k, v, log_a.reshape(bh, 1, s))
    return o, state, jnp.sum(log_a.astype(jnp.float32), axis=-1)


# ---------------------------------------------------------------------------
# Backward kernels.
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(k_ref, v_ref, la_ref, do_ref, dq_ref, state_scratch):
    """Forward-order pass: dq_i = dO_i M_i^T, re-carrying the prefix state."""
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _init():
        state_scratch[...] = jnp.zeros_like(state_scratch)

    k = k_ref[0].astype(jnp.float32)          # (C, dk)
    v = v_ref[0].astype(jnp.float32)          # (C, dv)
    la = la_ref[0].astype(jnp.float32)        # (1, C)
    do = do_ref[0].astype(jnp.float32)        # (C, dv)

    cb = cumsum_col(la)                       # (C, 1)
    a_blk = jnp.sum(la, axis=1, keepdims=True)  # (1, 1)
    dmat = _decay_mat(cb)
    # intra: dq_i += sum_{j<=i} e^{cb_i-cb_j} (dO_i·v_j) k_j
    dsc = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * dmat            # (C, C)
    dq_intra = jax.lax.dot_general(
        dsc, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                    # (C, dk)
    # inter: dq_i += e^{cb_i} dO_i M_prev^T
    state = state_scratch[...]
    dq_inter = jax.lax.dot_general(
        do, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(cb)
    dq_ref[0] = (dq_intra + dq_inter).astype(dq_ref.dtype)

    # same carry update as the forward: M <- e^A M + (k ⊙ e^{A-cb})^T v
    kw = k * jnp.exp(a_blk - cb)
    state_scratch[...] = jnp.exp(a_blk) * state + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, la_ref, do_ref, o_ref, dstate_ref,
                    dk_ref, dv_ref, dla_ref, dstate_scratch, r_scratch):
    """Reverse-order pass carrying the suffix dstate N (+ suffix decay-grad
    scalar). Block index maps are reversed, so program 0 sees the LAST
    sequence block and N is seeded with the state cotangent ``dM``."""
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _init():
        dstate_scratch[...] = dstate_ref[0].astype(jnp.float32)
        r_scratch[...] = jnp.zeros_like(r_scratch)

    q = q_ref[0].astype(jnp.float32)          # (C, dk)
    k = k_ref[0].astype(jnp.float32)          # (C, dk)
    v = v_ref[0].astype(jnp.float32)          # (C, dv)
    la = la_ref[0].astype(jnp.float32)        # (1, C)
    do = do_ref[0].astype(jnp.float32)        # (C, dv)
    o = o_ref[0].astype(jnp.float32)          # (C, dv)

    cb = cumsum_col(la)                       # (C, 1)
    a_blk = jnp.sum(la, axis=1, keepdims=True)  # (1, 1)
    dmat = _decay_mat(cb)
    w = jnp.exp(a_blk - cb)                    # (C, 1) e^{A - cb_j} <= 1
    n = dstate_scratch[...]                    # (dk, dv) suffix dstate

    # dk_j = sum_{i>=j} e^{cb_i-cb_j}(dO_i·v_j) q_i + w_j (N v_j)
    dsc = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * dmat            # (C, C)
    dk = jax.lax.dot_general(
        dsc, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                    # (C, dk)
    dk = dk + w * jax.lax.dot_general(
        v, n, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    # dv_j = sum_{i>=j} e^{cb_i-cb_j}(q_i·k_j) dO_i + w_j (N^T k_j)
    sc = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * dmat             # (C, C)
    dv = jax.lax.dot_general(
        sc, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                    # (C, dv)
    dv = dv + w * jax.lax.dot_general(
        k, n, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    # decay grad: dlog_a_m = Σ_{i>=m} r_i (suffix over the whole sequence),
    # r_i = dO_i·o_i − k_i·dk_i; in-block inclusive suffix sum (in the
    # (1, C) row layout of dla) + the carried sum over later blocks.
    r = (jnp.sum(do * o, axis=1, keepdims=True)
         - jnp.sum(k * dk, axis=1, keepdims=True))            # (C, 1)
    dla_ref[0] = suffix_sum_row(r) + r_scratch[...]
    r_scratch[...] = r_scratch[...] + jnp.sum(r, axis=0, keepdims=True)

    # carry to the previous block: N' = e^A N + sum_i e^{cb_i} q_i^T dO_i
    qw = q * jnp.exp(cb)
    dstate_scratch[...] = jnp.exp(a_blk) * n + jax.lax.dot_general(
        qw, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def lasp2_chunk_bwd(q, k, v, log_a, o, do, dstate, *,
                    block_size: int = DEFAULT_BLOCK, interpret: bool = False):
    """Backward of :func:`lasp2_chunk_fwd` wrt (q, k, v, log_a).

    ``o`` is the saved forward output; ``do``/``dstate`` are the cotangents
    of the output and the end-of-chunk state. Returns
    ``(dq, dk, dv, dla_partial)`` where ``dla_partial`` still needs the
    constant ``⟨state, dM⟩ + dA`` term (added by the custom_vjp wrapper,
    which owns the ``state``/``log_decay`` residuals).
    """
    bh, s, dk = q.shape
    dv = v.shape[-1]
    if s % block_size:
        raise ValueError(f"S={s} must be divisible by block={block_size}")
    nb = s // block_size

    fwd_order = lambda b, t: (b, t, 0)
    rev_order = lambda b, t: (b, nb - 1 - t, 0)
    rev_row = lambda b, t: (b, 0, nb - 1 - t)
    la_rows = log_a.reshape(bh, 1, s)

    dq = pl.pallas_call(
        _bwd_dq_kernel,
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, block_size, dk), fwd_order),
            pl.BlockSpec((1, block_size, dv), fwd_order),
            pl.BlockSpec((1, 1, block_size), lambda b, t: (b, 0, t)),
            pl.BlockSpec((1, block_size, dv), fwd_order),
        ],
        out_specs=pl.BlockSpec((1, block_size, dk), fwd_order),
        out_shape=jax.ShapeDtypeStruct((bh, s, dk), q.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="lasp2_chunk_bwd_dq",
    )(k, v, la_rows, do)

    dk_out, dv_out, dla = pl.pallas_call(
        _bwd_dkv_kernel,
        grid=(bh, nb),
        in_specs=[
            pl.BlockSpec((1, block_size, dk), rev_order),
            pl.BlockSpec((1, block_size, dk), rev_order),
            pl.BlockSpec((1, block_size, dv), rev_order),
            pl.BlockSpec((1, 1, block_size), rev_row),
            pl.BlockSpec((1, block_size, dv), rev_order),
            pl.BlockSpec((1, block_size, dv), rev_order),
            pl.BlockSpec((1, dk, dv), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_size, dk), rev_order),
            pl.BlockSpec((1, block_size, dv), rev_order),
            pl.BlockSpec((1, 1, block_size), rev_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dk), k.dtype),
            jax.ShapeDtypeStruct((bh, s, dv), v.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="lasp2_chunk_bwd_dkv",
    )(q, k, v, la_rows, do, o, dstate)
    return dq, dk_out, dv_out, dla.reshape(bh, s)


# ---------------------------------------------------------------------------
# Differentiable entry point (custom_vjp over the two Pallas passes).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def lasp2_chunk(q, k, v, log_a, block_size=DEFAULT_BLOCK, interpret=False):
    """Trainable chunked decayed causal linear attention (Pallas).

    Same signature/returns as :func:`lasp2_chunk_fwd`, but differentiable:
    ``jax.grad`` dispatches to the two-pass backward kernels. All three
    outputs ``(o, state, log_decay)`` accept cotangents — the faithful SP
    backward (paper Alg. 4) pulls on both ``o`` and ``state``.
    """
    return lasp2_chunk_fwd(q, k, v, log_a, block_size=block_size,
                           interpret=interpret)


def _chunk_vjp_fwd(q, k, v, log_a, block_size, interpret):
    o, state, ld = lasp2_chunk_fwd(q, k, v, log_a, block_size=block_size,
                                   interpret=interpret)
    return (o, state, ld), (q, k, v, log_a, o, state)


def _chunk_vjp_bwd(block_size, interpret, res, cots):
    q, k, v, log_a, o, state = res
    do, dstate, dld = cots
    dq, dk, dv, dla = lasp2_chunk_bwd(
        q, k, v, log_a, o, do, dstate.astype(jnp.float32),
        block_size=block_size, interpret=interpret)
    # ∂L/∂log_a_m also carries the end-of-chunk terms ⟨state, dM⟩ + dA,
    # identical for every position m (they sit behind the full decay chain).
    const = (jnp.einsum("bkv,bkv->b", state, dstate.astype(jnp.float32))
             + dld.astype(jnp.float32))
    dla = (dla + const[:, None]).astype(log_a.dtype)
    return dq, dk, dv, dla


lasp2_chunk.defvjp(_chunk_vjp_fwd, _chunk_vjp_bwd)
