"""Pallas TPU kernels: intra-chunk decayed causal linear attention, fwd+bwd.

This is the compute hot-spot of LASP-2 (paper Alg. 2 lines 5–8): each
device's local sequence chunk is processed block-by-block, carrying the
``dk × dv`` memory state in VMEM scratch across the (sequential) block grid
dimension. The cross-device part (the AllGather of chunk states) lives in
``repro.core.lasp2``; this kernel is the per-device "intra" workhorse it
overlaps with.

TPU adaptation of the paper's Triton kernel:

* chunks are ``(BLOCK, dk/dv)`` tiles, MXU-aligned (128 lanes); the three
  matmuls per chunk (``QK^T``, ``scores·V``, ``K^T V``) hit the MXU with
  fp32 accumulation via ``preferred_element_type``;
* the memory state is fp32 in VMEM *scratch* that persists across the
  sequential grid axis — the HBM↔VMEM traffic per chunk is just the
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

Layout: inputs are flattened to ``(BH, S, d)``; grid = ``(BH, S//T)``
with ``dimension_semantics=("parallel", "arbitrary")`` so distinct
batch·head programs parallelize across cores while tiles run in order.
A tile holds ``T`` tokens, several chunks of ``BLOCK``: :func:`seq_tile`
picks ``T`` from ``S`` alone, and a grid step runs its chunks one after
another with today's per-chunk math (the chunk size stays ``BLOCK``), so
the fixed cost of a grid step is paid once per tile. ``log_a`` and its
gradient move as ``(BH, 1, S)`` arrays in ``(1, T)`` rows
(``repro.kernels.layout``); the total log decay is a plain sum outside
the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import col_to_row, cumsum_col, suffix_sum_row, tri

DEFAULT_BLOCK = 128
# Most tokens one grid step holds. A step has a fixed cost (about a third
# of a microsecond on a v5e) that one 128-token chunk cannot hide; a tile of
# several chunks pays it once. Chosen on a v5e (PERF.md: the sweep, and
# why not 2048).
MAX_TILE = 1024


def seq_tile(s: int, chunk: int) -> int:
    """Tokens per grid step for a sequence of ``s`` tokens in chunks of
    ``chunk``: the largest multiple of ``chunk`` that divides ``s``, is at
    most ``MAX_TILE``, and is a multiple of 128 or all of ``s`` (the (8, 128)
    tiling of the ``(1, 1, T)`` log-decay rows); ``chunk`` when none is
    larger."""
    tile = chunk
    for t in range(2 * chunk, min(s, MAX_TILE) + 1, chunk):
        if s % t == 0 and (t % 128 == 0 or t == s):
            tile = t
    return tile


def _chunks(tile: int, chunk: int):
    """Static slices of the chunks of one tile, in sequence order."""
    return [slice(j, j + chunk) for j in range(0, tile, chunk)]


def _decay_mat(cb):
    """D_ij = exp(cb_i - cb_j) for i >= j else 0 (all factors <= 1);
    ``cb`` is the ``(c, 1)`` cumulative log decay column."""
    row, col = tri(cb.shape[0])
    diff = cb - col_to_row(cb)
    return jnp.where(row >= col, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)


def _decays(la_ref, rows):
    """The chunk's ``(1, C)`` log decays as their inclusive cumulative sum
    ``cb`` (a ``(C, 1)`` column), the chunk total ``A`` ``(1, 1)`` and the
    decay matrix."""
    la = la_ref[0, :, rows].astype(jnp.float32)     # (1, C)
    cb = cumsum_col(la)
    return cb, jnp.sum(la, axis=1, keepdims=True), _decay_mat(cb)


def _dot_nt(x, y):
    """``x @ y^T`` of two tiles as they arrive, accumulated in fp32. Of two
    bf16 inputs every product is exact in fp32, so feeding them to the MXU
    as bf16 changes nothing but the order of the sums."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _carry(k, v, cb, a_blk, state):
    """Prefix state after a chunk: M <- e^A M + (k ⊙ e^{A-cb})^T v."""
    kw = k * jnp.exp(a_blk - cb)
    return jnp.exp(a_blk) * state + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _kernel(q_ref, k_ref, v_ref, la_ref, o_ref, state_ref, state_scratch,
            *, chunk: int, ntiles: int):
    tile = pl.program_id(1)

    @pl.when(tile == 0)
    def _init():
        state_scratch[...] = jnp.zeros_like(state_scratch)

    state = state_scratch[...]
    for rows in _chunks(q_ref.shape[1], chunk):
        q_in, k_in = q_ref[0, rows], k_ref[0, rows]
        q = q_in.astype(jnp.float32)                # (C, dk)
        k = k_in.astype(jnp.float32)                # (C, dk)
        v = v_ref[0, rows].astype(jnp.float32)     # (C, dv)
        cb, a_blk, dmat = _decays(la_ref, rows)

        scores = _dot_nt(q_in, k_in) * dmat                     # (C, C)
        o_intra = jax.lax.dot_general(
            scores, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # (C, dv)
        # inter (within-device, previous chunks): (q ⊙ b) @ S_carry
        o_inter = jax.lax.dot_general(
            q * jnp.exp(cb), state, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0, rows] = (o_intra + o_inter).astype(o_ref.dtype)
        state = _carry(k, v, cb, a_blk, state)
    state_scratch[...] = state

    @pl.when(tile == ntiles - 1)
    def _finalize():
        state_ref[0] = state


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
    tile = seq_tile(s, block_size)
    nt = s // tile

    kernel = functools.partial(_kernel, chunk=block_size, ntiles=nt)
    o, state = pl.pallas_call(
        kernel,
        grid=(bh, nt),
        in_specs=[
            pl.BlockSpec((1, tile, dk), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, tile, dk), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, tile, dv), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, 1, tile), lambda b, t: (b, 0, t)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile, dv), lambda b, t: (b, t, 0)),
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

def _bwd_dq_kernel(k_ref, v_ref, la_ref, do_ref, dq_ref, state_scratch, *,
                   chunk: int):
    """Forward-order pass: dq_i = dO_i M_i^T, re-carrying the prefix state."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_scratch[...] = jnp.zeros_like(state_scratch)

    state = state_scratch[...]
    for rows in _chunks(k_ref.shape[1], chunk):
        v_in, do_in = v_ref[0, rows], do_ref[0, rows]
        k = k_ref[0, rows].astype(jnp.float32)     # (C, dk)
        v = v_in.astype(jnp.float32)                # (C, dv)
        do = do_in.astype(jnp.float32)              # (C, dv)
        cb, a_blk, dmat = _decays(la_ref, rows)
        # intra: dq_i += sum_{j<=i} e^{cb_i-cb_j} (dO_i·v_j) k_j
        dsc = _dot_nt(do_in, v_in) * dmat                       # (C, C)
        dq_intra = jax.lax.dot_general(
            dsc, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # (C, dk)
        # inter: dq_i += e^{cb_i} dO_i M_prev^T
        dq_inter = jax.lax.dot_general(
            do, state, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.exp(cb)
        dq_ref[0, rows] = (dq_intra + dq_inter).astype(dq_ref.dtype)
        state = _carry(k, v, cb, a_blk, state)
    state_scratch[...] = state


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, la_ref, do_ref, o_ref, dstate_ref,
                    dk_ref, dv_ref, dla_ref, dstate_scratch, r_scratch, *,
                    chunk: int):
    """Reverse-order pass carrying the suffix dstate N (+ suffix decay-grad
    scalar). Tile index maps are reversed and the chunks of a tile run last
    to first, so the first chunk seen is the sequence's LAST and N is
    seeded with the state cotangent ``dM``."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        dstate_scratch[...] = dstate_ref[0].astype(jnp.float32)
        r_scratch[...] = jnp.zeros_like(r_scratch)

    n = dstate_scratch[...]                    # (dk, dv) suffix dstate
    r_sum = r_scratch[...]                     # (1, 1) Σ r over later chunks
    for rows in reversed(_chunks(q_ref.shape[1], chunk)):
        q_in, k_in = q_ref[0, rows], k_ref[0, rows]
        v_in, do_in = v_ref[0, rows], do_ref[0, rows]
        q = q_in.astype(jnp.float32)                # (C, dk)
        k = k_in.astype(jnp.float32)                # (C, dk)
        v = v_in.astype(jnp.float32)                # (C, dv)
        do = do_in.astype(jnp.float32)              # (C, dv)
        o = o_ref[0, rows].astype(jnp.float32)     # (C, dv)
        cb, a_blk, dmat = _decays(la_ref, rows)
        w = jnp.exp(a_blk - cb)                     # (C, 1) e^{A - cb_j} <= 1

        # dk_j = sum_{i>=j} e^{cb_i-cb_j}(dO_i·v_j) q_i + w_j (N v_j)
        dsc = _dot_nt(do_in, v_in) * dmat                       # (C, C)
        dk = jax.lax.dot_general(
            dsc, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # (C, dk)
        dk = dk + w * jax.lax.dot_general(
            v, n, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dv_j = sum_{i>=j} e^{cb_i-cb_j}(q_i·k_j) dO_i + w_j (N^T k_j)
        sc = _dot_nt(q_in, k_in) * dmat                         # (C, C)
        dv = jax.lax.dot_general(
            sc, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # (C, dv)
        dv = dv + w * jax.lax.dot_general(
            k, n, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_ref[0, rows] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows] = dv.astype(dv_ref.dtype)

        # decay grad: dlog_a_m = Σ_{i>=m} r_i (suffix over the whole
        # sequence), r_i = dO_i·o_i − k_i·dk_i; in-chunk inclusive suffix
        # sum (in the (1, C) row layout of dla) + the carried sum over
        # later chunks.
        r = (jnp.sum(do * o, axis=1, keepdims=True)
             - jnp.sum(k * dk, axis=1, keepdims=True))         # (C, 1)
        dla_ref[0, :, rows] = suffix_sum_row(r) + r_sum
        r_sum = r_sum + jnp.sum(r, axis=0, keepdims=True)

        # carry to the previous chunk: N' = e^A N + sum_i e^{cb_i} q_i^T dO_i
        qw = q * jnp.exp(cb)
        n = jnp.exp(a_blk) * n + jax.lax.dot_general(
            qw, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    dstate_scratch[...] = n
    r_scratch[...] = r_sum


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
    tile = seq_tile(s, block_size)
    nt = s // tile

    fwd_order = lambda b, t: (b, t, 0)
    rev_order = lambda b, t: (b, nt - 1 - t, 0)
    rev_row = lambda b, t: (b, 0, nt - 1 - t)
    la_rows = log_a.reshape(bh, 1, s)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, chunk=block_size),
        grid=(bh, nt),
        in_specs=[
            pl.BlockSpec((1, tile, dk), fwd_order),
            pl.BlockSpec((1, tile, dv), fwd_order),
            pl.BlockSpec((1, 1, tile), lambda b, t: (b, 0, t)),
            pl.BlockSpec((1, tile, dv), fwd_order),
        ],
        out_specs=pl.BlockSpec((1, tile, dk), fwd_order),
        out_shape=jax.ShapeDtypeStruct((bh, s, dk), q.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="lasp2_chunk_bwd_dq",
    )(k, v, la_rows, do)

    dk_out, dv_out, dla = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, chunk=block_size),
        grid=(bh, nt),
        in_specs=[
            pl.BlockSpec((1, tile, dk), rev_order),
            pl.BlockSpec((1, tile, dk), rev_order),
            pl.BlockSpec((1, tile, dv), rev_order),
            pl.BlockSpec((1, 1, tile), rev_row),
            pl.BlockSpec((1, tile, dv), rev_order),
            pl.BlockSpec((1, tile, dv), rev_order),
            pl.BlockSpec((1, dk, dv), lambda b, t: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile, dk), rev_order),
            pl.BlockSpec((1, tile, dv), rev_order),
            pl.BlockSpec((1, 1, tile), rev_row),
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
