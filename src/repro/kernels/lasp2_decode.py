"""Pallas TPU kernel: single-token recurrent linear-attention decode.

The serving hot loop (paper's constant-memory inference): every step
multiplies the fp32 ``dk × dv`` memory state by the token's decay, adds the
rank-1 update ``k^T v``, and reads it out with ``q`` — no re-scan of the
prefix, no KV cache. One program per batch·head keeps the whole state
resident in VMEM for the three small matmuls; HBM traffic is exactly the
state in + state out + the q/k/v vectors, which is what makes batched
decode memory-bound on the state and O(1) in context length.

Mirrors ``repro.core.linear_attention.recurrent_step`` (the XLA path ops.py
falls back to off-TPU); agreement is enforced by ``tests/test_kernels.py``
in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, la_ref, m_ref, o_ref, m_out_ref):
    q = q_ref[0].astype(jnp.float32)          # (1, dk)
    k = k_ref[0].astype(jnp.float32)          # (1, dk)
    v = v_ref[0].astype(jnp.float32)          # (1, dv)
    la = la_ref[0]                            # (1, 1) log decay
    m = m_ref[0]                              # (dk, dv) fp32

    a = jnp.exp(la)
    # M' = a·M + k^T v  (rank-1 outer product on the MXU)
    kv = jax.lax.dot_general(k, v, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    m_new = a * m + kv
    # o = q M'
    o = jax.lax.dot_general(q, m_new, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0] = o.astype(o_ref.dtype)
    m_out_ref[0] = m_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def lasp2_decode_step(q, k, v, log_a, state, log_decay, *,
                      interpret: bool = False):
    """Batched single-token recurrent decode, Pallas TPU.

    q, k: (BH, dk); v: (BH, dv); log_a: (BH,); state: (BH, dk, dv) fp32;
    log_decay: (BH,) fp32.
    Returns (o (BH, dv) fp32, state' (BH, dk, dv) fp32, log_decay' (BH,)).
    """
    bh, dk = q.shape
    dv = v.shape[-1]
    la = log_a.astype(jnp.float32)
    o, m_new = pl.pallas_call(
        _kernel,
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((1, 1, dk), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, dk), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, dv), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, dv), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, 1, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="lasp2_decode_step",
    )(q[:, None, :], k[:, None, :], v[:, None, :], la.reshape(bh, 1, 1),
      state.astype(jnp.float32))
    return o[:, 0, :], m_new, log_decay.astype(jnp.float32) + la
