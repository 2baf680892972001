"""Backend dispatch for the perf-critical ops.

Model code calls these wrappers; on TPU the Pallas kernels run, elsewhere
(this CPU container, the dry-run) the mathematically-identical XLA path
from ``repro.core`` runs. ``backend="interpret"`` forces Pallas interpret
mode (used by tests). The dispatch is deliberately value-free: same
signatures, same semantics, sub-1e-3 numerical agreement enforced by
``tests/test_kernels.py``.

All three backends of :func:`linear_attention_op` are differentiable:
the XLA path via plain autodiff of ``chunk_scan``, the Pallas paths via
the two-pass backward kernels behind ``lasp2_chunk``'s ``custom_vjp``
(including the data-dependent ``log_a`` gradient and cotangents on the
end-of-chunk ``state`` — what the faithful LASP-2 backward pulls on).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import compat as _compat
from repro.core.linear_attention import (chunk_scan, pick_block,
                                         recurrent_step)
from repro.kernels import flash_attention as _flash
from repro.kernels import lasp2_chunk as _chunk
from repro.kernels import lasp2_decode as _decode

BACKENDS = ("xla", "pallas", "interpret")


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolve_backend(backend: Optional[str]) -> str:
    """``None`` → platform default; otherwise validate the name."""
    if backend is None:
        return default_backend()
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; expected "
                         f"one of {BACKENDS}")
    return backend


def linear_attention_op(q, k, v, log_a=None, *, block_size: int = 128,
                        backend: Optional[str] = None):
    """Local chunked decayed causal linear attention (differentiable).

    q, k: (..., S, dk); v: (..., S, dv); log_a: (..., S) or None.
    Returns (o, state (..., dk, dv) fp32, log_decay (...,) fp32).
    """
    backend = resolve_backend(backend)
    *lead, s, dk = q.shape
    dv = v.shape[-1]
    if log_a is None:
        log_a = jnp.zeros((*lead, s), jnp.float32)
    # Block policy is shared with core/lasp2.py (``pick_block``): the
    # preferred block when it divides S, else the largest MXU-aligned
    # divisor. Serving prefill additionally sees arbitrary prompt lengths
    # where no usable divisor exists (e.g. prime S) — rather than
    # degenerating toward 1-token blocks, right-pad to the next block
    # multiple: zero k/v rows add nothing to the state and log_a = 0
    # leaves the decay product alone, so outputs (sliced back to S),
    # final state, and log decay are exact. The Pallas kernel then runs
    # several chunks a grid step, in tiles it picks from S alone
    # (``lasp2_chunk.seq_tile``).
    bs = pick_block(s, block_size)
    if bs != s and bs % 32:
        bs = min(block_size, s)
    if s % bs:
        pad = bs - s % bs
        zkv = ((0, 0),) * (q.ndim - 2) + ((0, pad), (0, 0))
        q, k, v = (jnp.pad(x, zkv) for x in (q, k, v))
        log_a = jnp.pad(log_a, ((0, 0),) * (log_a.ndim - 1) + ((0, pad),))
        o, st, ld = linear_attention_op(q, k, v, log_a,
                                        block_size=block_size,
                                        backend=backend)
        return o[..., :s, :], st, ld
    if backend in ("pallas", "interpret"):
        bh = math.prod(lead)
        o, st, ld = _chunk.lasp2_chunk(
            q.reshape(bh, s, dk), k.reshape(bh, s, dk),
            v.reshape(bh, s, dv), log_a.reshape(bh, s),
            bs, backend == "interpret")
        return (o.reshape(*lead, s, dv), st.reshape(*lead, dk, dv),
                ld.reshape(*lead))
    out = chunk_scan(q, k, v, log_a, block_size=bs)
    return out.o, out.state, out.log_decay


def linear_decode_op(q, k, v, log_a, state, log_decay, *,
                     backend: Optional[str] = None):
    """Single-token recurrent linear-attention decode (``mode="decode"``).

    q, k: (B, H, dk); v: (B, H, dv); log_a: (B, H) or None;
    state: (B, H, dk, dv) fp32; log_decay: (B, H) fp32.
    Returns (o (B, H, dv) fp32, state', log_decay') — the constant-memory
    decode path: no prefix re-scan, state updated in place.
    """
    backend = resolve_backend(backend)
    b, h, dk = q.shape
    dv = v.shape[-1]
    if log_a is None:
        log_a = jnp.zeros((b, h), jnp.float32)
    if backend in ("pallas", "interpret"):
        o, st, ld = _decode.lasp2_decode_step(
            q.reshape(b * h, dk), k.reshape(b * h, dk),
            v.reshape(b * h, dv), log_a.reshape(b * h),
            state.reshape(b * h, dk, dv), log_decay.reshape(b * h),
            interpret=(backend == "interpret"))
        return (o.reshape(b, h, dv), st.reshape(b, h, dk, dv),
                ld.reshape(b, h))
    return recurrent_step(q, k, v, log_a, state=state, log_decay=log_decay)


def _round_up(n: int, m: int) -> int:
    """``n`` itself when it is at most ``m``, else the next multiple of
    ``m``."""
    return n if n <= m else -(-n // m) * m


def flash_attention_op(q, k, v, *, causal: bool = True, sliding_window=None,
                       scale=None, backend: Optional[str] = None,
                       block_q: Optional[int] = None,
                       block_k: Optional[int] = None, q_offset=None):
    """GQA softmax attention (differentiable). q: (B,Hq,S,dh); k/v:
    (B,Hkv,Sk,dh).

    For ``sq != sk`` (prefill-with-cache / ring-decode shapes) queries sit
    at global positions ``(sk - sq) + i`` — the same ``q_offset``
    convention on the Pallas kernel and the XLA mask fallback. Callers
    with a different origin (the LASP-2H rank offset ``t·C``) pass
    ``q_offset`` explicitly; a traced scalar is accepted.

    Awkward (non-block-multiple) ``sq``/``sk`` are right-padded to block
    multiples — mask-safe: padded keys are masked out via the kernel's
    ``kv_len`` and padded query rows are sliced off (their cotangents are
    zeroed by the pad/slice transpose) — so the Pallas path runs on odd
    prompt lengths instead of silently dropping to XLA.

    Block defaults: with ``block_q`` / ``block_k`` left ``None``, each
    length over 128 is padded to the next multiple of 128 (by less than
    128) and the kernel's ``pick_tiles`` takes the tiles from the padded
    lengths, the window and the input dtype; a length of at most 128 is
    one whole tile. Explicit blocks are honoured, each capped at its
    length, and the lengths padded to them.
    """
    backend = resolve_backend(backend)
    if _compat.is_tracer(sliding_window):
        backend = "xla"   # dynamic window (hymba stacked layers) → XLA path
    sq, sk = q.shape[2], k.shape[2]
    if q_offset is None:
        q_offset = sk - sq
    if backend in ("pallas", "interpret"):
        auto_q, auto_k = _flash.pick_tiles(
            _round_up(sq, 128), _round_up(sk, 128),
            sliding_window=sliding_window, dtype=q.dtype)
        bq = auto_q if block_q is None else min(block_q, sq)
        bk = auto_k if block_k is None else min(block_k, sk)
        pad_q, pad_k = -sq % bq, -sk % bk
        if pad_q:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        if pad_k:
            k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        o = _flash.flash_attention(
            q, k, v, causal=causal, sliding_window=sliding_window,
            scale=scale, q_offset=q_offset, kv_len=sk, block_q=bq,
            block_k=bk, interpret=(backend == "interpret"))
        return o[..., :sq, :] if pad_q else o
    # Imported lazily: lasp2h imports core.lasp2 (SPConfig), which in turn
    # dispatches its intra-chunk compute through this module — a top-level
    # import here would close that cycle.
    from repro.core.lasp2h import _softmax_attend, causal_mask
    if scale is None:
        scale = q.shape[-1] ** -0.5
    mask = None
    if causal:
        mask = causal_mask(sq, sk, q_offset=q_offset,
                           sliding_window=sliding_window)[None, None]
    elif sliding_window is not None:
        # Non-causal + window: the kernel applies only the one-sided
        # window bound (no future cutoff) — mirror that here instead of
        # sneaking the causal mask in via causal_mask.
        qpos = q_offset + jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        mask = ((qpos - kpos) < sliding_window)[None, None]
    return _softmax_attend(q, k, v, scale=scale, mask=mask)
