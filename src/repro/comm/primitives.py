"""Named SP collectives with trace-time communication accounting.

Each primitive performs exactly one logical exchange via the matching
``jax.lax`` collective and appends a :class:`CommRecord` to the ambient
tape (:func:`tape`): the op name, the per-device wire traffic under the
standard ring cost model (the same model ``repro.launch.hlo_analysis``
applies to compiled HLO), and the number of *sequential* exchange steps
the call represents. Records are computed from static shapes at trace
time, so wrapping ``jax.jit(fn).lower(...)`` in a tape captures a
program's full communication budget without running it:

    with comm.tape() as records:
        jax.jit(step).lower(batch)
    bytes_on_wire = sum(r.traffic_bytes for r in records)

The tape is advisory (benchmarks, reports); the *enforced* budget checks
parse compiled HLO instead (:mod:`repro.comm.budget`), so the two views
cross-validate each other.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.obs.scopes import comm_scope

_TAPE = threading.local()

# Wire-dtype registry for the ``comm_dtype`` knob (docs/communication.md):
# exchanges cast their payload to this dtype before the collective and
# accumulate/combine in fp32 locally. "bf16" halves every state/KV
# exchange's bytes (ZeCO's observation: comm *volume*, not just count,
# limits SP scalability) at ~3 decimal digits of payload precision.
_COMM_DTYPES = {
    "fp32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
}


def wire_dtype(comm_dtype: Optional[str]):
    """Resolve a ``comm_dtype`` knob value ("fp32" | "bf16") to a dtype."""
    if comm_dtype is None:
        return jnp.float32
    try:
        return _COMM_DTYPES[comm_dtype]
    except KeyError:
        raise ValueError(
            f"unknown comm_dtype {comm_dtype!r}; expected one of "
            f"{tuple(_COMM_DTYPES)}") from None


@jax.custom_vjp
def _pin(x):
    """Identity that XLA passes cannot look through (values unchanged)."""
    return jax.lax.optimization_barrier(x)


def _pin_fwd(x):
    return _pin(x), None


def _pin_bwd(_, ct):
    return (ct,)


_pin.defvjp(_pin_fwd, _pin_bwd)


def upcast_gathered(x, dtype=jnp.float32):
    """Upcast a gathered wire-dtype payload to the local accumulate dtype
    *behind an optimization barrier*.

    Without the barrier XLA's convert-mover commutes the upcast across
    the adjacent collective ("convert processes 1/W the data before the
    gather") — undoing the comm_dtype bf16 halving by putting the fp32
    payload back on the wire (observed on XLA-CPU, whose cost model does
    not price collective bytes). A no-op when no cast happened.
    """
    if x.dtype == jnp.dtype(dtype):
        return x
    return _pin(x).astype(dtype)


@dataclass(frozen=True)
class CommRecord:
    """One collective issued by an SP layer (static, trace-time)."""

    op: str              # all-gather | collective-permute | reduce-scatter
    payload_bytes: int   # bytes entering the collective, per device
    traffic_bytes: int   # per-device wire traffic (ring cost model)
    steps: int           # sequential exchange steps this call represents
    group: int           # devices participating
    tag: str = ""        # call-site label, e.g. "lasp2.states"


@contextmanager
def tape():
    """Collect CommRecords from every primitive traced inside the block."""
    prev = getattr(_TAPE, "records", None)
    _TAPE.records = []
    try:
        yield _TAPE.records
    finally:
        _TAPE.records = prev


def _record(rec: CommRecord) -> None:
    records = getattr(_TAPE, "records", None)
    if records is not None:
        records.append(rec)


def tape_summary(records: List[CommRecord]) -> Dict[str, float]:
    """Totals per op + overall, mirroring hlo_analysis.collective_summary."""
    out: Dict[str, float] = {}
    for r in records:
        out[r.op] = out.get(r.op, 0) + r.traffic_bytes
        out[f"{r.op}_count"] = out.get(f"{r.op}_count", 0) + 1
        out[f"{r.op}_steps"] = out.get(f"{r.op}_steps", 0) + r.steps
    out["total_bytes"] = sum(r.traffic_bytes for r in records)
    out["total_steps"] = sum(r.steps for r in records)
    return out


def _nbytes(x) -> int:
    return int(x.size) * x.dtype.itemsize


# ---------------------------------------------------------------------------
# The collectives.
# ---------------------------------------------------------------------------

def allgather_states(x, axis: str, *, axis_size: int, gather_axis: int = 0,
                     tiled: bool = False, tag: str = ""):
    """AllGather along mesh axis ``axis`` — THE LASP-2 exchange.

    Traffic per device (ring model): ``(g-1) × payload`` — the result is
    ``g × payload`` of which ``(g-1)/g`` crosses the wire. One collective
    call = one sequential step regardless of group size: the whole point
    of LASP-2 vs the ring (paper §3.4).
    """
    pb = _nbytes(x)
    _record(CommRecord("all-gather", pb, (axis_size - 1) * pb, steps=1,
                       group=axis_size, tag=tag))
    with comm_scope(tag, "all-gather"):
        return jax.lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def ring_sendrecv(x, axis: str, *, axis_size: int, shift: int = 1,
                  loop_trips: int = 1, tag: str = ""):
    """One ring hop: every rank sends ``x`` to ``(rank + shift) % W``.

    Implemented with ``ppermute``; per-device traffic = payload, one
    sequential step. ``loop_trips``: when called once inside a
    ``fori_loop`` body that executes W times, pass ``loop_trips=W`` so the
    tape stays honest (HLO also shows while bodies once — the budget
    checker has the same caveat).
    """
    perm = [(i, (i + shift) % axis_size) for i in range(axis_size)]
    pb = _nbytes(x)
    _record(CommRecord("collective-permute", pb, pb * loop_trips,
                       steps=loop_trips, group=axis_size, tag=tag))
    with comm_scope(tag, "collective-permute"):
        return jax.lax.ppermute(x, axis, perm)


def reduce_scatter_grads(x, axis: str, *, axis_size: int,
                         scatter_axis: int = 0, tiled: bool = True,
                         tag: str = ""):
    """Reduce-scatter along ``axis`` — the AD transpose of the state
    AllGather (what ``backward="autodiff"`` puts on the wire; emitted
    explicitly here for callers that hand-write the mirrored backward).

    Traffic per device: ``(g-1)/g × payload`` (result is payload / g).
    """
    pb = _nbytes(x)
    _record(CommRecord("reduce-scatter", pb,
                       (axis_size - 1) * pb // axis_size, steps=1,
                       group=axis_size, tag=tag))
    with comm_scope(tag, "reduce-scatter"):
        return jax.lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                                    tiled=tiled)


def psum_packed(x, axes, *, group_size: int, tag: str = ""):
    """All-reduce ``x`` over ``axes`` (a mesh axis name or tuple of them)
    in ONE collective — the 2D train step's single gradient reduction
    (all microbatch-accumulated gradients plus the loss/token counters are
    raveled into one fp32 vector first; see ``repro.train.step``).

    Traffic per device (ring model): ``2(g-1)/g × payload``.
    """
    pb = _nbytes(x)
    _record(CommRecord("all-reduce", pb,
                       2 * (group_size - 1) * pb // max(group_size, 1),
                       steps=1, group=group_size, tag=tag))
    with comm_scope(tag, "all-reduce"):
        return jax.lax.psum(x, axes)


def multi_axis_index(axis):
    """``jax.lax.axis_index`` that also accepts a TUPLE of axis names.

    Returns the mixed-radix rank index with the FIRST axis most
    significant — the same ordering ``jax.lax.all_gather`` uses when
    concatenating over a tuple of axes, so the value is directly usable
    as the gathered-chunk index ``t`` of this rank's shard.
    """
    if isinstance(axis, (tuple, list)):
        t = jax.lax.axis_index(axis[0])
        for a in axis[1:]:
            t = t * jax.lax.psum(1, a) + jax.lax.axis_index(a)
        return t
    return jax.lax.axis_index(axis)


def _alltoall_impl(x, axis, axis_size, split_axis, concat_axis, tag):
    pb = _nbytes(x)
    _record(CommRecord("all-to-all", pb,
                       (axis_size - 1) * pb // max(axis_size, 1), steps=1,
                       group=axis_size, tag=tag))
    with comm_scope(tag, "all-to-all"):
        return jax.lax.all_to_all(x, axis, split_axis, concat_axis,
                                  tiled=True)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _alltoall(x, axis, axis_size, split_axis, concat_axis, tag):
    return _alltoall_impl(x, axis, axis_size, split_axis, concat_axis, tag)


def _alltoall_fwd(x, axis, axis_size, split_axis, concat_axis, tag):
    return _alltoall_impl(x, axis, axis_size, split_axis, concat_axis,
                          tag), None


def _alltoall_bwd(axis, axis_size, split_axis, concat_axis, tag, _, ct):
    # The AD transpose of an all-to-all is the all-to-all with the split/
    # concat dims swapped — the mirrored pair, recorded on the tape like
    # any forward exchange.
    return (_alltoall_impl(ct, axis, axis_size, concat_axis, split_axis,
                           f"{tag}.bwd" if tag else "alltoall.bwd"),)


_alltoall.defvjp(_alltoall_fwd, _alltoall_bwd)


def alltoall(x, axis: str, *, axis_size: int, split_axis: int,
             concat_axis: int, tag: str = ""):
    """Tiled All-to-All over mesh axis ``axis`` — the Ulysses repartition.

    Splits ``split_axis`` into ``axis_size`` chunks (chunk j to rank j),
    concatenating the received chunks along ``concat_axis`` in rank
    order: ``dim[split] /= g``, ``dim[concat] *= g``. Traffic per device
    (ring model): ``(g-1)/g × payload`` — each rank keeps its own chunk.
    One collective call = one sequential step.

    Differentiable via ``custom_vjp``: the backward is the mirrored
    all-to-all (split/concat swapped), so autodiff through a
    seq→head→seq repartition pair costs exactly two more all-to-alls
    and the trace-time tape stays honest in both directions.
    """
    return _alltoall(x, axis, axis_size, split_axis, concat_axis, tag)


# ---------------------------------------------------------------------------
# Ring / pipelined prefix-scan exchanges (LASP-1 pattern, ZeCO refinement).
# ---------------------------------------------------------------------------

def auto_slices(dv: int, preferred: int = 4) -> int:
    """Slice count for the pipelined exchange: largest power of two
    <= ``preferred`` dividing the state's value dimension."""
    n = preferred
    while n > 1 and dv % n:
        n //= 2
    return max(n, 1)


def _prefix_chain(m_slice, chunk_decay, axis: str, axis_size: int, t,
                  tag: str, wire=jnp.float32):
    """Unrolled W-1 step ring prefix-accumulation of one state slice.

    At step s, rank t receives the packet that originated at rank
    ``t-1-s``; every forwarding rank has already folded its own chunk
    decay in, so the arriving packet equals
    ``exp(cum[t-1] - cum[src]) * M_src`` — accumulate iff ``src >= 0``.
    The loop is unrolled (W is a static mesh degree), which (a) lets the
    HLO budget checker count the 2(W-1) fwd+bwd permutes literally and
    (b) exposes every hop to XLA's latency-hiding scheduler.

    ``wire``: each hop's payload dtype; accumulation stays fp32. Note a
    bf16 wire re-rounds the packet at every hop (W-1 compounding casts) —
    looser than the single cast of the allgather strategy.
    """
    m_prev = jnp.zeros_like(m_slice)
    packet = m_slice
    for s in range(axis_size - 1):
        packet = upcast_gathered(
            ring_sendrecv(packet.astype(wire), axis, axis_size=axis_size,
                          tag=tag), jnp.float32)
        m_prev = jnp.where(t - 1 - s >= 0, m_prev + packet, m_prev)
        packet = packet * chunk_decay
    return m_prev


def pipelined_prefix_exchange(m_loc, log_decay, axis: str, *, axis_size: int,
                              t, n_slices: Optional[int] = None,
                              comm_dtype: Optional[str] = None,
                              tag: str = "pipelined"):
    """ZeCO-style pipelined ring prefix-scan of the chunk states.

    ``m_loc``: (..., dk, dv) fp32 local chunk state; ``log_decay``: (...,)
    fp32 total chunk log-decay; returns the decayed prefix state
    ``M_{1:t-1}`` (what :func:`prefix_state_combine` computes from a full
    gather). The prefix combine is elementwise-linear in the state, so the
    state splits along ``dv`` into ``n_slices`` *independent* ring chains:
    slice i+1's permute is dataflow-independent of slice i's accumulate,
    letting the scheduler pipeline communication of one slice behind
    computation on another (ZeCO's all-scan idea at chunk granularity —
    same total volume as the plain ring, W-1 → interleaved latency).

    With ``n_slices=1`` this *is* the LASP-1 ring exchange.
    """
    dv = m_loc.shape[-1]
    if n_slices is None:
        n_slices = auto_slices(dv)
    wire = wire_dtype(comm_dtype)
    chunk_decay = jnp.exp(log_decay)[..., None, None]
    if n_slices == 1:
        return _prefix_chain(m_loc, chunk_decay, axis, axis_size, t, tag,
                             wire=wire)
    slices = jnp.split(m_loc, n_slices, axis=-1)
    outs = [_prefix_chain(s_, chunk_decay, axis, axis_size, t,
                          f"{tag}[{i}]", wire=wire)
            for i, s_ in enumerate(slices)]
    return jnp.concatenate(outs, axis=-1)
