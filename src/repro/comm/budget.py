"""HLO-verified collective budgets.

The paper's claims are *counts*: LASP-2 does exactly one forward
AllGather of sequence-length-independent state; LASP-1's ring does
2(W-1) sequential permutes per fwd+bwd. A :class:`CollectiveBudget` is
that claim written down; :func:`assert_budget` proves it against the
compiled (post-SPMD) HLO via ``repro.launch.hlo_analysis`` — not against
what the Python source *intended* to emit. Tests in
``tests/comm_checks.py`` pin every strategy to its budget.

Caveat inherited from ``parse_collectives``: ops inside ``while`` bodies
(scans/fori_loops) appear once in HLO. The ring strategies are therefore
UNROLLED (static mesh degree) so their W-1 hops are literally countable.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional

from repro.launch.hlo_analysis import (_COLL_OPS, collective_axis_counts,
                                       collective_counts,
                                       parse_collectives)
from repro.launch.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS


@dataclass(frozen=True)
class CollectiveBudget:
    """Exact expected instruction counts; unlisted collective ops must be
    absent (strict=True) or are ignored (strict=False)."""

    counts: Mapping[str, int]
    strict: bool = True
    # optional per-op ceiling on summed per-device traffic bytes
    max_traffic: Mapping[str, float] = field(default_factory=dict)
    note: str = ""


def comm_itemsize(comm_dtype: Optional[str] = None) -> int:
    """Bytes per element on the wire for a ``comm_dtype`` knob value
    (derived from the single registry in ``repro.comm.primitives``)."""
    import numpy as np

    from repro.comm.primitives import wire_dtype
    return np.dtype(wire_dtype(comm_dtype)).itemsize


def packed_state_bytes(b: int, h: int, dk: int, dv: int,
                       comm_dtype: Optional[str] = None) -> int:
    """Per-device payload of the packed ``(M_t ‖ A_t)`` state exchange —
    ``B·H·(dk·dv + 1)`` scalars in the wire dtype. What the comm_dtype
    knob halves (bf16) while the collective *count* stays fixed."""
    return b * h * (dk * dv + 1) * comm_itemsize(comm_dtype)


def allgather_state_budget(world: int, *, with_grad: bool = False,
                           backward: str = "faithful", n_slices: int = 1,
                           state_bytes: Optional[int] = None
                           ) -> CollectiveBudget:
    """Registry ``budget_fn`` for the "allgather" (and "ulysses", whose
    linear-layer exchange IS allgather) inter-chunk state exchange:
    exactly 1 forward all-gather of the packed ``(M_t ‖ A_t)`` states;
    ``with_grad`` adds the backward's dM gather (faithful, Alg. 4) or
    its AD transpose reduce-scatter (autodiff)."""
    del n_slices  # allgather has no slicing knob

    def traffic(n_gathers, n_rs=0):
        if state_bytes is None:
            return {}
        out = {}
        if n_gathers:
            out["all-gather"] = n_gathers * (world - 1) * state_bytes
        if n_rs:
            # RS input is the gathered size: (g-1) × result bytes
            out["reduce-scatter"] = n_rs * (world - 1) * state_bytes
        return out

    if not with_grad:
        return CollectiveBudget({"all-gather": 1},
                                max_traffic=traffic(1))
    if backward == "faithful":
        return CollectiveBudget({"all-gather": 2},
                                max_traffic=traffic(2),
                                note="paper Alg. 2+4: fwd + dM gathers")
    return CollectiveBudget({"all-gather": 1, "reduce-scatter": 1},
                            max_traffic=traffic(1, 1),
                            note="autodiff: RS is the gather transpose")


def ring_state_budget(world: int, *, with_grad: bool = False,
                      backward: str = "autodiff", n_slices: int = 1,
                      state_bytes: Optional[int] = None
                      ) -> CollectiveBudget:
    """Registry ``budget_fn`` for the "ring"/"pipelined" exchanges:
    n_slices·(W-1) collective-permutes per pass, transposing 1:1 under
    autodiff. ``state_bytes`` ceilings describe the packed (M‖A) gather
    payload; the ring paths ship the unpacked M_t per hop, so only the
    count is pinned here."""
    del backward, state_bytes
    per_pass = n_slices * (world - 1)
    n = 2 * per_pass if with_grad else per_pass
    return CollectiveBudget({"collective-permute": n})


def lasp2_budget(strategy: str, world: int, *, with_grad: bool = False,
                 backward: str = "faithful", n_slices: int = 1,
                 state_bytes: Optional[int] = None) -> CollectiveBudget:
    """What one LASP-2 layer is allowed to put on the wire.

    forward only:
      allgather/ulysses → exactly 1 all-gather (the packed M‖A states)
      ring              → W-1 collective-permutes
      pipelined         → n_slices·(W-1) permutes (1/n_slices size)
    with_grad adds the strategy's backward:
      allgather faithful → +1 all-gather (Alg. 4's dM gather)
      allgather autodiff → +1 reduce-scatter (AD transpose of the gather)
      ring/pipelined     → the permutes transpose 1:1 (total doubles)

    ``state_bytes`` (see :func:`packed_state_bytes`): per-device payload
    of one exchange in the *wire* dtype — when given, the budget also
    pins per-op traffic ceilings under the ring cost model, so a
    comm_dtype=bf16 run is asserted to actually halve the bytes (an
    fp32-sized gather then exceeds the ceiling and fails).

    Dispatch is through the strategy registry (the per-strategy
    ``budget_fn`` passed to ``register_strategy``), so a strategy added
    through the public API gets budget coverage without touching this
    module.
    """
    from repro.comm.strategy import get_budget_fn
    return get_budget_fn(strategy)(world, with_grad=with_grad,
                                   backward=backward, n_slices=n_slices,
                                   state_bytes=state_bytes)


def hybrid_context_budget(strategy: str, degree: int, *, sp: int = 1,
                          b: int, hq: int, hkv: int, c: int, dh: int,
                          with_grad: bool = False,
                          comm_dtype: Optional[str] = None,
                          compute_itemsize: int = 4) -> CollectiveBudget:
    """What ONE LASP-2H softmax context-attention call may put on the
    wire, per strategy (registry ``context_budget_fn``).

    ``degree`` is the strategy's context-exchange axis size: the full
    sequence-sharding width for the K/V AllGather path, the ulysses
    (head-parallel) axis size for the All-to-All path. ``sp`` is the
    residual sequence axis ulysses still gathers K/V over on a 3D mesh
    (1 on 1D/2D meshes). ``c`` is the per-device chunk length, ``b``
    batch, ``hq``/``hkv`` query/KV head counts, ``dh`` head dim.
    """
    from repro.comm.strategy import get_context_budget_fn
    return get_context_budget_fn(strategy)(
        degree, sp=sp, b=b, hq=hq, hkv=hkv, c=c, dh=dh,
        with_grad=with_grad, comm_dtype=comm_dtype,
        compute_itemsize=compute_itemsize)


def allgather_context_budget(degree: int, *, sp: int = 1, b: int, hq: int,
                             hkv: int, c: int, dh: int,
                             with_grad: bool = False,
                             comm_dtype: Optional[str] = None,
                             compute_itemsize: int = 4
                             ) -> CollectiveBudget:
    """Registry ``context_budget_fn`` for the K/V AllGather context path
    (LASP-2H default; ring/pipelined layers use the same context path):
    exactly 2 all-gathers (K and V) over the full ``degree``-wide
    sequence sharding; autodiff transposes each into a reduce-scatter.
    Per-link volume is constant in ``degree``: (degree-1)·|K/V local|."""
    del sp, hq, compute_itemsize
    kv = b * hkv * c * dh * comm_itemsize(comm_dtype)
    counts: Dict[str, int] = {"all-gather": 2}
    ceil: Dict[str, float] = {"all-gather": 2 * (degree - 1) * kv}
    if with_grad:
        counts["reduce-scatter"] = 2
        ceil["reduce-scatter"] = 2 * (degree - 1) * kv
    return CollectiveBudget(counts, max_traffic=ceil,
                            note=f"K/V allgather, degree={degree}")


def ulysses_context_budget(degree: int, *, sp: int = 1, b: int, hq: int,
                           hkv: int, c: int, dh: int,
                           with_grad: bool = False,
                           comm_dtype: Optional[str] = None,
                           compute_itemsize: int = 4) -> CollectiveBudget:
    """Registry ``context_budget_fn`` for the ulysses head-parallel
    path: exactly 2 All-to-Alls per forward (packed q‖k‖v seq→head in,
    attention output head→seq out), mirrored 1:1 by the custom_vjp
    backward. Per-link volume shrinks ∝ (degree-1)/degree² relative to
    the payload — the Ulysses selling point vs the gather's constant
    per-link volume. On a 3D mesh (``sp > 1``) K/V additionally gather
    over the residual sequence axis: head count divides by ``degree``
    but token count multiplies by it, so that gather ships the same
    bytes as a 2D K/V gather of width ``sp``."""
    g = degree
    wi = comm_itemsize(comm_dtype)
    a2a_in = b * (hq + 2 * hkv) * c * dh * wi    # packed q‖k‖v blocks
    a2a_out = b * hq * c * dh * compute_itemsize  # attention output
    per_fwd = (g - 1) * a2a_in // g + (g - 1) * a2a_out // g
    counts: Dict[str, int] = {"all-to-all": 4 if with_grad else 2}
    ceil: Dict[str, float] = {
        "all-to-all": per_fwd * (2 if with_grad else 1)}
    if sp > 1:
        # after the a2a: hkv/g heads × c·g tokens per device = hkv·c
        kv = b * hkv * c * dh * wi
        counts["all-gather"] = 2
        ceil["all-gather"] = 2 * (sp - 1) * kv
        if with_grad:
            counts["reduce-scatter"] = 2
            ceil["reduce-scatter"] = 2 * (sp - 1) * kv
    return CollectiveBudget(counts, max_traffic=ceil,
                            note=f"ulysses a2a, degree={g} sp={sp}")


def ring_baseline_budget(world: int, *,
                         with_grad: bool = False) -> CollectiveBudget:
    """LASP-1 baseline (paper Alg. 5/6): W-1 permutes per pass — the
    2(W-1) sequential steps per iteration LASP-2 removes."""
    n = (world - 1) * (2 if with_grad else 1)
    return CollectiveBudget({"collective-permute": n})


def check_budget(hlo_text: str, budget: CollectiveBudget,
                 total_devices: int, records=None) -> List[str]:
    """Return human-readable violations (empty list = within budget).

    Counts always come from the compiled HLO. Traffic ceilings
    (``budget.max_traffic``) come from the HLO too unless ``records`` (a
    list of trace-time :class:`repro.comm.CommRecord`) is given — the
    wire-dtype-true view. Pass the tape when asserting ``comm_dtype``
    byte budgets on CPU: XLA-CPU's float-normalization pass upcasts bf16
    collectives to f32 in compiled HLO (bf16 is storage-only there), so
    only the tape shows the halving this backend cannot express; on TPU
    bf16 collectives are native and the two views agree.

    The tape only records collectives issued through the named
    primitives — AD-emitted ones (e.g. the reduce-scatter transpose of
    the forward gather) never reach it. A ceiling op the HLO count
    expects but the tape lacks is therefore reported as a violation
    rather than passing vacuously against 0 tape bytes.
    """
    counts = collective_counts(hlo_text, total_devices)
    violations = []
    for op, expected in budget.counts.items():
        got = counts.get(op, 0)
        if got != expected:
            violations.append(f"{op}: expected exactly {expected}, "
                              f"compiled HLO has {got}")
    if budget.strict:
        for op in _COLL_OPS:
            if op not in budget.counts and counts.get(op, 0):
                violations.append(f"{op}: expected none, compiled HLO has "
                                  f"{counts[op]}")
    if budget.max_traffic:
        by_op: Dict[str, float] = {}
        if records is not None:
            for r in records:
                by_op[r.op] = by_op.get(r.op, 0.0) + r.traffic_bytes
        else:
            for c in parse_collectives(hlo_text, total_devices):
                by_op[c.op] = by_op.get(c.op, 0.0) + c.traffic_bytes
        src = "tape" if records is not None else "compiled HLO"
        for op, ceiling in budget.max_traffic.items():
            if records is not None and op not in by_op \
                    and budget.counts.get(op, 0):
                violations.append(
                    f"{op}: expected on the wire but absent from the "
                    f"CommRecord tape (AD-emitted?) — byte ceiling "
                    f"unverifiable from records")
            elif by_op.get(op, 0.0) > ceiling:
                violations.append(
                    f"{op}: {src} traffic {by_op.get(op, 0.0):.0f}B "
                    f"exceeds budget {ceiling:.0f}B")
    return violations


def assert_budget(hlo_text: str, budget: CollectiveBudget,
                  total_devices: int, records=None) -> None:
    violations = check_budget(hlo_text, budget, total_devices, records)
    if violations:
        note = f" ({budget.note})" if budget.note else ""
        raise AssertionError(
            "collective budget violated" + note + ":\n  "
            + "\n  ".join(violations))


# ---------------------------------------------------------------------------
# Per-axis budgets (2D DP×SP training, docs/parallelism.md).
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AxisBudget:
    """Exact expected counts per (collective op, spanned mesh axes).

    Keys are ``(op, axes)`` with ``axes`` the mesh-ordered tuple of axis
    names the collective's replica groups span
    (``hlo_analysis.group_axes``). ``strict=True``: any collective with a
    key not listed is a violation."""

    counts: Mapping[tuple, int]
    strict: bool = True
    note: str = ""


def train_step_axis_budget(mesh, *, n_sp_layers: int,
                           n_hybrid_layers: int = 0,
                           comm_strategy: str = "allgather",
                           microbatches: int = 1,
                           backward: str = "autodiff",
                           zero1: bool = True,
                           remat: str = "none") -> AxisBudget:
    """What one compiled (scan-unrolled) DP×SP(×TP) train step may put
    on the wire — the LASP-2(H) composition claim written down:

    * per LASP-2 layer × microbatch, over the sequence sharding ONLY
      (``(sequence,)`` on 2D, ``(sequence, model)`` on 3D — tokens shard
      over both): 1 forward all-gather of the packed ``(M_t, A_t)``
      states, plus the backward's 1 reduce-scatter (autodiff transpose)
      or 1 all-gather of ``dM_t`` (the paper-faithful Alg. 4).
    * per hybrid (softmax) layer × microbatch: the context exchange.
      ulysses → exactly 2 All-to-Alls over ``(model,)`` per forward (or
      over ``(sequence,)`` when there is no model axis), +2 mirrored in
      the backward, plus — 3D only, sp>1 — 2 K/V all-gathers over
      ``(sequence,)`` and their 2 backward reduce-scatters. allgather →
      2 K/V all-gathers over the full sequence sharding + 2 backward
      reduce-scatters.
    * exactly 1 gradient reduction spanning every nontrivial axis per
      step: the packed flat-gradient all-reduce (params are replicated;
      token/batch shards all contribute partial gradients).
    * ZeRO-1 only: 1 all-gather over the optimizer-shard axes — ``data``
      on 2D, ``(data, model)`` on 3D (the parameter re-assembly after
      the sharded update).

    ``remat="full"`` replays every layer's forward in the backward, so
    each forward exchange above runs twice per step (still one per layer
    forward).
    """
    nontrivial = tuple(n for n in mesh.axis_names if mesh.shape[n] > 1)
    dp = mesh.shape.get(DATA_AXIS, 1)
    sp = mesh.shape.get(SEQ_AXIS, 1)
    tp = mesh.shape.get(MODEL_AXIS, 1)
    # tokens shard over both sequence-like axes; mesh order (SEQ, MODEL)
    seq_axes = tuple(a for a in (SEQ_AXIS, MODEL_AXIS)
                     if mesh.shape.get(a, 1) > 1)
    counts: Dict[tuple, int] = {}

    def add(op, axes, n):
        if n and axes:
            counts[(op, axes)] = counts.get((op, axes), 0) + n

    fwd = 2 if remat == "full" else 1     # forward passes per step
    if seq_axes and n_sp_layers:
        per_pass = n_sp_layers * microbatches
        add("all-gather", seq_axes, fwd * per_pass)
        if backward == "faithful":
            add("all-gather", seq_axes, per_pass)
        else:
            add("reduce-scatter", seq_axes, per_pass)
    if seq_axes and n_hybrid_layers:
        per_pass = n_hybrid_layers * microbatches
        if comm_strategy == "ulysses":
            a2a_axes = (MODEL_AXIS,) if tp > 1 else (SEQ_AXIS,)
            add("all-to-all", a2a_axes, (2 * fwd + 2) * per_pass)
            if tp > 1 and sp > 1:
                add("all-gather", (SEQ_AXIS,), 2 * fwd * per_pass)
                add("reduce-scatter", (SEQ_AXIS,), 2 * per_pass)
        else:
            add("all-gather", seq_axes, 2 * fwd * per_pass)
            add("reduce-scatter", seq_axes, 2 * per_pass)
    counts[("all-reduce", nontrivial)] = 1
    zero_axes = tuple(a for a in (DATA_AXIS, MODEL_AXIS)
                      if mesh.shape.get(a, 1) > 1)
    if zero1 and zero_axes:
        add("all-gather", zero_axes, 1)
    return AxisBudget(counts, note=f"dp={dp} sp={sp} tp={tp} "
                                   f"layers={n_sp_layers}"
                                   f"+{n_hybrid_layers}h A={microbatches} "
                                   f"remat={remat}")


def check_axis_budget(hlo_text: str, mesh,
                      budget: AxisBudget) -> List[str]:
    """Human-readable violations of an :class:`AxisBudget` (empty list =
    within budget)."""
    got = collective_axis_counts(hlo_text, mesh)
    violations = []
    for key, expected in budget.counts.items():
        if got.get(key, 0) != expected:
            violations.append(
                f"{key[0]} over {key[1] or ('<none>',)}: expected exactly "
                f"{expected}, compiled HLO has {got.get(key, 0)}")
    if budget.strict:
        for key, n in got.items():
            if key not in budget.counts and n:
                violations.append(
                    f"{key[0]} over {key[1] or ('<none>',)}: expected "
                    f"none, compiled HLO has {n}")
    return violations


def assert_axis_budget(hlo_text: str, mesh, budget: AxisBudget) -> None:
    violations = check_axis_budget(hlo_text, mesh, budget)
    if violations:
        note = f" ({budget.note})" if budget.note else ""
        raise AssertionError(
            "per-axis collective budget violated" + note + ":\n  "
            + "\n  ".join(violations))


def compiled_hlo(fn, *args, static_argnums=()) -> str:
    """Compiled (post-SPMD) HLO text of ``jit(fn)(*args)``."""
    import jax
    return jax.jit(fn, static_argnums=static_argnums).lower(
        *args).compile().as_text()


def gather_result_bytes(hlo_text: str, total_devices: int,
                        op: str = "all-gather") -> Optional[int]:
    """Result size of the largest ``op`` in the module — used to pin the
    state gather to its expected W·(dk·dv+1)-scalar volume."""
    sizes = [c.result_bytes for c in parse_collectives(hlo_text,
                                                       total_devices)
             if c.op == op]
    return max(sizes) if sizes else None
