"""Per-run communication flight recorder.

The repo can already *prove* LASP-2's comm claims at trace time — the
``CommRecord`` tape (``repro.comm.primitives``) says what the Python
source put on the wire, and the HLO budget checks
(``repro.comm.budget``) say what the compiled program actually emits.
The flight recorder is the runtime third leg: it snapshots both static
views ONCE at compile, cross-validates them (tape vs compiled HLO —
"expected vs measured" collective structure), and then stamps every
logged step with the run's throughput story:

* tokens/s and achieved FLOP/s → **MFU** (model FLOPs over
  ``n_devices × peak``, reusing ``launch.roofline.model_flops`` — the
  single FLOP model the roofline uses, via its import-side-effect-free
  home in ``launch.hlo_analysis``),
* expected collective bytes per step (from the tape) next to the
  HLO-derived bytes, so a report can show comm volume per token,
* step-wall drift against a rolling expectation (the runtime analogue
  of the watchdog, attributed per phase when phase walls are given).

Drift at compile time (a collective op the tape promised but the HLO
lacks, or tape traffic the HLO cannot carry) is flagged in the
``compile`` record and kept on ``drift_events`` — the distributed test
battery injects a fake tape record and asserts the flag fires.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from repro.launch.hlo_analysis import device_peak_flops
from repro.obs.metrics import Histogram, MetricsSink, as_sink


@dataclass
class CompileSnapshot:
    """Static expectations captured once per compile."""

    # tape view (what the source promised)
    tape_bytes_by_op: Dict[str, float] = field(default_factory=dict)
    tape_counts: Dict[str, int] = field(default_factory=dict)
    expected_bytes_per_step: float = 0.0
    expected_steps_per_step: int = 0
    # HLO view (what the compiled program carries)
    hlo_counts: Dict[str, int] = field(default_factory=dict)
    hlo_bytes_by_op: Dict[str, float] = field(default_factory=dict)
    hlo_bytes_per_step: float = 0.0
    drift: List[str] = field(default_factory=list)

    def as_record(self) -> Dict[str, Any]:
        rec: Dict[str, Any] = {"kind": "compile",
                               "expected_collective_bytes":
                                   self.expected_bytes_per_step,
                               "expected_comm_steps":
                                   self.expected_steps_per_step,
                               "hlo_collective_bytes":
                                   self.hlo_bytes_per_step,
                               "drift": list(self.drift)}
        for op, n in sorted(self.tape_counts.items()):
            rec[f"tape/{op}_count"] = n
        for op, b in sorted(self.tape_bytes_by_op.items()):
            rec[f"tape/{op}_bytes"] = b
        for op, n in sorted(self.hlo_counts.items()):
            rec[f"hlo/{op}_count"] = n
        for op, b in sorted(self.hlo_bytes_by_op.items()):
            rec[f"hlo/{op}_bytes"] = b
        return rec


class FlightRecorder:
    """Runtime telemetry for one compiled program (train step, decode
    step, bench case).

    Parameters
    ----------
    sink: where records go (``None`` → dropped).
    model_flops_per_step: model-level FLOPs one step performs (use
        ``launch.roofline.model_flops`` with the run's shape); enables
        achieved-FLOP/s + MFU fields on step records.
    n_devices: devices the program spans (MFU denominator).
    peak_flops: per-device peak (default: the local device's entry in
        ``launch.hlo_analysis.DEVICE_PEAKS``; a device not in that table
        gets ``mfu: None``).
    wall_factor / wall_window / wall_warmup: rolling-median step-wall
        drift detection; the first ``wall_warmup`` steps (compile /
        resume spikes) are excluded from the window and never flagged.
    """

    def __init__(self, sink: Optional[MetricsSink] = None, *,
                 model_flops_per_step: Optional[float] = None,
                 n_devices: int = 1, peak_flops: Optional[float] = None,
                 wall_factor: float = 3.0, wall_window: int = 50,
                 wall_warmup: int = 1):
        self.sink = as_sink(sink)
        self.model_flops_per_step = model_flops_per_step
        self.n_devices = max(int(n_devices), 1)
        self.peak_flops = peak_flops if peak_flops is not None \
            else device_peak_flops()
        self.wall_factor = wall_factor
        self.wall_window = wall_window
        self.wall_warmup = wall_warmup
        self.snapshot: Optional[CompileSnapshot] = None
        self.drift_events: List[str] = []
        self.wall_hist = Histogram()
        self._walls: List[float] = []
        self._seen = 0

    # -- compile-time snapshot ----------------------------------------------

    def on_compile(self, *, records=None, hlo_text: Optional[str] = None,
                   total_devices: int = 1,
                   hlo_counts: Optional[Dict[str, int]] = None,
                   hlo_bytes_by_op: Optional[Dict[str, float]] = None,
                   note: str = "") -> CompileSnapshot:
        """Snapshot the trace-time tape and the compiled HLO; emit one
        ``compile`` record; return the snapshot (``snapshot.drift``
        lists expected-vs-compiled mismatches).

        ``records``: the ``CommRecord`` list captured by tracing the
        program inside ``repro.comm.tape()``. ``hlo_text``: compiled
        (post-SPMD) HLO; tests may instead pass precomputed
        ``hlo_counts``/``hlo_bytes_by_op``.

        Drift rules (conservative — autodiff legitimately emits
        collectives the tape never sees, e.g. the reduce-scatter
        transpose of a forward gather, so the HLO may exceed the tape):

        * an op the tape promises more instances of than the HLO
          carries is drift (the program lost a collective the source
          intended — or the tape was tampered with);
        * tape traffic for an op the compiled HLO cannot carry at all
          is drift.
        """
        snap = CompileSnapshot()
        records = list(records) if records else []
        for r in records:
            snap.tape_bytes_by_op[r.op] = \
                snap.tape_bytes_by_op.get(r.op, 0.0) + r.traffic_bytes
            snap.tape_counts[r.op] = snap.tape_counts.get(r.op, 0) + 1
            snap.expected_steps_per_step += r.steps
        snap.expected_bytes_per_step = sum(snap.tape_bytes_by_op.values())

        if hlo_text is not None:
            from repro.launch.hlo_analysis import parse_collectives
            for c in parse_collectives(hlo_text, total_devices):
                snap.hlo_counts[c.op] = snap.hlo_counts.get(c.op, 0) + c.count
                snap.hlo_bytes_by_op[c.op] = \
                    snap.hlo_bytes_by_op.get(c.op, 0.0) + c.traffic_bytes
        if hlo_counts is not None:
            snap.hlo_counts = dict(hlo_counts)
        if hlo_bytes_by_op is not None:
            snap.hlo_bytes_by_op = dict(hlo_bytes_by_op)
        snap.hlo_bytes_per_step = sum(snap.hlo_bytes_by_op.values())

        for op, n in sorted(snap.tape_counts.items()):
            got = snap.hlo_counts.get(op, 0)
            if got < n:
                snap.drift.append(
                    f"{op}: tape promises {n} collective(s), compiled "
                    f"HLO has {got}")
            elif snap.tape_bytes_by_op.get(op, 0.0) > 0 \
                    and snap.hlo_bytes_by_op.get(op, 0.0) == 0 \
                    and snap.hlo_bytes_by_op:
                snap.drift.append(
                    f"{op}: tape promises "
                    f"{snap.tape_bytes_by_op[op]:.0f}B but the compiled "
                    f"HLO carries none")

        self.snapshot = snap
        self.drift_events.extend(snap.drift)
        rec = snap.as_record()
        if note:
            rec["note"] = note
        self.sink.emit(rec)
        return snap

    # -- per-step records ----------------------------------------------------

    def expected_wall_s(self) -> Optional[float]:
        """Rolling-median step wall over the post-warmup window."""
        if not self._walls:
            return None
        xs = sorted(self._walls)
        return xs[len(xs) // 2]

    def on_step(self, step: int, wall_s: float, *,
                tokens: Optional[int] = None,
                phases: Optional[Dict[str, float]] = None,
                metrics: Optional[Dict[str, float]] = None,
                straggler: Optional[bool] = None,
                host: Optional[Dict[str, float]] = None) -> Dict[str, Any]:
        """Build + emit one ``step`` record; returns it.

        ``phases``: ``{"<name>_s": wall}`` from a ``PhaseTimer.flush()``.
        ``host``: the step's GC pauses and compiles
        (``HostWatch.delta()``).
        ``straggler``: an external verdict (the train loop's watchdog);
        if ``None``, the recorder's own rolling-median drift rule
        decides."""
        rec: Dict[str, Any] = {"kind": "step", "step": int(step),
                               "wall_s": float(wall_s)}
        if metrics:
            rec.update({k: float(v) for k, v in metrics.items()})
        if phases:
            rec.update({k: float(v) for k, v in phases.items()})
        if host:
            rec.update({k: float(v) for k, v in host.items()})

        expected = self.expected_wall_s()
        self._seen += 1
        warming = self._seen <= self.wall_warmup
        if not warming:
            self._walls.append(float(wall_s))
            self._walls = self._walls[-self.wall_window:]
            self.wall_hist.add(float(wall_s))
        if straggler is None:
            straggler = bool(expected is not None and not warming
                             and wall_s > self.wall_factor * expected)
        rec["straggler"] = bool(straggler)
        if expected is not None:
            rec["expected_wall_s"] = expected

        if tokens:
            rec["tokens"] = int(tokens)
            rec["tokens_per_s"] = tokens / wall_s if wall_s > 0 else 0.0
        if self.model_flops_per_step and wall_s > 0:
            achieved = self.model_flops_per_step / wall_s
            rec["achieved_flops"] = achieved
            rec["mfu"] = achieved / (self.peak_flops * self.n_devices) \
                if self.peak_flops else None
        if self.snapshot is not None:
            rec["expected_collective_bytes"] = \
                self.snapshot.expected_bytes_per_step
            rec["hlo_collective_bytes"] = self.snapshot.hlo_bytes_per_step
            if tokens and self.snapshot.expected_bytes_per_step:
                rec["comm_bytes_per_token"] = \
                    self.snapshot.expected_bytes_per_step / tokens
        self.sink.emit(rec)
        return rec

    def event(self, name: str, **fields) -> Dict[str, Any]:
        """Emit a structured ``event`` record (straggler, resume, signal,
        …) — the telemetry form of what used to be a bare print."""
        rec: Dict[str, Any] = {"kind": "event", "event": name}
        rec.update(fields)
        self.sink.emit(rec)
        return rec

    def summary(self, **extra) -> Dict[str, Any]:
        """Emit the run-level ``summary`` record (wall histogram, drift
        count, plus caller extras) and return it."""
        rec: Dict[str, Any] = {"kind": "summary",
                               "steps_recorded": self._seen,
                               "drift_events": len(self.drift_events)}
        for stat, v in self.wall_hist.summary().items():
            rec[f"wall_s_{stat}"] = v
        if self.snapshot is not None:
            rec["expected_collective_bytes"] = \
                self.snapshot.expected_bytes_per_step
        rec.update(extra)
        self.sink.emit(rec)
        return rec
