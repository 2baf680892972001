"""Post-compile HLO analysis: collective traffic + roofline terms.

``cost_analysis()`` supplies per-device HLO FLOPs/bytes, but counts each
``while`` body (scan) ONCE — verified empirically. The roofline therefore
extrapolates from reduced-depth *unrolled* lowers (see
``repro.launch.roofline``); this module handles the per-compile parsing.

Collective bytes are not in ``cost_analysis`` at all: we parse the
compiled (post-SPMD) HLO text and apply the standard ring-cost model per
op (paper §3.4's communication model, generalized):

  all-gather        (g-1)/g × result_bytes
  reduce-scatter    (g-1)   × result_bytes          (input = g × result)
  all-reduce        2(g-1)/g × bytes
  all-to-all        (g-1)/g × bytes
  collective-permute  result_bytes

Hardware peaks live in one table keyed by ``jax.Device.device_kind``
(:data:`DEVICE_PEAKS`). The dry-run roofline projects onto TPU v5e, the
chip the repo is built for; MFU is reported only for a device in the
table (``None`` elsewhere, the CPU included).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

# Published per-chip peaks, keyed by the ``device_kind`` JAX reports.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s ICI per chip (4 links -> 50 GB/s per link).
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
ROOFLINE_KIND = "TPU v5 lite"   # the chip the dry-run roofline projects onto
PEAK_FLOPS = DEVICE_PEAKS[ROOFLINE_KIND]["flops"]      # bf16 per chip
HBM_BW = DEVICE_PEAKS[ROOFLINE_KIND]["hbm_bw"]         # bytes/s per chip
ICI_BW = DEVICE_PEAKS[ROOFLINE_KIND]["ici_bw"]         # bytes/s per link


def device_peak_flops(device=None) -> Optional[float]:
    """bf16 peak FLOP/s of ``device`` (default: the first local device),
    or ``None`` when its ``device_kind`` is not in :data:`DEVICE_PEAKS` —
    MFU is then not defined, never measured against another chip's peak."""
    if device is None:
        import jax
        device = jax.devices()[0]
    peaks = DEVICE_PEAKS.get(device.device_kind)
    return peaks["flops"] if peaks else None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _type_bytes(type_str: str) -> int:
    """Sum bytes over every `dtype[shape]` group in an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, total_devices: int) -> int:
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    return total_devices


def parse_replica_groups(line: str):
    """Device-id groups of one collective instruction, or ``None`` when
    the instruction carries no ``replica_groups`` attribute (= one group
    of all devices).

    Handles both HLO spellings: the explicit list
    ``replica_groups={{0,1},{2,3}}`` and the iota form
    ``replica_groups=[2,2]<=[4]`` / ``[2,2]<=[2,2]T(1,0)`` (ids =
    ``arange(prod(dims)).reshape(dims).transpose(perm).reshape(n, g)``).
    """
    m = re.search(r"replica_groups=\{((?:\{[0-9, ]*\},?)*)\}", line)
    if m:
        groups = [[int(x) for x in g.split(",") if x.strip()]
                  for g in re.findall(r"\{([0-9, ]*)\}", m.group(1))]
        # ``replica_groups={}`` is XLA's spelling for ONE group of all
        # devices — same meaning as the attribute being absent.
        return groups or None
    # collective-permute carries source_target_pairs instead; each (src,
    # tgt) pair is a 2-device "group" for axis-span purposes.
    m = re.search(r"source_target_pairs=\{((?:\{[0-9, ]*\},?)*)\}", line)
    if m:
        pairs = [[int(x) for x in g.split(",") if x.strip()]
                 for g in re.findall(r"\{([0-9, ]*)\}", m.group(1))]
        return pairs or None
    m = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?",
        line)
    if m:
        import numpy as np
        n, g = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(x) for x in m.group(4).split(",")])
        return ids.reshape(n, g).tolist()
    return None


def group_axes(groups, mesh) -> tuple:
    """Which mesh axes a collective's device groups span.

    Returns the (mesh-ordered) tuple of axis names whose coordinate
    varies within at least one group — e.g. on a ``(data, sequence)``
    mesh, groups ``{{0,1,2,3},{4,5,6,7}}`` span ``("sequence",)`` and
    ``{{0,4},...}`` span ``("data",)``. ``groups=None`` (no
    ``replica_groups`` attribute) spans every non-trivial axis.
    """
    import numpy as np
    names = tuple(mesh.axis_names)
    devs = np.asarray(mesh.devices)
    if groups is None:
        return tuple(n for n, s in zip(names, devs.shape) if s > 1)
    coord = {}
    for idx in np.ndindex(devs.shape):
        coord[int(devs[idx].id)] = idx
    varying = set()
    for g in groups:
        unknown = [d for d in g if d not in coord]
        if unknown:
            # Fail loudly: silently dropping ids would misclassify the
            # axes a collective spans and corrupt every budget built on
            # this (e.g. a mesh over a device subset, or ids that are not
            # the flat 0..N-1 ordering of this mesh).
            raise ValueError(
                f"replica group {g} names device ids {unknown} not in "
                f"the mesh (known: {sorted(coord)})")
        cs = [coord[d] for d in g]
        for ax in range(len(names)):
            if len({c[ax] for c in cs}) > 1:
                varying.add(names[ax])
    return tuple(n for n in names if n in varying)


def collective_axis_counts(hlo_text: str, mesh):
    """Instruction counts per (collective op, spanned mesh axes).

    The per-axis view of :func:`collective_counts`: keys are
    ``(op, axes)`` with ``axes`` the mesh-ordered tuple from
    :func:`group_axes`. This is what proves the 2D DP×SP budget — e.g.
    "every LASP-2 all-gather spans ONLY the sequence axis, exactly one
    reduction spans data" (``repro.comm.budget.check_axis_budget``).
    """
    import numpy as np
    total = int(np.asarray(mesh.devices).size)
    counts = {}
    for c in parse_collectives(hlo_text, total):
        key = (c.op, group_axes(c.groups, mesh))
        counts[key] = counts.get(key, 0) + c.count
    return counts


@dataclass
class Collective:
    op: str
    result_bytes: int
    group_size: int
    count: int = 1
    groups: Optional[List[List[int]]] = None   # device-id replica groups

    @property
    def traffic_bytes(self) -> float:
        g = max(self.group_size, 2)
        b = self.result_bytes
        if self.op == "all-gather":
            t = (g - 1) / g * b
        elif self.op == "all-reduce":
            t = 2 * (g - 1) / g * b
        elif self.op == "reduce-scatter":
            t = (g - 1) * b
        elif self.op == "all-to-all":
            t = (g - 1) / g * b
        else:  # collective-permute
            t = b
        return t * self.count


_ASYNC_PHASES = ("fused_computation", "async_collective_fusion")


def parse_collectives(hlo_text: str, total_devices: int) -> List[Collective]:
    """All collective ops in the compiled module ('-start' variants counted,
    '-done' skipped). NOTE: ops inside while bodies appear once — callers
    using scans must extrapolate (repro.launch.roofline).

    TPU spellings: an asynchronous collective fusion is split into start,
    update and done fusion computations that each hold a copy of the
    instruction under one ``channel_id`` (counted once), and a
    reduce-scatter is an all-reduce inside an ``all-reduce-scatter``
    fusion computation (counted as the reduce-scatter, with its scattered
    result size)."""
    out = []
    seen_channels = set()
    computation = ""
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)", line)
            computation = m.group(1) if m else ""
            continue
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(",
                     stripped)
        if not m:
            continue
        type_str, op = m.groups()
        base = op.replace("-start", "")
        if base not in _COLL_OPS or op.endswith("-done"):
            continue
        ch = re.search(r"channel_id=(\d+)", stripped)
        if ch and computation.startswith(_ASYNC_PHASES):
            if ch.group(1) in seen_channels:
                continue
            seen_channels.add(ch.group(1))
        rb = _type_bytes(type_str)
        if base == "all-gather" and op.endswith("-start"):
            rb //= 2   # start ops carry (operand, result) tuple types
        group_size = _group_size(stripped, total_devices)
        if base == "all-reduce" and computation.startswith(
                "all-reduce-scatter"):
            base, rb = "reduce-scatter", rb // max(group_size, 1)
        out.append(Collective(base, rb, group_size,
                              groups=parse_replica_groups(stripped)))
    return out


def collective_counts(hlo_text: str, total_devices: int) -> Dict[str, int]:
    """Instruction counts per collective op in the compiled module (same
    while-body caveat as :func:`parse_collectives`). The comm-budget
    checks (``repro.comm.budget``) are built on this."""
    counts: Dict[str, int] = {}
    for c in parse_collectives(hlo_text, total_devices):
        counts[c.op] = counts.get(c.op, 0) + c.count
    return counts


# op_name ends ".../<name>/pallas_call", the name possibly wrapped in
# transforms: "transpose(jvp(flash_attention_bwd_dq))".
_KERNEL_RE = re.compile(r'op_name="[^"]*?([\w-]+)\)*/pallas_call"')


def tpu_kernels(hlo_text: str) -> Dict[str, int]:
    """``{kernel name: instruction count}`` of the Pallas kernels in a
    compiled TPU module: each is a ``tpu_custom_call`` instruction whose
    metadata names its ``pallas_call(name=...)``. A program that fell back
    to XLA or to interpret mode has none."""
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _KERNEL_RE.search(line)
        name = m.group(1) if m else "<unnamed>"
        counts[name] = counts.get(name, 0) + 1
    return counts


def collective_summary(colls: List[Collective]) -> Dict[str, float]:
    summary: Dict[str, float] = {}
    for c in colls:
        summary[c.op] = summary.get(c.op, 0.0) + c.traffic_bytes
        summary[f"{c.op}_count"] = summary.get(f"{c.op}_count", 0) + c.count
    summary["total_bytes"] = sum(c.traffic_bytes for c in colls)
    return summary


@dataclass
class CostVector:
    """Per-device cost of one compiled program (additive, scalable)."""

    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_by_op: Dict[str, float] = field(default_factory=dict)

    def __add__(self, o):
        d = dict(self.coll_by_op)
        for k, v in o.coll_by_op.items():
            d[k] = d.get(k, 0.0) + v
        return CostVector(self.flops + o.flops,
                          self.hbm_bytes + o.hbm_bytes,
                          self.coll_bytes + o.coll_bytes, d)

    def __sub__(self, o):
        d = {k: v - o.coll_by_op.get(k, 0.0)
             for k, v in self.coll_by_op.items()}
        return CostVector(self.flops - o.flops,
                          self.hbm_bytes - o.hbm_bytes,
                          self.coll_bytes - o.coll_bytes, d)

    def scale(self, f):
        return CostVector(self.flops * f, self.hbm_bytes * f,
                          self.coll_bytes * f,
                          {k: v * f for k, v in self.coll_by_op.items()})


def measure(compiled, total_devices: int) -> CostVector:
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text(), total_devices)
    summ = collective_summary(colls)
    by_op = {c: summ.get(c, 0.0) for c in _COLL_OPS if c in summ}
    return CostVector(
        flops=float(ca.get("flops", 0.0)),
        hbm_bytes=float(ca.get("bytes accessed", 0.0)),
        coll_bytes=float(summ.get("total_bytes", 0.0)),
        coll_by_op=by_op)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N_active·D (train), 2·N_active·D (prefill),
    2·N_active·B (decode, D = one token per row).

    Lives here (not in ``repro.launch.roofline``, which re-exports it)
    so runtime telemetry (``repro.obs``) can compute achieved-MFU
    without importing the roofline module, whose import sets the
    512-virtual-device ``XLA_FLAGS`` for its own subprocesses.
    """
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch


def roofline_terms(cost: CostVector) -> Dict[str, float]:
    """The three per-step time lower bounds, in seconds (per chip; FLOPs
    and bytes here are already per-device post-SPMD)."""
    t_compute = cost.flops / PEAK_FLOPS
    t_memory = cost.hbm_bytes / HBM_BW
    t_coll = cost.coll_bytes / ICI_BW
    dominant = max((t_compute, "compute"), (t_memory, "memory"),
                   (t_coll, "collective"))[1]
    return {"compute_s": t_compute, "memory_s": t_memory,
            "collective_s": t_coll, "dominant": dominant}


def memory_report(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "generated_code_bytes": ma.generated_code_size_in_bytes,
        "peak_bytes": (ma.argument_size_in_bytes
                       + ma.output_size_in_bytes
                       + ma.temp_size_in_bytes
                       - ma.alias_size_in_bytes),
    }


# ---------------------------------------------------------------------------
# Lowered (pre-optimization) StableHLO parsing — the wire-dtype view.
#
# XLA:CPU's float normalization UPCASTS bf16 collectives to f32 in the
# *compiled* HLO (bf16 is storage-only there), so a comm_dtype=bf16
# assertion must read the LOWERED StableHLO, where the element types the
# program put on the wire are still visible. Used by the compiled-program
# sanitizer (repro.analysis.sanitizer, SAN203/SAN205).
# ---------------------------------------------------------------------------

_STABLEHLO_OPS = ("all_gather", "all_reduce", "reduce_scatter",
                  "all_to_all", "collective_permute", "collective_broadcast")
_STABLEHLO_OP_RE = re.compile(
    r'"stablehlo\.(' + "|".join(_STABLEHLO_OPS) + r')"')
_STABLEHLO_GROUPS_RE = re.compile(
    r"replica_groups\s*=\s*dense<(\[\[.*?\]\]|\[?[0-9 ,]*\]?)>", re.S)
_STABLEHLO_FNTYPE_RE = re.compile(
    r":\s*\((tensor<[^)]*?)\)\s*->", re.S)
_TENSOR_RE = re.compile(r"tensor<([0-9x]*)([a-z][a-z0-9]*)>")


@dataclass(frozen=True)
class StableHloCollective:
    """One collective in lowered StableHLO text, with its wire-visible
    element type (the thing compiled CPU HLO loses for bf16)."""

    op: str                     # hlo-style name, e.g. "all-gather"
    dtype: str                  # element type of the first operand
    shape: tuple                # dims of the first operand
    groups: Optional[tuple]     # replica groups (device ids), or None


def parse_stablehlo_collectives(text: str) -> List[StableHloCollective]:
    """Every collective op in a ``lowered.as_text()`` module, in program
    order. Region-holding ops (all_reduce/reduce_scatter) print their
    function type after the region body, so the scan is text-positional,
    not line-based."""
    import json
    out = []
    for m in _STABLEHLO_OP_RE.finditer(text):
        tail = text[m.end():]
        gm = _STABLEHLO_GROUPS_RE.search(tail[:2000])
        groups = None
        if gm:
            raw = gm.group(1)
            if not raw.startswith("[["):
                raw = f"[[{raw.strip('[]')}]]"
            groups = tuple(tuple(g) for g in json.loads(raw))
        fm = _STABLEHLO_FNTYPE_RE.search(tail)
        dtype, shape = "?", ()
        if fm:
            tm = _TENSOR_RE.search(fm.group(1))
            if tm:
                shape = tuple(int(d) for d in tm.group(1).split("x") if d)
                dtype = tm.group(2)
        out.append(StableHloCollective(
            op=m.group(1).replace("_", "-"), dtype=dtype, shape=shape,
            groups=groups))
    return out


def collective_fingerprint(text: str) -> List[tuple]:
    """Order-preserving (op, dtype, shape, groups) sequence of a lowered
    module — the determinism invariant: two independent lowerings of the
    same step must produce the identical fingerprint (SAN205)."""
    return [(c.op, c.dtype, c.shape, c.groups)
            for c in parse_stablehlo_collectives(text)]


def alias_entries(compiled_text: str) -> int:
    """Number of entries in the compiled module's input/output alias
    table (``input_output_alias={ {0}: (0, {}, may-alias), ... }``).
    0 = donation degraded to a copy (SAN204)."""
    m = re.search(r"input_output_alias=\{", compiled_text)
    if not m:
        return 0
    depth, i = 1, m.end()
    while i < len(compiled_text) and depth:
        depth += {"{": 1, "}": -1}.get(compiled_text[i], 0)
        i += 1
    return compiled_text[m.end():i].count("alias")
