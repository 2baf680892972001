"""jaxlint: AST rules for the SPMD hot path.

Each rule has a code, a one-line title, and an ``--explain`` doc
(``python -m repro.analysis --explain JL101``). Rules are plain functions
``rule(ctx) -> list[Finding]`` over a parsed :class:`FileContext`; the
runner (``repro.analysis.lint``) handles discovery, scoping, inline
``# jaxlint: disable=CODE`` comments and the suppression file.

Scoping (who gets which rules) is decided per file by the runner:

* JL101 (axis literals), JL103 (Tracer isinstance), JL105/JL106 (Pallas
  debris / unmasked dynamic loads) run on every discovered file;
* JL102 (host syncs) runs on the traced hot-path modules ``core/``,
  ``kernels/``, ``comm/``, ``train/step.py`` plus ``obs/metrics.py``
  (where the deliberate fencing sites carry ``@host_sync_allowed``);
* JL104 (nondeterminism) runs on ``core/``, ``kernels/``, ``comm/``,
  ``train/step.py`` only — host-side drivers legitimately use clocks.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from repro.analysis.findings import Finding

# The canonical mesh-axis names. Imported — not spelled — so the only
# file in the tree holding the raw strings stays launch/mesh.py (JL101's
# own invariant).
from repro.launch.mesh import DATA_AXIS, MODEL_AXIS, POD_AXIS, SEQ_AXIS

AXIS_NAMES = frozenset({DATA_AXIS, SEQ_AXIS, MODEL_AXIS, POD_AXIS})

# Non-axis meanings the axis words also carry in this tree (JL101 deny
# contexts): the data-dependent decay *kind* of linear-attention configs
# (compared/passed as ``decay=``/``kind=``), and phase-timer labels.
_KIND_NAMES = {"decay", "kind"}
_KIND_CALLS = {"phase", "LinearAttnConfig"}

_HOST_SYNC_DECORATOR = "host_sync_allowed"


# ---------------------------------------------------------------------------
# File context.
# ---------------------------------------------------------------------------

@dataclass
class FileContext:
    """One parsed file plus the per-node bookkeeping rules need."""

    path: str                      # display path (repo-relative)
    text: str
    sync_scope: bool = False       # JL102 applies
    det_scope: bool = False        # JL104 applies
    axis_exempt: bool = False      # JL101 skipped (launch/mesh.py)
    tracer_exempt: bool = False    # JL103 skipped (core/compat.py)
    tree: Optional[ast.AST] = None
    parents: Dict[ast.AST, ast.AST] = field(default_factory=dict)
    lines: List[str] = field(default_factory=list)

    def __post_init__(self):
        self.tree = ast.parse(self.text, filename=self.path)
        self.lines = self.text.splitlines()
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node

    def src(self, node) -> str:
        ln = getattr(node, "lineno", 0)
        return self.lines[ln - 1].strip() if 0 < ln <= len(self.lines) else ""

    def finding(self, code, node, message) -> Finding:
        return Finding(code=code, path=self.path,
                       line=getattr(node, "lineno", 0),
                       col=getattr(node, "col_offset", 0),
                       message=message, source=self.src(node))

    def ancestors(self, node):
        while node in self.parents:
            node = self.parents[node]
            yield node

    def in_host_sync_allowed(self, node) -> bool:
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in anc.decorator_list:
                    if _terminal_name(dec) == _HOST_SYNC_DECORATOR:
                        return True
        return False


def _terminal_name(node) -> Optional[str]:
    """Rightmost identifier of a Name/Attribute/Call chain."""
    if isinstance(node, ast.Call):
        node = node.func
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _base_name(node) -> Optional[str]:
    """Leftmost identifier: ``np.random.normal`` -> ``np``."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


# ---------------------------------------------------------------------------
# JL101 — raw axis-name string literals.
# ---------------------------------------------------------------------------

def _axis_literal_denied(ctx: FileContext, node: ast.Constant) -> bool:
    """True when an axis-word literal is *not* a mesh-axis usage."""
    prev = node
    for anc in ctx.ancestors(node):
        if isinstance(anc, ast.Compare):
            others = [anc.left] + list(anc.comparators)
            for other in others:
                if other is prev:
                    continue
                if _terminal_name(other) in _KIND_NAMES:
                    return True
        if isinstance(anc, ast.keyword) and anc.arg in _KIND_NAMES:
            return True
        if isinstance(anc, ast.Call):
            if _terminal_name(anc.func) in _KIND_CALLS:
                return True
            return False        # any other call: axis context, flag it
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef, ast.Module)):
            return False
        prev = anc
    return False


def check_axis_literals(ctx: FileContext) -> List[Finding]:
    if ctx.axis_exempt:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                and node.value in AXIS_NAMES
                and not _axis_literal_denied(ctx, node)):
            out.append(ctx.finding(
                "JL101", node,
                f'raw axis-name literal "{node.value}" — use the constant '
                f"exported by repro.launch.mesh (DATA_AXIS / SEQ_AXIS / "
                f"MODEL_AXIS / POD_AXIS)"))
    return out


# ---------------------------------------------------------------------------
# JL102 — host syncs in traced hot-path modules.
# ---------------------------------------------------------------------------

_SYNC_NAMES = {"block_until_ready", "device_get"}
_NUMPY_ALIASES = {"np", "numpy", "onp"}


def check_host_syncs(ctx: FileContext) -> List[Finding]:
    if not ctx.sync_scope:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = _terminal_name(f)
        what = None
        if isinstance(f, ast.Name) and name == "print":
            what = "print()"
        elif name in _SYNC_NAMES:
            what = f"{name}()"
        elif (isinstance(f, ast.Attribute) and name == "item"
                and not node.args and not node.keywords):
            what = ".item()"
        elif (isinstance(f, ast.Attribute) and name == "asarray"
                and _base_name(f.value) in _NUMPY_ALIASES):
            what = "np.asarray()"
        if what is None:
            continue
        if ctx.in_host_sync_allowed(node):
            continue
        out.append(ctx.finding(
            "JL102", node,
            f"host-sync call {what} in a traced hot-path module — it "
            f"stalls the dispatch pipeline (or fails under tracing); "
            f"fence through repro.obs instead, or mark a deliberate "
            f"fencing helper with @host_sync_allowed"))
    return out


# ---------------------------------------------------------------------------
# JL103 — isinstance(x, jax.core.Tracer) bypassing compat.is_tracer.
# ---------------------------------------------------------------------------

def _mentions_tracer(node) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "Tracer":
            return True
        if isinstance(sub, ast.Name) and sub.id == "Tracer":
            return True
    return False


def check_tracer_isinstance(ctx: FileContext) -> List[Finding]:
    if ctx.tracer_exempt:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Call)
                and _terminal_name(node.func) == "isinstance"
                and len(node.args) == 2 and _mentions_tracer(node.args[1])):
            out.append(ctx.finding(
                "JL103", node,
                "isinstance(x, ...Tracer) — use repro.core.compat."
                "is_tracer, the one place that names the Tracer class"))
    return out


# ---------------------------------------------------------------------------
# JL104 — nondeterminism sources in traced code.
# ---------------------------------------------------------------------------

_NONDET_MODULES = {"time", "random"}


def check_nondeterminism(ctx: FileContext) -> List[Finding]:
    if not ctx.det_scope:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] in _NONDET_MODULES:
                    out.append(ctx.finding(
                        "JL104", node,
                        f"import of '{alias.name}' in traced code — "
                        f"clocks/host RNG poison custom_vjp replay and "
                        f"compile-cache determinism; thread jax.random "
                        f"keys or host-side timestamps in as inputs"))
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[0] in _NONDET_MODULES:
                out.append(ctx.finding(
                    "JL104", node,
                    f"import from '{node.module}' in traced code (see "
                    f"JL104 --explain)"))
        elif (isinstance(node, ast.Attribute) and node.attr == "random"
                and _base_name(node) in _NUMPY_ALIASES):
            out.append(ctx.finding(
                "JL104", node,
                "np.random in traced code — host RNG is invisible to "
                "jax's tracing and breaks bitwise replay; use "
                "jax.random with a threaded key"))
    return out


# ---------------------------------------------------------------------------
# JL105 — Pallas debug debris.
# ---------------------------------------------------------------------------

_PALLAS_ALIASES = {"pl", "pallas", "pltpu"}


def check_pallas_debris(ctx: FileContext) -> List[Finding]:
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _terminal_name(node.func)
        if (name == "debug_print"
                and (not isinstance(node.func, ast.Attribute)
                     or _base_name(node.func.value) in _PALLAS_ALIASES)):
            out.append(ctx.finding(
                "JL105", node,
                "pl.debug_print left in a kernel — debug scaffolding; "
                "it forces a host round-trip per grid step"))
        if name == "pallas_call":
            for kw in node.keywords:
                if (kw.arg == "interpret"
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value is True):
                    out.append(ctx.finding(
                        "JL105", node,
                        "pallas_call(interpret=True) hard-coded — "
                        "interpret mode must flow from the "
                        "kernel_backend knob, never be baked in"))
    return out


# ---------------------------------------------------------------------------
# JL106 — unmasked dynamic pl.load / pl.store.
# ---------------------------------------------------------------------------

_DSLICE_NAMES = {"ds", "dslice", "dynamic_slice"}


def check_unmasked_dynamic_load(ctx: FileContext) -> List[Finding]:
    out = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _terminal_name(node.func)
        if name not in ("load", "store"):
            continue
        if not (isinstance(node.func, ast.Attribute)
                and _base_name(node.func.value) in _PALLAS_ALIASES):
            continue
        dynamic = any(
            isinstance(sub, ast.Call)
            and _terminal_name(sub.func) in _DSLICE_NAMES
            for arg in node.args for sub in ast.walk(arg))
        masked = any(kw.arg in ("mask", "other") for kw in node.keywords)
        if dynamic and not masked:
            out.append(ctx.finding(
                "JL106", node,
                f"dynamic pl.{name} without mask= — a padded tail block "
                f"reads/writes out of bounds; pass mask= (and other= for "
                f"loads) covering the valid prefix"))
    return out


# ---------------------------------------------------------------------------
# Registry + explain docs.
# ---------------------------------------------------------------------------

Rule = Callable[[FileContext], List[Finding]]

RULES: Dict[str, Tuple[str, Rule]] = {
    "JL101": ("raw axis-name string literal", check_axis_literals),
    "JL102": ("host sync in traced hot path", check_host_syncs),
    "JL103": ("Tracer isinstance bypassing compat", check_tracer_isinstance),
    "JL104": ("nondeterminism in traced code", check_nondeterminism),
    "JL105": ("Pallas debug debris", check_pallas_debris),
    "JL106": ("unmasked dynamic pl.load/store", check_unmasked_dynamic_load),
}

EXPLAIN: Dict[str, str] = {
    "JL101": """\
JL101 — raw axis-name string literal

The mesh axis names ("data", "sequence", "model", "pod") are exported as
constants by repro/launch/mesh.py (DATA_AXIS, SEQ_AXIS, MODEL_AXIS,
POD_AXIS), and mesh.py is the ONLY module allowed to spell the strings.
Everything else — PartitionSpec entries, shard_map axis_names, psum/
all_gather axis arguments, sharding-rule tables, budget keys — must use
the constants, so renaming an axis is a one-line change the type of
which the compiler can check, instead of a repo-wide grep with silent
misses. MODEL_AXIS is a LIVE training axis since the 3D DP×SP×TP
ulysses mesh landed — "model" literals in training code are real
budget-classification hazards, not dead-axis pedantry.

Denied contexts (not flagged): the axis words also appear as linear-
attention decay *kinds* (cfg.linear_attn.decay == "data") and phase-
timer labels (timer.phase("data")); comparisons against names/attributes
called `decay`/`kind`, `decay=`/`kind=` keywords, and arguments to
`phase(...)`/`LinearAttnConfig(...)` are recognized as non-axis usages.

Fix: from repro.launch.mesh import DATA_AXIS, SEQ_AXIS, ...
""",
    "JL102": """\
JL102 — host-sync call inside a traced hot-path module

block_until_ready, .item(), np.asarray, jax.device_get and print() all
force a device->host round-trip. Inside the traced hot path (core/,
kernels/, comm/, train/step.py) they either fail outright under tracing
or — worse — silently serialize the async dispatch pipeline, which is
exactly the per-step stall LASP-2's single-AllGather structure exists to
avoid. Host-side drivers (train/loop.py, serve/, launch/) are out of
scope: they own the synchronization points.

The observability fencing helpers in obs/metrics.py are the one
legitimate holder: they synchronize deliberately so per-phase walls
attribute async work to the right phase. Those sites carry
@repro.analysis.decorators.host_sync_allowed, which exempts the
enclosing function.

Fix: return values out of the traced region and sync in the driver, or
route timing through repro.obs (scoped_timer / Fence).
""",
    "JL103": """\
JL103 — isinstance(x, jax.core.Tracer)

Where jax exports the Tracer class is a jax decision (jax.core today;
jax.extend.core has none), so the repo names it in exactly one place:
repro/core/compat.py exports is_tracer(). Direct isinstance checks
scatter that decision and each breaks when jax moves the class.

Fix: from repro.core.compat import is_tracer; is_tracer(x).
""",
    "JL104": """\
JL104 — time/random/np.random in traced code

Traced code (core/, kernels/, comm/, train/step.py) runs under jit:
host clocks and host RNG are read ONCE at trace time and baked into the
program — the value silently freezes, and any dependence on it breaks
both the custom_vjp forward/backward consistency and compile-cache
determinism (two lowerings of the same step must produce identical
programs; the sanitizer's SAN205 check asserts exactly that).

Fix: randomness flows through jax.random keys threaded as inputs;
timestamps are host-driver concerns (train/loop.py, repro.obs).
""",
    "JL105": """\
JL105 — Pallas debug debris

pl.debug_print and hard-coded pallas_call(interpret=True) are debugging
scaffolding. debug_print forces a host round-trip per grid step;
interpret=True silently runs the kernel on the interpreter — orders of
magnitude slower — while looking like a real Pallas deployment. The
interpret path is a supported *backend* (kernel_backend="interpret"),
so it must always arrive via the knob, never a literal.

Fix: delete the debug_print; pass interpret through from the caller's
kernel_backend plumbing (repro/kernels/ops.py).
""",
    "JL106": """\
JL106 — dynamic pl.load / pl.store without mask=

A pl.load/pl.store whose index contains pl.ds(...) (a dynamic slice)
can straddle the padded tail of a block — on TPU the out-of-bounds
lanes read garbage (or clamp), which is how padding bugs ship silently.
Any dynamic load/store must pass mask= (and other= for loads) covering
the valid prefix, like the flash kernels' where-masked tails.

Fix: mask = iota < valid_len; pl.load(ref, idx, mask=mask, other=0.0).
""",
    "PAL301": """\
PAL301 — BlockSpec index_map out of grid bounds

Every pallas_call BlockSpec index_map must map every grid point to a
block index inside the operand's block grid (0 <= idx < ceil(dim /
block)). An out-of-range index map reads a neighboring batch row's
blocks (or clamps silently on TPU) — the bug class PR 3 fixed by hand
in the backward band arithmetic. repro.analysis.pallas_check evaluates
every index map of every kernel at every grid point under
jax.eval_shape (no kernel execution) and flags violations.

Fix: clamp with jnp.clip against the block count (see
kernels/flash_attention.py kv_im) or fix the band arithmetic.
""",
    "SAN201": """\
SAN201 — host transfer in a compiled hot-path program

The compiled (post-SPMD) HLO of the train/decode steps must contain no
infeed/outfeed ops and no host custom-calls: any of these means a
device<->host round trip inside the step, serializing the async
dispatch pipeline every iteration.
""",
    "SAN202": """\
SAN202 — f64 ops in a compiled hot-path program

Nothing in the training or decode path is f64: an f64[...] (or
c128[...]) buffer in compiled HLO means an accidental Python-float
promotion doubled somebody's bytes (and on TPU, f64 is emulated).
Keep scalars jnp-typed; check weak-type promotion at the site the
sanitizer names.
""",
    "SAN203": """\
SAN203 — comm_dtype=bf16 collective not actually bf16 on the wire

With comm_dtype=bf16, the LASP-2 state exchange (the per-layer
all-gather of (M_t, A_t) over the sequence axis, and its reduce-scatter
transpose) must carry bf16 element type. The check reads the LOWERED
StableHLO (the compiled CPU HLO upcasts bf16 collectives to f32 —
storage-only bf16 on XLA:CPU — so the wire dtype is only visible before
optimization). The ZeRO-1 parameter all-gather over the data axis and
the packed gradient all-reduce stay fp32 by design and are exempt.
""",
    "SAN204": """\
SAN204 — donated buffers not actually aliased

train/loop.py donates the step state (donate_argnums=(0,)) and the
serve engine donates the decode cache; if the compiled program's
input_output_alias table is empty the donation silently degraded to a
copy — peak memory doubles for the params + optimizer state. Usually a
dtype/layout mismatch between the donated input and its output.
""",
    "SAN205": """\
SAN205 — nondeterministic lowering (collective fingerprint drift)

Two independent lowerings of the same step must produce the identical
sequence of collectives (op, element type, shape, replica groups). A
drift means something nondeterministic leaked into trace time — dict
ordering, host RNG (JL104's dynamic twin) — and invalidates the HLO
budget checks and compile caching.
""",
}


def explain(code: str) -> str:
    try:
        return EXPLAIN[code.upper()]
    except KeyError:
        known = ", ".join(sorted(EXPLAIN))
        raise KeyError(f"unknown rule code {code!r}; known: {known}")
