"""Layer scopes of the traced train step, and their reading back from a
compiled module.

The model, the layer scan, the loss and the optimizer open a
:func:`scope` around their work while the step is traced. A scope is a
``jax.named_scope``: it changes nothing but the ``op_name`` metadata of
the instructions it covers, so the compiled program is otherwise the same
and costs nothing with tracing off. Scan, remat and autodiff keep the
names, wrapped in the transform that made each instruction::

    jit(train_step)/transpose(jvp(layers))/while/body/closed_call/
        checkpoint/rematted_computation/mixer.linear/dot_general

:func:`instruction_scopes` maps every instruction of a compiled module's
``as_text()`` to its innermost scope and its pass, which is how a device
trace, whose events are named by instruction, gets a layer.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

import jax

SCOPES = (
    "embed",            # token embedding lookup (models/model.py)
    "layers",           # the layer scan itself: slicing and stacking
    "mixer.softmax",    # ln1 and the token mixer, one scope per kind
    "mixer.linear",
    "mixer.mamba2",
    "mixer.hymba",
    "mixer.cross",
    "mlp",              # ln2 and the MLP or MoE
    "head",             # final norm and logits
    "loss",             # cross-entropy (train/step.py)
    "optimizer",        # clipping or guard verdict, schedule, AdamW
    "grad_reduce",      # the manual step's packed gradient psum
)
# Each exchange of repro.comm.primitives opens ``comm.<tag>`` around its
# collective (``comm_scope``): ``comm.lasp2.states``, ``comm.lasp2h.k``,
# ``comm.lasp2h.v``, ``comm.train.grads``, ... Autodiff's mirrored
# exchange keeps the name under ``transpose(...)``, so it reads as pass
# ``bwd``; the all-to-all's hand-written backward is ``comm.<tag>.bwd``.
COMM = "comm."

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_\-.]+)\s*=\s")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_WRAPPED = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*\((.*)\)$")


def scope(name: str):
    """``jax.named_scope(name)`` for one of :data:`SCOPES`; any other name
    is refused, so a typo cannot open a scope no reader knows."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; known: {SCOPES}")
    return jax.named_scope(name)


def comm_scope(tag: str, op: str):
    """``jax.named_scope("comm.<tag>")`` around one collective; an
    untagged call is named by its op, ``comm.<op>``."""
    return jax.named_scope(COMM + (tag or op))


def _known(part: str) -> bool:
    return part in SCOPES or (part.startswith(COMM) and len(part) > len(COMM))


def parse_op_name(op_name: str) -> Tuple[Optional[str], str]:
    """``(innermost scope or None, pass)`` of one ``op_name`` path.

    The pass is ``remat`` under ``rematted_computation`` (the forward
    recomputed in the backward pass), else ``bwd`` under a ``transpose(``,
    else ``fwd`` (the forward, and work no derivative touches such as the
    optimizer)."""
    if "rematted_computation" in op_name:
        kind = "remat"
    elif "transpose(" in op_name:
        kind = "bwd"
    else:
        kind = "fwd"
    found = None
    for part in op_name.split("/"):
        while True:
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
        if _known(part):
            found = part
    return found, kind


def instruction_scopes(hlo_text: str) -> Dict[str, Tuple[Optional[str], str]]:
    """Instruction name -> ``(scope or None, pass)`` over every
    computation of a compiled module's text; an instruction without
    ``op_name`` metadata maps to ``(None, "fwd")``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        out[m.group(1)] = parse_op_name(op.group(1)) if op else (None, "fwd")
    return out
