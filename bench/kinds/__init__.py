"""Layer kinds, found by name: ``bench/kinds/<name>.py``.

A configuration's ``layer_pattern`` lists the kinds of its layers, one
entry per pattern position: a mixer's name (that mixer followed by the
dense MLP), or ``{"mixer": <name>, "mlp": <name>}``. The pattern repeats
``num_hidden_layers / len(layer_pattern)`` times, as the program stacks it.
Each name is a module of this directory, so a configuration brings a new
kind as a new file. A kind module gives:

``PROGRAM``
    the program's name for it (``repro.configs.base.LayerSpec``'s
    ``mixer`` or ``mlp``).
``weights(c)``
    its leaves of one layer, ``{name: (shape, init)}``; ``init`` is
    ``matrix``, ``bias`` or ``norm`` (``bench.weights._make``).
``to_program(lw)``, ``from_program(tree)``
    those leaves as the program's ``mixer`` or ``mlp`` subtree, and back.
``apply``
    its plain float32 reference, every matrix product through
    ``bench.reference.model._ein`` (so that the float8 control reaches
    it), nothing imported from the program: a mixer's
    ``(c, lw, h, pos, seg, precision) -> (S, d)``, an MLP's
    ``(c, lw, h, precision) -> (S, d)``; ``lw`` holds its leaves of one
    layer, ``h`` the normed input of one row.
``matmul_params(c)``
    matrix parameters of one layer that every token multiplies.
``mixing_flops(c, seq_len)``, ``decode_mixing_flops(c, context)``
    forward FLOPs per token beyond those products, in a row of
    ``seq_len`` tokens and in one decode step after ``context`` tokens
    (0 for an MLP).
"""

from __future__ import annotations

import importlib.util
import os

DIR = os.path.dirname(os.path.abspath(__file__))
DEFAULT_MLP = "dense"

_loaded = {}


def kind(name: str):
    """The module of the kind called ``name``."""
    path = os.path.join(DIR, f"{name}.py")
    if path not in _loaded:
        if not os.path.exists(path):
            raise KeyError(f"no layer kind {name!r} at {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_kind_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def pattern(c: dict) -> list:
    """The configuration's pattern positions, each ``{"mixer", "mlp"}``."""
    out = []
    for entry in c["layer_pattern"]:
        if isinstance(entry, str):
            entry = {"mixer": entry, "mlp": DEFAULT_MLP}
        if set(entry) != {"mixer", "mlp"}:
            raise ValueError(f"a pattern entry is a mixer's name or "
                             f"{{'mixer', 'mlp'}}, not {entry!r}")
        out.append(dict(entry))
    return out


def groups(c: dict) -> int:
    """How many times the pattern repeats."""
    n, p = c["num_hidden_layers"], len(c["layer_pattern"])
    if n % p:
        raise ValueError(f"{n} layers do not repeat a pattern of {p}")
    return n // p
