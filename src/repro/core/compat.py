"""Thin helpers over the jax APIs the repo relies on (jax 0.9).

:func:`shard_map` fixes the repo's calling convention for the
partial-manual ``jax.shard_map`` (``axis_names`` = the manual axes, all
of them when ``None``); :func:`is_tracer` is the one place that names the
``Tracer`` class (lint rule JL103 points every other check here).
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=False):
    """Partial-manual ``jax.shard_map``.

    ``axis_names``: set of mesh axes made manual inside ``f`` (all axes
    when None) — other axes stay auto-sharded by GSPMD.
    """
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def is_tracer(x) -> bool:
    """True iff ``x`` is a jax tracer (an abstract value inside a trace),
    e.g. "is this sliding window dynamic?" in backend dispatch.
    ``jax.extend.core`` has no ``Tracer``, so this stays on
    ``jax.core.Tracer``."""
    return isinstance(x, jax.core.Tracer)
