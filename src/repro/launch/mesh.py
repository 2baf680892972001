"""Mesh construction + canonical mesh-axis names.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets ``XLA_FLAGS`` for 512 host devices before any jax
initialization; tests and benches see the default single device).

Axis naming is unified HERE and consumed everywhere else (sharding rules,
SP configs, the test batteries, benchmarks) — no other module may invent
axis names:

* ``DATA_AXIS`` ("data")      — data parallelism: batch sharding, gradient
  reduction, ZeRO-1 optimizer-state sharding; doubles as the FSDP axis on
  the production inference meshes.
* ``SEQ_AXIS`` ("sequence")   — LASP-2 sequence parallelism: every
  inter-chunk state exchange (the paper's single AllGather) runs over this
  axis and ONLY this axis.
* ``MODEL_AXIS`` ("model")    — tensor parallelism on the production
  inference meshes; on 3D training meshes it is the ulysses head-parallel
  axis (All-to-All repartition of attention heads) and additionally
  carries a share of the sequence for the linear layers.
* ``POD_AXIS`` ("pod")        — cross-pod data parallelism.
"""

from __future__ import annotations

import jax

DATA_AXIS = "data"
SEQ_AXIS = "sequence"
MODEL_AXIS = "model"
POD_AXIS = "pod"


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips, (DATA_AXIS, MODEL_AXIS).
    Multi-pod: 2×16×16 = 512 chips, (POD_AXIS, DATA_AXIS, MODEL_AXIS)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (POD_AXIS, DATA_AXIS, MODEL_AXIS) if multi_pod \
        else (DATA_AXIS, MODEL_AXIS)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_training_mesh(dp_degree: int, sp_degree: int, tp_degree: int = 1,
                       *, devices=None):
    """The training deployment mesh.

    ``tp_degree == 1`` (default): the paper's 2D mesh (PAPER.md §4,
    Table 6) — batch over ``DATA_AXIS`` × sequence over ``SEQ_AXIS``;
    ``(1, W)`` is pure sequence parallelism, ``(W, 1)`` pure data
    parallelism. ``tp_degree > 1``: the 3D DP×SP×TP mesh
    ``(DATA_AXIS, SEQ_AXIS, MODEL_AXIS)`` — tokens shard over the
    combined (sequence, model) axes and the model axis additionally
    carries the ulysses head-parallel All-to-All
    (docs/parallelism.md §3D)."""
    devices = devices if devices is not None else jax.devices()
    if dp_degree * sp_degree * tp_degree != len(devices):
        raise ValueError(
            f"dp_degree×sp_degree×tp_degree = {dp_degree}×{sp_degree}×"
            f"{tp_degree} must equal the device count {len(devices)}")
    import numpy as np
    if tp_degree == 1:
        dev = np.asarray(devices).reshape(dp_degree, sp_degree)
        return jax.sharding.Mesh(dev, (DATA_AXIS, SEQ_AXIS))
    dev = np.asarray(devices).reshape(dp_degree, sp_degree, tp_degree)
    return jax.sharding.Mesh(dev, (DATA_AXIS, SEQ_AXIS, MODEL_AXIS))


def make_sp_mesh(sp_degree: int, *, devices=None):
    """1-D pure-SP mesh over ``SEQ_AXIS`` (the SP test batteries and
    benchmarks)."""
    devices = devices if devices is not None else jax.devices()
    if sp_degree > len(devices):
        raise ValueError(
            f"sp_degree {sp_degree} exceeds {len(devices)} devices")
    import numpy as np
    return jax.sharding.Mesh(np.asarray(devices[:sp_degree]), (SEQ_AXIS,))


def make_test_mesh(shape=(2, 4), axes=(DATA_AXIS, SEQ_AXIS)):
    """Small mesh for in-repo distributed tests (8 host devices).

    Defaults to the 2D DP×SP training mesh; the TP batteries pass
    ``axes=(DATA_AXIS, MODEL_AXIS)`` explicitly."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
