"""Weights made by the benchmark from ``--seed``, on the device, in one
jitted call.

They are a flat dict of arrays, each layer leaf stacked over the
pattern's repeats as the program stacks it (``wq``: (L, d, H·dh), …),
which the plain reference reads as it is; ``program_tree`` arranges the
same arrays in the program's parameter layout. Neither the program's nor
the reference's own initialisation is used, so both sides see identical
values that neither of them made.

Each layer holds ``ln1``, its mixer kind's leaves and, where its MLP kind
has leaves, ``ln2`` and those (``bench/kinds``). A one-position pattern
names a leaf as the kind does; with several positions a leaf of position
``p`` is ``p<p>.<leaf>``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import kinds
from bench.model import padded_vocab

def layer_leaves(c: dict) -> list:
    """Per pattern position, the leaves of one layer: ``{name: (shape,
    init)}``."""
    d = c["hidden_size"]
    out = []
    for spec in kinds.pattern(c):
        leaves = {"ln1": ((d,), "norm"),
                  **kinds.kind(spec["mixer"]).weights(c)}
        mlp = kinds.kind(spec["mlp"]).weights(c)
        if mlp:
            leaves.update(ln2=((d,), "norm"), **mlp)
        out.append(leaves)
    return out


def leaf_name(c: dict, p: int, name: str) -> str:
    return name if len(c["layer_pattern"]) == 1 else f"p{p}.{name}"


def leaves(c: dict) -> dict:
    """Every leaf of the model: ``{name: (shape, init)}``."""
    d, vp, g = c["hidden_size"], padded_vocab(c), kinds.groups(c)
    out = {"embed": ((vp, d), "embed"), "lm_head": ((vp, d), "embed"),
           "final_norm": ((d,), "norm")}
    for p, layer in enumerate(layer_leaves(c)):
        for name, (shape, init) in layer.items():
            out[leaf_name(c, p, name)] = ((g,) + tuple(shape), init)
    return out


def seed_key(seed: int):
    """A PRNG key for any seed up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _make(key, c: dict, matrix_dtype):
    """Each leaf by its ``init``: ``matrix`` N(0, 1/fan_in) and ``embed``
    N(0, 0.02²) in ``matrix_dtype``; ``bias`` N(0, 0.02²) and ``norm``
    1 + N(0, 0.02²) in fp32. Leaf ``i`` of the sorted names draws from
    ``fold_in(key, i)``."""
    out = {}
    for i, (name, (shape, init)) in enumerate(sorted(leaves(c).items())):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if init in ("embed", "bias"):
            x = 0.02 * z
        elif init == "norm":
            x = 1.0 + 0.02 * z
        elif init == "matrix":
            x = z * shape[-2] ** -0.5
        else:
            raise ValueError(f"unknown init {init!r} of leaf {name!r}")
        out[name] = x.astype(matrix_dtype) \
            if init in ("matrix", "embed") else x
    return out


def make_weights(seed: int, c: dict, matrix_dtype, out_shardings=None):
    """Flat weight dict from the seed, built on the device in one call.
    Matrices are ``matrix_dtype``; norm scales and biases fp32."""
    fn = jax.jit(functools.partial(_make, c=c, matrix_dtype=matrix_dtype),
                 out_shardings=out_shardings)
    return fn(seed_key(seed))


def program_tree(w: dict, c: dict) -> dict:
    """The same arrays in ``repro.models.model``'s parameter layout: one
    subtree per pattern position, stacked over the pattern's repeats."""
    groups = []
    for p, (spec, layer) in enumerate(zip(kinds.pattern(c),
                                          layer_leaves(c))):
        lw = {n: w[leaf_name(c, p, n)] for n in layer}
        mixer, mlp = kinds.kind(spec["mixer"]), kinds.kind(spec["mlp"])
        g = {"ln1": {"scale": lw["ln1"]}, "mixer": mixer.to_program(lw)}
        if "ln2" in lw:
            g["ln2"] = {"scale": lw["ln2"]}
            g["mlp"] = mlp.to_program(lw)
        groups.append(g)
    return {"embed": {"table": w["embed"], "lm_head": w["lm_head"]},
            "groups": groups,
            "final_norm": {"scale": w["final_norm"]}}


def flat_from_program(tree: dict, c: dict) -> dict:
    """Inverse of :func:`program_tree`."""
    out = {"embed": tree["embed"]["table"],
           "lm_head": tree["embed"]["lm_head"],
           "final_norm": tree["final_norm"]["scale"]}
    for p, (spec, g) in enumerate(zip(kinds.pattern(c), tree["groups"])):
        lw = {"ln1": g["ln1"]["scale"],
              **kinds.kind(spec["mixer"]).from_program(g["mixer"])}
        if "mlp" in g:
            lw.update(ln2=g["ln2"]["scale"],
                      **kinds.kind(spec["mlp"]).from_program(g["mlp"]))
        out.update({leaf_name(c, p, n): x for n, x in lw.items()})
    return out
