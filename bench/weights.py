"""Weights made by the benchmark from ``--seed``, on the device, in one
jitted call.

They are a flat dict of arrays stacked over layers (``wq``: (L, d, H·dh),
…), which the plain reference reads as it is; ``program_tree`` arranges the
same arrays in the program's parameter layout. Neither the program's nor
the reference's own initialisation is used, so both sides see identical
values that neither of them made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.model import padded_vocab

def shapes(c: dict) -> dict:
    L, d = c["num_hidden_layers"], c["hidden_size"]
    hq = c["num_attention_heads"] * c["head_dim"]
    hkv = c["num_key_value_heads"] * c["head_dim"]
    f, vp = c["intermediate_size"], padded_vocab(c)
    out = {"embed": (vp, d), "lm_head": (vp, d), "final_norm": (d,),
           "ln1": (L, d), "wq": (L, d, hq), "wk": (L, d, hkv),
           "wv": (L, d, hkv), "wo": (L, hq, d), "ln2": (L, d),
           "w1": (L, d, f), "w3": (L, d, f), "w2": (L, f, d)}
    if c["qkv_bias"]:
        out.update(bq=(L, hq), bk=(L, hkv), bv=(L, hkv))
    return out


def seed_key(seed: int):
    """A PRNG key for any seed up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _make(key, c: dict, matrix_dtype):
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(c).items())):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if name in ("embed", "lm_head"):
            x = 0.02 * z
        elif name.startswith("b"):
            x = 0.02 * z
        elif name.startswith("ln") or name == "final_norm":
            x = 1.0 + 0.02 * z
        else:
            x = z * shape[-2] ** -0.5
        out[name] = x.astype(matrix_dtype) if x.ndim >= 2 and \
            name not in ("ln1", "ln2", "bq", "bk", "bv") else x
    return out


def make_weights(seed: int, c: dict, matrix_dtype, out_shardings=None):
    """Flat weight dict from the seed, built on the device in one call.
    Matrices are ``matrix_dtype``; norm scales and biases fp32."""
    fn = jax.jit(functools.partial(_make, c=c, matrix_dtype=matrix_dtype),
                 out_shardings=out_shardings)
    return fn(seed_key(seed))


def program_tree(w: dict, c: dict) -> dict:
    """The same arrays in ``repro.models.model``'s parameter layout (one
    pattern position, stacked over all layers)."""
    if len(c["layer_pattern"]) != 1:
        raise NotImplementedError("one layer kind per configuration so far")
    mixer = {n: w[n] for n in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
             if n in w}
    return {"embed": {"table": w["embed"], "lm_head": w["lm_head"]},
            "groups": [{"ln1": {"scale": w["ln1"]}, "mixer": mixer,
                        "ln2": {"scale": w["ln2"]},
                        "mlp": {n: w[n] for n in ("w1", "w3", "w2")}}],
            "final_norm": {"scale": w["final_norm"]}}


def flat_from_program(tree: dict, c: dict) -> dict:
    """Inverse of :func:`program_tree`."""
    g = tree["groups"][0]
    return {"embed": tree["embed"]["table"],
            "lm_head": tree["embed"]["lm_head"],
            "final_norm": tree["final_norm"]["scale"],
            "ln1": g["ln1"]["scale"], "ln2": g["ln2"]["scale"],
            **g["mixer"], **g["mlp"]}
