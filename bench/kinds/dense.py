"""The dense gated MLP (SwiGLU), as Qwen1.5 and Llama have it:

    out = (silu(h W1) ⊙ h W3) W2
"""

from __future__ import annotations

import functools

import jax

from bench.reference.model import _ein

PROGRAM = "dense"

_LEAVES = ("w1", "w3", "w2")


def weights(c: dict) -> dict:
    d, f = c["hidden_size"], c["intermediate_size"]
    return {"w1": ((d, f), "matrix"), "w3": ((d, f), "matrix"),
            "w2": ((f, d), "matrix")}


def to_program(lw: dict) -> dict:
    return {n: lw[n] for n in _LEAVES}


def from_program(tree: dict) -> dict:
    return {n: tree[n] for n in _LEAVES}


def apply(c, lw, h, precision):
    mm = functools.partial(_ein, "sd,df->sf", precision=precision)
    return mm(jax.nn.silu(mm(h, lw["w1"])) * mm(h, lw["w3"]), lw["w2"])


def matmul_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def mixing_flops(c: dict, seq_len: int = None) -> int:
    return 0


def decode_mixing_flops(c: dict, context: int = None) -> int:
    return 0
