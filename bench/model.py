"""The bridge from a configuration file to the program under test.

The only place where a configuration's sizes become the program's own
``ModelConfig`` and ``RunConfig``. Everything else the benchmark knows of
the model (weights, counts, reference) reads the configuration file.
"""

from __future__ import annotations

import json
import os

from bench import kinds

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def padded_vocab(c: dict) -> int:
    """Vocabulary rows the program holds: padded to a multiple of 128."""
    return -(-c["vocab_size"] // 128) * 128


def model_config(c: dict):
    from repro.configs.base import LayerSpec, LinearAttnConfig, ModelConfig

    la = c["linear_attention"]
    return ModelConfig(
        name=c["name"], family="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=c["qkv_bias"], rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        pattern=tuple(LayerSpec(mixer=kinds.kind(p["mixer"]).PROGRAM,
                                mlp=kinds.kind(p["mlp"]).PROGRAM)
                      for p in kinds.pattern(c)),
        linear_attn=LinearAttnConfig(
            feature_map=la["feature_map"], decay=la["decay"],
            backward=la["backward"], block_size=la["block_size"]),
        dtype=c["compute_dtype"], param_dtype=c["train_param_dtype"],
        source=c["source"])


def run_config(c: dict, layout: dict):
    from repro.configs.base import RunConfig

    o = c["optimizer"]
    return RunConfig(
        remat=layout.get("remat", "full"),
        learning_rate=o["learning_rate"], min_lr=o["min_lr"],
        warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
        weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
        adam_b1=o["b1"], adam_b2=o["b2"])
