"""Compiles the train step of a tiny 1/4 hybrid (3 linear : 1 softmax
layers) at dp 1 × sp 4 on four virtual CPU devices and prints, as one
JSON object, every layer scope its instructions carry with their passes.

    python3 tests/comm_scope_checks.py

Run by ``tests/test_scopes.py`` in a process of its own: the device
count is fixed when JAX starts."""

import collections
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.model import load_json, model_config, run_config  # noqa: E402
from repro.launch.mesh import make_training_mesh  # noqa: E402
from repro.obs import scopes  # noqa: E402
from repro.sharding.rules import make_plan  # noqa: E402
from repro.train import step as train_step  # noqa: E402


def main() -> int:
    c = dict(load_json("bench/tests/data/tiny.json"),
             layer_pattern=["linear", "linear", "linear", "softmax"],
             num_hidden_layers=4)
    cfg, run = model_config(c), run_config(c, {"remat": "full"})
    mesh = make_training_mesh(1, 4, devices=jax.devices()[:4])
    plan = make_plan(mesh, "train", n_heads=cfg.n_heads,
                     n_kv_heads=cfg.n_kv_heads)
    state = jax.eval_shape(
        lambda k: train_step._init_state(k, cfg, run, plan),
        jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((1, 1, 512), jnp.int32)
    batch = {"tokens": tok, "labels": tok,
             "resets": jax.ShapeDtypeStruct((1, 1, 512), bool)}
    text = jax.jit(train_step.make_train_step(cfg, run, plan)).lower(
        state, batch).compile().as_text()
    found = collections.defaultdict(set)
    for scope, kind in scopes.instruction_scopes(text).values():
        if scope is not None:
            found[scope].add(kind)
    print(json.dumps({k: sorted(v) for k, v in found.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
