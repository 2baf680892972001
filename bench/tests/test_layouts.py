"""Layer kinds found by name, patterns of several positions, and the
sequence-parallel layout over four devices, each against the plain
reference at a tiny size on the CPU."""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import kinds
from bench.model import load_json
from bench.tests import harness
from bench.reference import model as R
from bench.weights import leaves, make_weights, seed_key

HERE = os.path.dirname(os.path.abspath(__file__))
TWO = {"layer_pattern": ["linear", {"mixer": "linear", "mlp": "dense"}],
       "num_hidden_layers": 4}


def shapes(c):
    return {n: shape for n, (shape, _) in leaves(c).items()}


def _named_rule(key, c, matrix_dtype):
    """The weights of a one-kind configuration as they were made before
    kinds came from files: every leaf by its name, stacked over layers."""
    L, d = c["num_hidden_layers"], c["hidden_size"]
    hq = c["num_attention_heads"] * c["head_dim"]
    hkv = c["num_key_value_heads"] * c["head_dim"]
    f, vp = c["intermediate_size"], -(-c["vocab_size"] // 128) * 128
    shp = {"embed": (vp, d), "lm_head": (vp, d), "final_norm": (d,),
           "ln1": (L, d), "wq": (L, d, hq), "wk": (L, d, hkv),
           "wv": (L, d, hkv), "wo": (L, hq, d), "ln2": (L, d),
           "w1": (L, d, f), "w3": (L, d, f), "w2": (L, f, d),
           "bq": (L, hq), "bk": (L, hkv), "bv": (L, hkv)}
    out = {}
    for i, (name, shape) in enumerate(sorted(shp.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name in ("embed", "lm_head") or name.startswith("b"):
            x = 0.02 * z
        elif name.startswith("ln") or name == "final_norm":
            x = 1.0 + 0.02 * z
        else:
            x = z * shape[-2] ** -0.5
        out[name] = x.astype(matrix_dtype) if x.ndim >= 2 and \
            name not in ("ln1", "ln2", "bq", "bk", "bv") else x
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_position_weights_keep_their_names_and_scales(dtype):
    c = load_json("bench/tests/data/tiny.json")
    seed = 2**33 + 7
    got = make_weights(seed, c, dtype)
    # made in one jitted call, as the benchmark makes them
    want = jax.jit(lambda k: _named_rule(k, c, dtype))(seed_key(seed))
    assert sorted(got) == sorted(want)
    for n in want:
        assert got[n].dtype == want[n].dtype, n
        assert np.array_equal(np.asarray(got[n]), np.asarray(want[n])), n
    # the cell's configuration keeps the same leaves and shapes
    full = load_json("bench/configs/qwen1.5-1.8b-linear.json")
    assert shapes(full) == {n: tuple(x.shape) for n, x in jax.eval_shape(
        lambda k: _named_rule(k, full, jnp.float32),
        jax.random.PRNGKey(0)).items()}


def test_pattern_entries_name_a_mixer_or_both_kinds():
    c = dict(load_json("bench/tests/data/tiny.json"), **TWO)
    assert kinds.pattern(c) == [{"mixer": "linear", "mlp": "dense"}] * 2
    assert kinds.groups(c) == 2
    with pytest.raises(ValueError):
        kinds.pattern(dict(c, layer_pattern=[{"mixer": "linear"}]))
    with pytest.raises(ValueError):
        kinds.groups(dict(c, num_hidden_layers=3))
    with pytest.raises(KeyError):
        kinds.kind("no_such_kind")


def test_two_position_pattern_agrees_to_rounding():
    c = dict(load_json("bench/tests/data/tiny.json"), **TWO)
    w = shapes(c)
    assert w["p0.wq"] == w["p1.wq"] == (2, 64, 64)
    assert "wq" not in w
    r = harness.run("train", config=TWO)
    assert r["correct"], r["check"]
    for name in ("loss_gap", "grad_gap", "change_gap"):
        assert r["check"][name]["value"] < 1e-5


@pytest.mark.parametrize("pattern", [["linear"], TWO["layer_pattern"]])
def test_long_rows_in_blocks_agree_with_one_block(monkeypatch, pattern):
    """A row longer than ``ROWS`` runs the mixer and the MLP in blocks (the
    last one padded): the same loss and gradient to fp32 rounding."""
    c = dict(load_json("bench/tests/data/tiny.json"),
             layer_pattern=pattern, num_hidden_layers=2 * len(pattern))
    w = make_weights(5, c, jnp.float32)
    s = 3 * R.CHUNK
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, c["vocab_size"], s), jnp.int32)
    labels = jnp.roll(tokens, -1)
    seg = jnp.asarray(np.cumsum(rng.random(s) < 0.01), jnp.int32)

    def loss_and_grad():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda w_: R.loss(w_, tokens, labels, seg, c)))(w)

    whole = loss_and_grad()
    monkeypatch.setattr(R, "ROWS", 2 * R.CHUNK)
    blocked = loss_and_grad()
    assert float(blocked[0]) == pytest.approx(float(whole[0]), rel=1e-6)
    for n, g in whole[1].items():
        np.testing.assert_allclose(blocked[1][n], g, rtol=1e-4,
                                   atol=1e-6 * float(jnp.max(jnp.abs(g))),
                                   err_msg=n)


def test_a_kind_is_a_file(tmp_path, monkeypatch):
    for name in os.listdir(kinds.DIR):
        if name.endswith(".py"):
            shutil.copy(os.path.join(kinds.DIR, name), tmp_path / name)
    shutil.copy(os.path.join(kinds.DIR, "linear.py"),
                tmp_path / "linear_copy.py")
    monkeypatch.setattr(kinds, "DIR", str(tmp_path))
    assert kinds.kind("linear_copy").__file__ == str(
        tmp_path / "linear_copy.py")
    r = harness.run("train", config={"layer_pattern": ["linear_copy"]})
    assert r["correct"], r["check"]
    assert r["check"]["loss_gap"]["value"] < 1e-5


@pytest.fixture(scope="module")
def four_devices():
    """The tiny cell at dp 1 × sp 4 on four CPU devices: sound, and with
    each fault a four-chip cell can have planted."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "four_devices.py"), "sound",
         "half_batch", "no_exchange"], env=env, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return {r["case"]: r for r in map(json.loads, out.stdout.splitlines())}


def test_sequence_parallel_layout_agrees_to_rounding(four_devices):
    r = four_devices["sound"]
    assert r["count"] == 4
    assert r["correct"], r["check"]
    for name in ("loss_gap", "grad_gap", "change_gap"):
        assert r["check"][name]["value"] < 1e-5


@pytest.mark.parametrize("fault", ["half_batch", "no_exchange"])
def test_a_fault_under_sequence_parallelism_is_caught(four_devices, fault):
    r = four_devices[fault]
    assert not r["correct"]
    assert r["check"]["loss_gap"]["value"] > \
        harness.LIMITS["train"]["limits"]["loss_gap"]
