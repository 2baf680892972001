"""The softmax kind and the 1/4 hybrid of ``hybrid-train-sp4-64k``, at a
tiny size on the CPU: the kind against the program's softmax mixer, its
counts against hand counts, and the hybrid at dp 1 × sp 4 on four devices
against the reference under the cell's limits, sound and with each fault
its softmax layer can have."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import correct, counts, kinds
from bench.model import load_json, model_config
from bench.weights import layer_leaves, make_weights

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "hybrid-train-sp4-64k"
FAULTS = ("kv_local", "q_offset_zero", "non_causal")
softmax = kinds.kind("softmax")


def _one_softmax_layer():
    return dict(load_json("bench/tests/data/tiny.json"),
                layer_pattern=["softmax"], num_hidden_layers=1)


@pytest.mark.parametrize("s", [256, 300])
def test_softmax_kind_agrees_with_the_program_mixer(s):
    from repro.models import blocks
    from repro.sharding.rules import local_plan

    c = _one_softmax_layer()
    w = make_weights(3, c, jnp.float32)
    lw = {n: w[n][0] for n in layer_leaves(c)[0]}
    h = jax.random.normal(jax.random.PRNGKey(1), (s, c["hidden_size"]))
    pos, seg = jnp.arange(s), jnp.zeros(s, jnp.int32)
    ctx = blocks.Ctx(cfg=model_config(c), plan=local_plan(),
                     positions=pos[None])
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda x: softmax.apply(c, lw, x, pos, seg,
                                              "fp32"))(h)
        prog = jax.jit(lambda x: blocks.softmax_apply(
            softmax.to_program(lw), x[None], ctx))(h)[0]
    assert float(jnp.max(jnp.abs(ref - prog))) < 1e-5 * float(
        jnp.max(jnp.abs(ref)))


def test_softmax_reference_masks_other_documents(monkeypatch):
    """Keys of an earlier document are not seen, and the query blocks,
    the last one padded, agree with blocks that divide the row."""
    c = _one_softmax_layer()
    w = make_weights(4, c, jnp.float32)
    lw = {n: w[n][0] for n in layer_leaves(c)[0]}
    s = 300
    h = jax.random.normal(jax.random.PRNGKey(2), (s, c["hidden_size"]))
    pos = jnp.arange(s)
    two = jnp.where(pos < 200, 0, 1)
    with jax.default_matmul_precision("highest"):
        whole = softmax.apply(c, lw, h, pos, two, "fp32")
        alone = softmax.apply(c, lw, h[200:], pos[200:],
                              jnp.zeros(s - 200, jnp.int32), "fp32")
        monkeypatch.setattr(softmax, "QUERY_BLOCK", 100)
        unpadded = softmax.apply(c, lw, h, pos, two, "fp32")
    assert jnp.allclose(whole[200:], alone, rtol=1e-5, atol=1e-5)
    assert jnp.allclose(unpadded, whole, rtol=1e-5, atol=1e-5)


def test_softmax_counts_match_hand_counts():
    c = _one_softmax_layer()          # 4 heads of 16
    assert softmax.mixing_flops(c, 512) == 2 * 4 * 16 * 513
    # summed over the row: token t sees t + 1 keys, 4·dh a key and head
    assert softmax.mixing_flops(c, 512) * 512 == \
        4 * 16 * 4 * sum(t + 1 for t in range(512))
    assert softmax.decode_mixing_flops(c, 100) == 4 * 4 * 16 * 100
    for fn in (softmax.mixing_flops, softmax.decode_mixing_flops):
        with pytest.raises(ValueError):
            fn(c, None)
    # rank 2 of 4 over 1,024 keys: query blocks end at 639 and 767
    assert softmax.needed_blocks(sq=256, sk=1024, q_offset=512) == (11, 6)
    assert softmax.needed_blocks(sq=256, sk=1024, q_offset=0) == (3, 2)
    bh, dh = 4, 16
    f, b = softmax.kernel_work("flash_attention_fwd", bh=bh, sq=256,
                               sk=1024, dh=dh, q_offset=512)
    assert f == bh * 11 * 4 * 128 * 128 * dh
    assert b == bh * (2 * 256 * dh * 2 + 2 * 768 * dh * 2 + 256 * 4)
    f, _ = softmax.kernel_work("flash_attention_bwd_dkv", bh=bh, sq=256,
                               sk=1024, dh=dh, q_offset=512)
    assert f == 2 * softmax.kernel_work(
        "flash_attention_fwd", bh=bh, sq=256, sk=1024, dh=dh,
        q_offset=512)[0]
    with pytest.raises(KeyError):
        softmax.kernel_work("lasp2_chunk_fwd", bh=bh, sq=256, sk=1024,
                            dh=dh, q_offset=0)


def test_the_cell_counts_its_softmax_layer_at_its_row_length():
    bench = json.load(open(os.path.join(HERE, "..", "..",
                                        "BENCHMARK.json")))
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    conf = {x["name"]: x for x in bench["configs"]}[cell["config"]]
    c = load_json(conf["file"])
    t = load_json(f"bench/traffic/{cell['traffic']}.json")
    with pytest.raises(ValueError):
        counts.train_flops_per_token(c)
    lin = dict(c, layer_pattern=["linear"] * 4)
    extra = counts.train_flops_per_token(c, t["seq_len"]) - \
        counts.train_flops_per_token(lin, t["seq_len"])
    assert extra == 3 * (softmax.mixing_flops(c, t["seq_len"])
                         - kinds.kind("linear").mixing_flops(c))
    assert t["layout"]["dp"] * t["layout"]["sp"] == cell["chips"] == 4
    assert t["docs"]["min"] == t["docs"]["max"] == t["seq_len"]
    # the ranks' needed work grows with their offset: rank 3 the most
    work = [softmax.rank_work(c, t, r)["flash_attention_fwd"][0]
            for r in range(4)]
    assert work == sorted(work) and work[3] > 6 * work[0]


@pytest.fixture(scope="module")
def hybrid_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "hybrid_four_devices.py"),
         "sound", *FAULTS], env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return {r["case"]: r for r in map(json.loads, out.stdout.splitlines())}


def test_hybrid_under_sequence_parallelism_agrees(hybrid_four_devices):
    r = hybrid_four_devices["sound"]
    assert r["count"] == 4
    assert r["correct"], r["check"]
    limits = correct.load_limits(CELL)["limits"]
    assert set(r["check"]) == set(limits)
    # fp32 on the CPU against fp32: far under the cell's limits
    fp32 = {"loss_gap": 2e-7, "grad_gap": 4e-6, "change_gap": 2e-6}
    for name, c in r["check"].items():
        assert c["value"] < fp32[name], name


@pytest.mark.parametrize("fault", FAULTS)
def test_a_softmax_fault_fails_the_cell_limits(hybrid_four_devices, fault):
    r = hybrid_four_devices[fault]
    assert not r["correct"], r["check"]
