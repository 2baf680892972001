"""Layer scopes of the train step (repro.obs.scopes), the host watch
(repro.obs.HostWatch), and the benchmark's per-layer reduction of a
device trace (bench/scoped.py) on the committed v5e trace."""

import collections
import contextlib
import gc
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.comm import primitives
from repro.configs import get_smoke
from repro.configs.base import RunConfig
from repro.models import blocks, model
from repro.obs import HostWatch, scopes
from repro.sharding.rules import local_plan, make_plan
from repro.train import step as train_step

ROOT = os.path.join(os.path.dirname(__file__), "..")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import scoped  # noqa: E402

TRACE = os.path.join(ROOT, "bench/tests/data/trace.xplane.pb")
_OPCODE = re.compile(r"=\s.*?\s([a-z][a-z0-9_\-]*)\(")

# every scope the tiny linear-attention step opens, with its passes
EXPECTED = {
    "embed": {"fwd", "bwd"},
    "layers": {"fwd", "remat", "bwd"},
    "mixer.linear": {"fwd", "remat", "bwd"},
    "mlp": {"fwd", "remat", "bwd"},
    "head": {"fwd", "bwd"},
    "loss": {"fwd", "bwd"},
    "optimizer": {"fwd"},
}


def _compile(flavour):
    from repro.launch.mesh import make_training_mesh

    cfg = get_smoke("linear-llama3-1b")
    run = RunConfig(num_microbatches=1, remat="full")
    if flavour == "manual":
        plan = make_plan(make_training_mesh(1, 1, 1), "train",
                         global_batch=2, n_kv_heads=cfg.n_kv_heads,
                         n_heads=cfg.n_heads)
        assert plan.manual_axes
    else:
        plan = local_plan()
    state = train_step.init_state(jax.random.PRNGKey(0), cfg, run, plan)
    tok = jnp.zeros((1, 2, 64), jnp.int32)
    batch = {"tokens": tok, "labels": tok,
             "resets": jnp.zeros((1, 2, 64), bool)}
    fn = jax.jit(train_step.make_train_step(cfg, run, plan))
    return fn.lower(state, batch).compile().as_text()


def _opcodes(hlo_text):
    out = collections.Counter()
    for line in hlo_text.splitlines():
        m = _OPCODE.search(line)
        if m and scopes._INSTR.match(line):
            out[m.group(1)] += 1
    return out


@pytest.fixture(scope="module")
def compiled():
    return {f: _compile(f) for f in ("gspmd", "manual")}


@pytest.mark.parametrize("flavour", ["gspmd", "manual"])
def test_instruction_scopes_cover_every_layer_and_pass(compiled, flavour):
    found = collections.defaultdict(set)
    for scope, kind in scopes.instruction_scopes(compiled[flavour]).values():
        if scope is not None:
            found[scope].add(kind)
    expected = dict(EXPECTED)
    if flavour == "manual":
        expected["grad_reduce"] = {"fwd"}
        expected["comm.train.grads"] = {"fwd"}
    assert dict(found) == expected


@pytest.mark.parametrize("flavour", ["gspmd", "manual"])
def test_scopes_change_metadata_only(compiled, flavour, monkeypatch):
    def null(name):
        assert name in scopes.SCOPES
        return contextlib.nullcontext()

    for mod in (model, blocks, train_step):
        monkeypatch.setattr(mod, "scope", null)
    monkeypatch.setattr(primitives, "comm_scope",
                        lambda tag, op: contextlib.nullcontext())
    bare = _compile(flavour)
    assert not any(s for s, _ in scopes.instruction_scopes(bare).values())
    counts = _opcodes(compiled[flavour])
    assert counts["fusion"] > 0 and counts == _opcodes(bare)


def test_scope_refuses_unknown_names():
    with pytest.raises(ValueError):
        scopes.scope("mixer.linaer")
    with scopes.scope("mixer.linear"):
        pass


@pytest.mark.parametrize("op_name,expected", [
    ("jit(train_step)/while/body/closed_call/transpose(jvp(layers))/while/"
     "body/closed_call/checkpoint/rematted_computation/mixer.linear/"
     "dot_general", ("mixer.linear", "remat")),
    ("jit(train_step)/while/body/closed_call/transpose(jvp(layers))/while/"
     "body/closed_call/checkpoint/mlp/transpose", ("mlp", "bwd")),
    ("jit(train_step)/while/body/closed_call/jvp(layers)/while/body/"
     "dynamic_update_slice", ("layers", "fwd")),
    ("jit(train_step)/optimizer/is_finite", ("optimizer", "fwd")),
    ("jit(train_step)/while/body/closed_call", (None, "fwd")),
    ("jit(train_step)/shard_map/while/body/closed_call/jvp(layers)/while/"
     "body/closed_call/checkpoint/mixer.softmax/comm.lasp2h.k/all_gather",
     ("comm.lasp2h.k", "fwd")),
    ("jit(train_step)/shard_map/while/body/closed_call/transpose(jvp("
     "layers))/while/body/closed_call/checkpoint/checkpoint/mixer.softmax/"
     "comm.lasp2h.v/reduce_scatter", ("comm.lasp2h.v", "bwd")),
    ("jit(train_step)/shard_map/grad_reduce/comm.train.grads/psum",
     ("comm.train.grads", "fwd")),
    ("jit(train_step)/comm./psum", (None, "fwd")),
])
def test_parse_op_name(op_name, expected):
    assert scopes.parse_op_name(op_name) == expected


def test_hybrid_manual_step_names_its_exchanges():
    """The LASP-2 state all-gather and LASP-2H's K/V all-gathers of a
    1/4 hybrid at dp 1 × sp 4 carry ``comm.<tag>`` in every pass, the
    gradient psum ``comm.train.grads``."""
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "comm_scope_checks.py")],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    found = json.loads(out.stdout.strip().splitlines()[-1])
    for tag in ("comm.lasp2h.k", "comm.lasp2h.v", "comm.lasp2.states"):
        assert found[tag] == ["bwd", "fwd", "remat"], tag
    assert found["comm.train.grads"] == ["fwd"]
    assert found["mixer.softmax"] == ["bwd", "fwd", "remat"]


def test_comm_scopes_leave_the_one_chip_step_unchanged(monkeypatch):
    """``linear-train-8k``'s step on one chip, at the cell's own sizes,
    lowers to the same text with the exchanges' scopes and without them,
    and no location of it names one: it has no exchange."""
    from bench import train as bench_train
    from bench.model import load_json, model_config, run_config
    from bench.weights import _make, program_tree
    from repro.optim import adamw

    c = load_json("bench/configs/qwen1.5-1.8b-linear.json")
    t = load_json("bench/traffic/train-packed-8k.json")
    cfg, run = model_config(c), run_config(c, t["layout"])
    plan = local_plan()

    def build(key):
        params = program_tree(_make(key, c, jnp.float32), c)
        return {"params": params, "opt": adamw.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.eval_shape(build, jax.random.PRNGKey(0))
    batch = jax.eval_shape(lambda: bench_train._feed(t, 1, 0,
                                                     c["vocab_size"]))

    def lowered():
        return jax.jit(train_step.make_train_step(cfg, run, plan)).lower(
            state, batch)

    with_scopes = lowered()
    assert scopes.COMM not in with_scopes.as_text(debug_info=True)
    monkeypatch.setattr(primitives, "comm_scope",
                        lambda tag, op: contextlib.nullcontext())
    assert lowered().as_text() == with_scopes.as_text()


def test_host_watch_counts_gc_and_compiles():
    with HostWatch() as w:
        gc.collect()
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
        d = w.delta()
        assert d["gc_pauses"] >= 1 and d["gc_s"] > 0
        assert d["compiles"] >= 1 and d["compile_s"] > 0
        assert w.delta() == dict.fromkeys(d, 0)
    before = dict(w.counts)
    gc.collect()
    assert w.counts == before, "a closed watch counts nothing"


# ---------------------------------------------------------------------------
# The reduction, on three steps recorded on a TPU v5e
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def reduced():
    r = scoped.reduce(scoped.load(TRACE))
    assert list(r) == ["/device:TPU:0"]
    return r["/device:TPU:0"]


def test_clock_bounds_from_run_ids(reduced):
    lo, hi = reduced["clock_ms"]
    assert lo == pytest.approx(1.371, abs=1e-3)
    assert hi == pytest.approx(1.865, abs=1e-3)
    assert reduced["delta_ms"] == pytest.approx((lo + hi) / 2)
    # the host heard of run 10's end 0.354 ms later than of run 11's
    assert reduced["late_ms"] == pytest.approx(2.219 - 1.865, abs=1e-3)


def test_gaps_between_steps_on_the_device_clock(reduced):
    assert reduced["steps"] == 3 and reduced["module"] == "jit_step"
    (g0, l0), (g1, l1) = reduced["gaps_ms"]
    assert g0 == pytest.approx(51.945, abs=1e-3)
    assert g1 == pytest.approx(51.779, abs=1e-3)
    # both gaps are the recorder's 50 ms host pauses, its longest spans
    host = scoped.load(TRACE)["host"]
    pause = max(host, key=lambda h: h[2] - h[1])[0]
    assert pause in scoped.SPANS and (l0, l1) == (pause, pause)
    assert reduced["step_gap_ms"] == pytest.approx((g0 + g1) / 2)


def test_ops_by_full_instruction_name(reduced):
    assert reduced["op_calls"]["lasp2_chunk_fwd.1"] == 3
    # no scopes given: every op is unscoped, and they fill the step
    assert reduced["scope_ms"] == {}
    assert reduced["unscoped_top"][0][0] == "convert_reduce_fusion"
    assert 0.3 < reduced["unscoped_ms"] <= reduced["step_ms"] < 0.4


def test_scopes_join_by_instruction_name():
    names = {"lasp2_chunk_fwd.1": ("mixer.linear", "remat"),
             "convert_reduce_fusion": ("loss", "fwd")}
    r = scoped.reduce(scoped.load(TRACE), names)["/device:TPU:0"]
    assert r["scope_ms"]["mixer.linear"]["remat"] == \
        pytest.approx(0.1219, abs=2e-4)
    assert r["scope_ms"]["loss"]["fwd"] == pytest.approx(0.2481, abs=2e-4)


def test_crossed_clock_bounds_attribute_nothing():
    data = scoped.load(TRACE)
    data["complete"] = {k: v - 10_000_000 for k, v in
                        data["complete"].items()}
    r = scoped.reduce(data)["/device:TPU:0"]
    lo, hi = r["clock_ms"]
    assert lo > hi and r["delta_ms"] is None
    assert [lab for _, lab in r["gaps_ms"]] == ["unattributed"] * 2


# ---------------------------------------------------------------------------
# The readers
# ---------------------------------------------------------------------------

READERS = {"linear_mixer_ms.train": 80.0, "mlp_ms.train": 100.0,
           "head_loss_ms.train": 30.0, "optimizer_ms.train": 20.0,
           "layer_scan_self_ms.train": 10.0, "step_gap_ms.train": 0.05}


class _Ctx:
    def __init__(self, kind):
        self.traffic = {"kind": kind}


def _reader(name):
    from bench.run import metric_reader
    return metric_reader(name)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_train_and_skips_serve(name):
    read = _reader(name)
    assert read({"ctx": _Ctx("serve")}) is None
    record = {"ctx": _Ctx("train"), "scoped": {
        "has_scopes": True, "step_gap_ms": 0.05,
        "scope_ms": {"mixer.linear": 80.0, "mlp": 100.0, "head": 22.0,
                     "loss": 8.0, "optimizer": 20.0, "layers": 10.0}}}
    assert read(record) == pytest.approx(READERS[name])


def test_scope_readers_read_nothing_from_a_program_without_scopes():
    record = {"ctx": _Ctx("train"), "scoped": {
        "has_scopes": False, "step_gap_ms": 0.05, "scope_ms": {}}}
    assert _reader("mlp_ms.train")(record) is None
    assert _reader("step_gap_ms.train")(record) == pytest.approx(0.05)


# ---------------------------------------------------------------------------
# Per-device readings over several chips (bench/ranks.py) and the readers
# of the hybrid cell
# ---------------------------------------------------------------------------

HLO = """\
  %all-gather-start.1 = (bf16[4,8]{1,0}, bf16[16,8]{1,0}) all-gather-start(bf16[4,8]{1,0} %x), channel_id=1, metadata={op_name="jit(s)/comm.lasp2h.k/all_gather"}
  %all-gather-done.1 = bf16[16,8]{1,0} all-gather-done((bf16[4,8]{1,0}, bf16[16,8]{1,0}) %all-gather-start.1)
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation.3
  %all-reduce-scatter-fusion.1 = f32[2]{0} fusion(f32[8]{0} %b), kind=kOutput, calls=%all-reduce-scatter.2
  %psum.7 = f32[255298]{0} all-reduce(f32[255298]{0} %concatenate.127), channel_id=2
  %dot.4 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p, f32[8,8]{1,0} %q)
"""


def test_collective_instructions_by_opcode_name_and_callee():
    from bench import ranks
    assert ranks.collective_instructions(HLO) == {
        "all-gather-start.1", "all-gather-done.1",
        "all-reduce-scatter-fusion.1", "psum.7"}


def test_exposed_collective_time_is_what_no_other_op_covers():
    from bench import ranks
    dev = {"modules": [("jit_step", 0, 100, 1), ("jit_step", 200, 300, 2)],
           "ops": [("fusion.1", 0, 40), ("all-gather-done.1", 30, 60),
                   ("fusion.2", 60, 100), ("while.1", 0, 100),
                   ("psum.7", 200, 250), ("fusion.3", 220, 300),
                   ("psum.7", 350, 400)]}
    scopes_ = {"all-gather-done.1": ("comm.lasp2h.k", "bwd"),
               "psum.7": ("comm.train.grads", "fwd")}
    r = ranks.exposed(dev, "jit_step", {"all-gather-done.1", "psum.7"},
                      scopes_)
    # 20 ns of each collective are bare; the last psum is outside a step
    assert r["exposed_ns"] == pytest.approx(20.0)
    assert r["collective_ns"] == pytest.approx(40.0)
    assert r["by_label"] == {"comm.lasp2h.k.bwd": pytest.approx(10.0),
                             "comm.train.grads": pytest.approx(10.0)}
    assert ranks.exposed(dev, "jit_other", set(), {}) is None


def _ranks_record(kind="train"):
    # every device's step takes 1000 ms; the one with the longest softmax
    # sets it, and the others wait for it in a collective
    def dev(softmax_ms, exposed_ns):
        return {"scopes": {"busy_ms": 1000.0, "scope_ms": {"mixer.softmax": {
                    "fwd": softmax_ms / 4, "remat": softmax_ms / 4,
                    "bwd": softmax_ms / 2}}},
                "exposed": {"exposed_ns": exposed_ns, "collective_ns": 0.0,
                            "by_label": {"comm.lasp2h.k": exposed_ns}}}
    return {"ctx": _Ctx(kind), "ranks": {"has_scopes": True, "devices": {
        "/device:TPU:0": dev(400.0, 303e6), "/device:TPU:1": dev(700.0, 3e6),
        "/device:TPU:2": dev(500.0, 201e6)}}}


def test_worst_device_readers():
    """The softmax reads its longest device; the exposed collectives read
    the device the others wait for, not the longest wait."""
    from bench import ranks
    assert ranks.critical_device(_ranks_record()["ranks"]) == \
        "/device:TPU:1"
    assert _reader("softmax_mixer_ms.train")(_ranks_record()) == \
        pytest.approx(700.0)
    assert _reader("exposed_collective_ms.train")(_ranks_record()) == \
        pytest.approx(3.0)
    none = {"ctx": _Ctx("serve")}
    for name in ("softmax_mixer_ms.train", "exposed_collective_ms.train"):
        assert _reader(name)(dict(none)) is None


def _hybrid_ctx():
    from types import SimpleNamespace

    from bench.model import load_json
    return SimpleNamespace(
        config=load_json("bench/configs/qwen1.5-1.8b-hybrid4.json"),
        traffic=load_json("bench/traffic/train-sp4-64k.json"), chips=4,
        peaks={"flops": 197e12, "hbm_bw": 819e9})


def test_mfu_seq_counts_the_softmax_layer_at_the_row_length():
    from bench import counts
    ctx = _hybrid_ctx()
    window = {"tokens": 10 * 65536, "window_s": 4.0}
    got = _reader("mfu_seq.train")({"ctx": ctx, "window": window})
    want = 100.0 * counts.train_flops_per_token(ctx.config, 65536) * \
        10 * 65536 / (4.0 * 4 * 197e12)
    assert got == pytest.approx(want)
    # a configuration whose count does not depend on the row reads as
    # mfu.train does
    from bench.model import load_json
    ctx.config = load_json("bench/configs/qwen1.5-1.8b-linear.json")
    assert _reader("mfu_seq.train")({"ctx": ctx, "window": window}) == \
        pytest.approx(_reader("mfu.train")({"ctx": ctx, "window": window}))


def test_flash_roofline_sums_the_ranks_needed_work():
    from bench import counts, kinds
    ctx = _hybrid_ctx()
    softmax = kinds.kind("softmax")
    trace = {"op_calls": {"flash_attention_fwd": 2.0,
                          "flash_attention_bwd_dq": 1.0},
             "op_s": {"flash_attention_fwd": 0.8,
                      "flash_attention_bwd_dq": 0.5}}
    least = 0.0
    for rank in range(4):
        w = softmax.rank_work(ctx.config, ctx.traffic, rank)
        least += 2 * counts.least_time(*w["flash_attention_fwd"],
                                       ctx.peaks)[0]
        least += counts.least_time(*w["flash_attention_bwd_dq"],
                                   ctx.peaks)[0]
    got = _reader("flash_attention_roofline")({"ctx": ctx, "trace": trace})
    assert got == pytest.approx(100.0 * least / (4 * (0.8 + 0.5)))
    assert 0 < got < 100
    assert _reader("flash_attention_roofline")(
        {"ctx": ctx, "trace": {"op_calls": {}, "op_s": {}}}) is None
