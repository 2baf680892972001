"""Layer scopes of the train step (repro.obs.scopes), the host watch
(repro.obs.HostWatch), and the benchmark's per-layer reduction of a
device trace (bench/scoped.py) on the committed v5e trace."""

import collections
import contextlib
import gc
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

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
    assert dict(found) == expected


@pytest.mark.parametrize("flavour", ["gspmd", "manual"])
def test_scopes_change_metadata_only(compiled, flavour, monkeypatch):
    def null(name):
        assert name in scopes.SCOPES
        return contextlib.nullcontext()

    for mod in (model, blocks, train_step):
        monkeypatch.setattr(mod, "scope", null)
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
])
def test_parse_op_name(op_name, expected):
    assert scopes.parse_op_name(op_name) == expected


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
