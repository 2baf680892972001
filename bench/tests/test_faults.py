"""A run with the timed path broken underneath comes out not correct.
Each test drives the whole harness on the CPU at a tiny size (the look
for a chip skipped) with one fault planted in the program, and the
float8 control is read against the reference as the cell reads it."""

import functools

import jax.numpy as jnp
import pytest

from bench import correct, serve, train
from bench.reference import train as ref_train
from bench.tests import harness
from bench.weights import make_weights


@pytest.fixture
def broken_step(monkeypatch):
    return lambda fault: harness.plant(fault, monkeypatch.setattr)


def test_state_left_unchanged_is_caught(broken_step):
    broken_step("state_unchanged")
    r = harness.run("train")
    assert not r["correct"]
    assert r["check"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_caught(broken_step):
    broken_step("half_batch")
    r = harness.run("train")
    assert not r["correct"]
    assert r["check"]["loss_gap"]["value"] > harness.LIMITS["train"][
        "limits"]["loss_gap"]


def test_a_token_altered_where_produced_is_caught(monkeypatch):
    from repro.serve import scheduler
    real = scheduler.Request.record

    def record(self, tok):
        if len(self.tokens) == 2:
            tok = (tok + 1) % 500
        return real(self, tok)
    monkeypatch.setattr(scheduler.Request, "record", record)
    r = harness.run("serve")
    assert not r["correct"]
    assert r["check"]["logit_gap"]["value"] > 1e-3


def test_a_decode_step_that_keeps_its_state_is_caught(monkeypatch):
    from repro.models import model as M
    real = M.decode_step

    def decode_step(params, token, cache, cfg, plan=None, **kw):
        logits, new = real(params, token, cache, cfg, plan, **kw)
        return logits, dict(cache, pos=new["pos"])
    monkeypatch.setattr(M, "decode_step", decode_step)
    r = harness.run("serve")
    assert not r["correct"]
    assert r["check"]["logit_gap"]["value"] > 1e-3


def test_float8_control_fails_the_training_limits():
    ctx = harness.ctx("train")
    c = ctx.config
    wf = functools.partial(make_weights, ctx.seed, c, jnp.float32)
    batches = train._ref_batches(ctx.traffic, ctx.seed, c["vocab_size"])

    def ref(precision):
        losses, grad, change = ref_train.run(wf, batches, c, precision)
        return {"losses": losses, "grad": grad, "change": change}

    readings = correct.train_readings(ref("fp8"), ref("fp32"))
    ok, check = correct.judge(readings, harness.LIMITS["train"])
    assert not ok
    assert check["loss_gap"]["value"] > \
        harness.LIMITS["train"]["limits"]["loss_gap"]


def test_float8_control_fails_the_serving_limit():
    ctx = harness.ctx("serve")
    s = serve.setup(ctx)
    w = serve.window(ctx, s)
    s.clear()
    picked = serve.sample(ctx, w)
    g = serve.reference_gaps(ctx, picked, control=True)
    assert g["tokens"] > 0
    assert g["widest"] > harness.LIMITS["serve"]["limits"]["logit_gap"]
