"""A tiny cell for the CPU tests: the layout of qwen1.5-1.8b-linear at
test widths, with fp32 compute so that program and reference agree to
rounding; and the faults that can be planted under its timed path."""

import jax
import jax.numpy as jnp

from bench import run as B
from bench.model import load_json

BENCH = {"per_layer": [], "end_to_end": [
    {"name": n, "unit": "x"} for n in (
        "train_tokens_per_s", "serve_tokens_per_s", "ttft_p95_ms",
        "itl_p95_ms", "setup_s")]}
# the tiny cell's own limits: far above fp32 rounding (about 1e-6 here)
LIMITS = {"train": {"limits": {"loss_gap": 1e-4, "grad_gap": 1e-4,
                               "change_gap": 1e-3}},
          "serve": {"limits": {"logit_gap": 1e-3}}}
SP4 = {"layout": {"dp": 1, "sp": 4, "remat": "full"}}


def ctx(kind, seed=2**31 + 11, seconds=2.0, config=None, traffic=None,
        chips=1):
    """``config`` and ``traffic`` replace keys of the tiny files."""
    c = dict(load_json("bench/tests/data/tiny.json"), **(config or {}))
    t = dict(load_json(f"bench/tests/data/tiny-{kind}.json"),
             **(traffic or {}))
    return B.Ctx(f"tiny-{kind}", {"chips": chips}, c, t, seed, seconds,
                 False, chips)


def run(kind, **kw):
    return B.run_cell(ctx(kind, **kw), BENCH, jax.devices(),
                      limits=LIMITS[kind])


def plant(fault, setattr):
    """Break the program's training path underneath the harness, through
    ``setattr`` (``monkeypatch.setattr``):

    ``state_unchanged``  a step that returns its state unchanged;
    ``half_batch``  half of each row's tokens left out of the loss, the
        mean taken over the rest;
    ``no_exchange``  the sequence-parallel state exchange left out: each
        chip gathers its own state and zeros for the others'.
    """
    if fault == "no_exchange":
        from repro.comm import primitives
        real_gather = primitives.allgather_states

        def own_only(x, axis, *, axis_size, gather_axis=0, tiled=False,
                     tag=""):
            if tiled:
                return real_gather(x, axis, axis_size=axis_size,
                                   gather_axis=gather_axis, tiled=tiled,
                                   tag=tag)
            me = jax.lax.axis_index(axis)
            return jnp.stack([jnp.where(me == i, x, jnp.zeros_like(x))
                              for i in range(axis_size)], gather_axis)
        setattr(primitives, "allgather_states", own_only)
        return

    from repro.train import step as program_step
    real = program_step.make_train_step

    def make(cfg, run_, plan):
        step = real(cfg, run_, plan)

        def broken(state, batch):
            if fault == "half_batch":
                lab = batch["labels"]
                half = jnp.arange(lab.shape[-1]) >= lab.shape[-1] // 2
                batch = dict(batch, labels=jnp.where(half, -1, lab))
                return step(state, batch)
            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics
        return broken
    setattr(program_step, "make_train_step", make)
