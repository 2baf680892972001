"""Serving driver: one cell's set-up, open-loop window and check.

Set-up makes the weights on the device from the seed in the served type,
builds the program's ``ServeEngine`` with the cell's slots, and warms up
every shape the schedule will use: one prefill per prompt-length bucket
(one request at a time), the decode step over all slots, and sampling.

The window is an open loop: requests fall due on the seeded schedule
whether or not earlier ones finished. A due request waits in the
client's queue until the engine has a free slot and nothing waiting, and
the client then submits it; the engine's ``step()`` admits it, prefills
it, and decodes every active slot. So each admission prefills one
request, and no shape compiles in the window. Every output token is
stamped with the host clock when ``step()`` returns it. Time to first
token is taken from when the request was due.

After the window closes, the requests that fell due in it are served to
the end (a minute at most) so that every one has its first token and a
sampled few can be checked against the reference.
"""

from __future__ import annotations

import collections
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from bench import loadgen
from bench.model import model_config
from bench.reference import model as R
from bench.stats import percentile
from bench.weights import make_weights, program_tree

DRAIN_S = 60.0
CHECK_REQUESTS = 6


def setup(ctx, rate=None):
    from repro.serve.engine import ServeEngine

    c, t = ctx.config, ctx.traffic
    w = make_weights(ctx.seed, c, jnp.dtype(c["serve_param_dtype"]))
    engine = ServeEngine(model_config(c), program_tree(w, c),
                         max_len=loadgen.max_context(t),
                         max_batch=t["engine"]["slots"])
    del w
    # one request per prompt bucket, alone: compiles prefill (1, bucket),
    # the slot insert, sampling and the decode over every slot
    from repro.serve.scheduler import bucket_length
    buckets = sorted({min(bucket_length(n), engine.max_len)
                      for n in loadgen.prompt_buckets(t, ctx.seconds, rate)})
    for b in buckets:
        engine.submit(np.zeros((b,), np.int32), 2)
        engine.run()
    engine.reset_metrics()
    return {"engine": engine, "buckets": buckets}


def window(ctx, s, rate=None):
    c, t = ctx.config, ctx.traffic
    engine = s["engine"]
    sched = engine.sched
    reqs = loadgen.serve_schedule(t, ctx.seed, ctx.seconds, c["vocab_size"],
                                  rate)
    pending = collections.deque(reqs)
    queue = collections.deque()
    live = {}                       # uid -> (Request, program's request)
    t0 = time.perf_counter()
    close = ctx.seconds
    backlog_mid = None

    def stamp(now):
        for uid, (r, pr) in list(live.items()):
            k = len(pr.tokens)
            if k > len(r.tokens):
                r.times.extend([now] * (k - len(r.tokens)))
                r.tokens.extend(int(x) for x in pr.tokens[len(r.tokens):])
            if pr.done:
                del live[uid]

    in_window = span("window")
    in_window.__enter__()
    while True:
        now = time.perf_counter() - t0
        if in_window is not None and now >= close:
            in_window.__exit__(None, None, None)
            in_window = None
        if now >= close + DRAIN_S:
            break
        if now >= close and not pending and not queue and not live:
            break
        if backlog_mid is None and now >= close / 2:
            backlog_mid = len(queue) + len(sched.waiting)
        while pending and pending[0].due <= now:
            r = pending.popleft()
            r.noticed = now
            queue.append(r)
        if queue and not sched.waiting and sched.free_slots():
            r = queue.popleft()
            with span("submit"):
                r.uid = engine.submit(r.prompt, r.max_new)
            r.submitted = time.perf_counter() - t0
            live[r.uid] = (r, sched.waiting[-1])
        if sched.has_work():
            with span("step"):
                engine.step()
            with span("record"):
                stamp(time.perf_counter() - t0)
        elif pending:
            with span("wait"):
                until = pending[0].due if now >= close \
                    else min(pending[0].due, close)
                time.sleep(max(0.0, until - now))
        if now < close:
            s["backlog_end"] = len(queue) + len(sched.waiting)
    if in_window is not None:
        in_window.__exit__(None, None, None)
    return _summary(ctx, reqs, close, backlog_mid, s.get("backlog_end", 0))


def _summary(ctx, reqs, close, backlog_mid, backlog_end):
    due = [r for r in reqs if r.due < close]
    ttft = [r.times[0] - r.due for r in due if r.times]
    gaps = [b - a for r in reqs for a, b in zip(r.times, r.times[1:])
            if b <= close]
    out_tokens = sum(1 for r in reqs for x in r.times if x <= close)
    prompt_tokens = sum(len(r.prompt) for r in reqs
                        if not math.isnan(r.submitted)
                        and r.submitted <= close)
    noticed = [r.noticed - r.due for r in due if not math.isnan(r.noticed)]
    submitted = [r.submitted - r.due for r in due
                 if not math.isnan(r.submitted)]
    failed = sum(1 for r in due if len(r.tokens) < r.max_new)
    return {"window_s": close, "requests": reqs, "ttft": ttft, "gaps": gaps,
            "out_tokens": out_tokens, "prompt_tokens": prompt_tokens,
            "attempted": len(due), "failed": failed,
            "late_noticed": noticed, "late_submitted": submitted,
            "backlog_mid": backlog_mid, "backlog_end": backlog_end}


def end_to_end(ctx, w):
    return {"serve_tokens_per_s": w["out_tokens"] / w["window_s"],
            "ttft_p95_ms": 1e3 * percentile(w["ttft"], 95),
            "itl_p95_ms": 1e3 * percentile(w["gaps"], 95)}


def lateness_line(w) -> str:
    def q(xs):
        if not xs:
            return "none"
        return (f"p50 {1e3 * percentile(xs, 50):.3f} ms, p95 "
                f"{1e3 * percentile(xs, 95):.3f} ms, max "
                f"{1e3 * max(xs):.3f} ms")
    return (f"generator lateness over {len(w['late_noticed'])} requests: "
            f"seen due {q(w['late_noticed'])}; submitted "
            f"{q(w['late_submitted'])}; backlog mid-window "
            f"{w['backlog_mid']}, at close {w['backlog_end']}")


def sample(ctx, w):
    """The requests to check: the longest finished one and a seeded draw
    of the others."""
    done = [r for r in w["requests"] if r.due < w["window_s"]
            and len(r.tokens) == r.max_new]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([ctx.seed, 1])
    k = min(CHECK_REQUESTS - 1, len(rest))
    pick = [rest[i] for i in sorted(rng.choice(len(rest), k, replace=False))]
    return [longest] + pick


def reference_gaps(ctx, picked, control=False):
    """Gaps, over every served token of the picked requests, by which the
    reference's logit of that token lies below its best: the widest, the
    mean, the share of tokens that are not the reference's best, and the
    count. With ``control`` the token is instead the one the float8
    reference puts first at each position."""
    c = ctx.config
    length = loadgen.max_context(ctx.traffic)
    w = make_weights(ctx.seed, c, jnp.dtype(c["serve_param_dtype"]))

    @jax.jit
    def gaps(w, tokens, read, served, valid):
        with jax.default_matmul_precision("highest"):
            pos = jnp.arange(tokens.shape[0])
            seg = jnp.zeros_like(tokens)
            lg = R.logits(w, R.hidden(w, tokens, pos, seg, c)[read], c)
            if control:
                lc = R.logits(w, R.hidden(w, tokens, pos, seg, c, "fp8")
                              [read], c, "fp8")
                served = jnp.argmax(lc, axis=-1)
        g = jnp.max(lg, -1) - jnp.take_along_axis(lg, served[:, None], -1)[
            :, 0]
        g = jnp.where(valid, g, 0.0)
        return jnp.max(g), jnp.sum(g), jnp.sum(g > 0)

    widest, total, off, n_tokens = 0.0, 0.0, 0, 0
    for r in picked:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        tokens = np.zeros((length,), np.int32)
        tokens[:len(seq)] = seq
        n = len(r.tokens)
        read = np.zeros((length,), np.int32)
        read[:n] = np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + n)
        served = np.zeros((length,), np.int32)
        served[:n] = r.tokens
        valid = np.arange(length) < n
        g_max, g_sum, g_off = gaps(w, tokens, read, served, valid)
        widest = max(widest, float(g_max))
        total, off, n_tokens = total + float(g_sum), off + int(g_off), \
            n_tokens + n
    return {"widest": widest, "mean": total / max(n_tokens, 1),
            "off_share": off / max(n_tokens, 1), "tokens": n_tokens}


def check(ctx, s, w):
    s.clear()
    picked = sample(ctx, w)
    if not picked:
        return {"logit_gap": math.inf}, {"tokens_checked": 0}
    g = reference_gaps(ctx, picked)
    return {"logit_gap": g["widest"]}, {**g, "requests_checked": len(picked)}


def decode_bh(ctx):
    return ctx.traffic["engine"]["slots"] * ctx.config["num_attention_heads"]
