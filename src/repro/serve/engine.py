"""Constant-memory serving engine with continuous batching.

The decode cache holds, per linear/SSM layer, only the fp32 ``dk × dv``
recurrent state plus its cumulative log decay — the paper's
constant-memory-inference property, O(1) in context length — and, per
softmax layer of a LASP-2H hybrid, a ring-buffer KV cache whose length is
the layer's sliding window (also O(1) for windowed layers). Prefill reuses
the chunked scan (Pallas ``lasp2_chunk`` kernel on TPU) and lands the final
per-layer states directly in the cache; decode advances every sequence by
one ``recurrent_step`` — the prefix is never re-scanned.

Scheduling is continuous: a fixed grid of ``max_batch`` decode slots,
with per-step admission of waiting requests (batched prefill, grouped by
bucketed prompt length) and per-step eviction of finished ones
(:mod:`repro.serve.scheduler`). Per-request RNG streams make sampled
output independent of how requests were batched together.

API::

    engine = ServeEngine(cfg, params, max_len=2048, max_batch=8)
    uid = engine.submit([1, 2, 3], max_new_tokens=32, temperature=0.8)
    results = engine.run()          # {uid: np.ndarray of generated tokens}

    # or the one-shot batch form (ragged prompts welcome):
    outs = engine.generate(prompts, max_new_tokens=32)
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.obs.metrics import Metrics, as_sink
from repro.serve.scheduler import ContinuousScheduler, PrefillBatch, Request
from repro.sharding.rules import Parallelism, local_plan


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *,
                 plan: Optional[Parallelism] = None, max_len: int = 2048,
                 max_batch: int = 8, bucket_lengths: Optional[bool] = None,
                 sink=None, max_queue: Optional[int] = None,
                 finished_timeout: Optional[float] = None):
        self.cfg = cfg
        self.params = params
        self.plan = plan or local_plan()
        self.max_len = max_len
        self.max_batch = max_batch
        # Telemetry (docs/observability.md): one Metrics registry shared
        # with the scheduler; per-request records go to ``sink`` as each
        # request finishes. All host-side — no device ops are added.
        self.sink = as_sink(sink)
        self.metrics = Metrics()
        self._submit_t: Dict[int, float] = {}
        self._ttft: Dict[int, float] = {}
        # Length bucketing left-pads prompts, which is only exact for pure
        # recurrent stacks; hybrids fall back to exact-length groups.
        self.bucket_lengths = M.pad_safe(cfg) if bucket_lengths is None \
            else bucket_lengths
        # Degradation knobs (docs/resilience.md): bounded admission
        # queue (submit raises QueueFullError when full) and eviction of
        # uncollected finished results.
        self.sched = ContinuousScheduler(max_batch, max_len,
                                         bucket_lengths=self.bucket_lengths,
                                         metrics=self.metrics,
                                         max_queue=max_queue,
                                         finished_timeout=finished_timeout)

        self._cache = M.init_cache(cfg, max_batch, max_len)
        self._tok = np.zeros((max_batch,), np.int32)
        self._temps = np.zeros((max_batch,), np.float32)
        self._keys = np.zeros((max_batch, 2), np.uint32)

        def _prefill(params_, tokens, pad_lens):
            return M.prefill(params_, tokens, cfg, self.plan,
                             max_len=max_len, pad_lens=pad_lens)

        def _prefill_exact(params_, tokens):
            return M.prefill(params_, tokens, cfg, self.plan,
                             max_len=max_len)

        def _decode(params_, tok, cache):
            return M.decode_step(params_, tok, cache, cfg, self.plan)

        def _insert(cache, small, slots):
            layers = jax.tree.map(
                lambda b, s: b.at[:, slots].set(s.astype(b.dtype),
                                                mode="drop"),
                cache["layers"], small["layers"])
            pos = cache["pos"].at[slots].set(small["pos"], mode="drop")
            return {"layers": layers, "pos": pos}

        def _sample(logits, temps, base_keys, steps):
            def one(lg, t, k, s):
                kk = jax.random.fold_in(k, s)
                g = jax.random.categorical(kk, lg / jnp.maximum(t, 1e-6))
                return jnp.where(t <= 0.0,
                                 jnp.argmax(lg, -1), g).astype(jnp.int32)
            return jax.vmap(one)(logits, temps, base_keys, steps)

        def _prefill_static(params_, tokens, img_emb, enc_frames):
            return M.prefill(params_, tokens, cfg, self.plan,
                             max_len=max_len, img_emb=img_emb,
                             enc_frames=enc_frames)

        def _decode_static(params_, tok, cache, img_emb, enc_out):
            return M.decode_step(params_, tok, cache, cfg, self.plan,
                                 img_emb=img_emb, enc_out=enc_out)

        self._prefill = jax.jit(_prefill)
        self._prefill_exact = jax.jit(_prefill_exact)
        self._decode = jax.jit(_decode, donate_argnums=(2,))
        self._insert = jax.jit(_insert, donate_argnums=(0,))
        self._sample = jax.jit(_sample)
        # static-batch (encoder / image) path: jitted once, reused across
        # generate() calls
        self._prefill_static = jax.jit(_prefill_static)
        self._decode_static = jax.jit(_decode_static, donate_argnums=(2,))
        self._encode = jax.jit(
            lambda p, f: M.encode(p, f, cfg, self.plan)) \
            if cfg.encoder is not None else None

        for kind, nbytes in self.cache_stats().items():
            if not kind.endswith("_arrays"):
                self.metrics.gauge(f"cache_bytes_{kind}", nbytes)

    # -- request API --------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               seed: int = 0, stream: int = 0,
               deadline_s: Optional[float] = None) -> int:
        """Queue one request; returns its uid. Work happens in step().

        ``(seed, stream)`` names the request's RNG stream — sampling is
        deterministic in it, independent of how requests get batched.
        ``deadline_s``: evict the request (``finish_reason="deadline"``,
        partial tokens kept) if it hasn't finished this many seconds
        after submission. Raises
        :class:`repro.serve.scheduler.QueueFullError` when the bounded
        admission queue is full."""
        uid = self.sched.submit(prompt, max_new_tokens,
                                temperature=temperature, eos_id=eos_id,
                                seed=seed, stream=stream,
                                deadline_s=deadline_s)
        self._submit_t[uid] = time.perf_counter()
        return uid

    def step(self) -> List[Request]:
        """One scheduler tick: admit + prefill waiting requests into free
        slots, decode all active slots by one token. Returns the requests
        that finished this tick."""
        finished: List[Request] = list(self.sched.expire())
        for batch in self.sched.admit():
            finished += self._admit(batch)
        if self.sched.active:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("decode"):
                logits, self._cache = self._decode(
                    self.params, jnp.asarray(self._tok), self._cache)
                steps = np.array([len(r.tokens) if r is not None else 0
                                  for r in self.sched.slots], np.int32)
                tok = np.asarray(self._sample(
                    logits, jnp.asarray(self._temps),
                    jnp.asarray(self._keys), jnp.asarray(steps)))
            active = [i for i, r in enumerate(self.sched.slots)
                      if r is not None]
            # np.asarray above blocked on the device, so the wall is fenced
            self.metrics.observe("decode_step_s", time.perf_counter() - t0)
            self.metrics.inc("decode_steps")
            self.metrics.inc("decode_tokens", len(active))
            self._tok[active] = tok[active]
            finished += self.sched.record_step(tok)
        n_active = len(self.sched.active)
        self.metrics.gauge("active_slots", n_active)
        self.metrics.gauge("cache_occupancy", n_active / self.max_batch)
        for r in finished:
            self._finish(r)
        return finished

    def run(self) -> Dict[int, np.ndarray]:
        """Drive step() until all submitted requests finished; returns
        {uid: generated tokens}."""
        done: List[Request] = []
        while self.sched.has_work():
            done += self.step()
        return {r.uid: np.asarray(r.tokens, np.int32) for r in done}

    def _admit(self, batch: PrefillBatch) -> List[Request]:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("prefill"):
            if self.bucket_lengths:
                logits, small = self._prefill(
                    self.params, jnp.asarray(batch.prompts),
                    jnp.asarray(batch.pad_lens))
            else:
                logits, small = self._prefill_exact(
                    self.params, jnp.asarray(batch.prompts))
            slots = jnp.asarray(batch.slots)
            self._cache = self._insert(self._cache, small, slots)
            temps = np.array([r.temperature for r in batch.requests],
                             np.float32)
            keys = np.stack([
                np.asarray(jax.random.fold_in(jax.random.PRNGKey(r.seed),
                                              r.stream), np.uint32)
                for r in batch.requests])
            tok = np.asarray(self._sample(
                logits, jnp.asarray(temps), jnp.asarray(keys),
                jnp.zeros((len(batch.requests),), jnp.int32)))
        now = time.perf_counter()
        self.metrics.observe("prefill_s", now - t0)
        self.metrics.inc("prefill_batches")
        self.metrics.inc("prefill_tokens", int(batch.prompts.size))
        for j, r in enumerate(batch.requests):
            self._tok[r.slot] = tok[j]
            self._temps[r.slot] = r.temperature
            self._keys[r.slot] = keys[j]
            # TTFT: submit() → the request's first token, which is sampled
            # right here from the prefill logits (not from the first
            # decode step)
            self._ttft[r.uid] = now - self._submit_t.get(r.uid, t0)
            self.metrics.observe("ttft_s", self._ttft[r.uid])
        return self.sched.record_prefill(batch, tok)

    def _finish(self, req: Request) -> None:
        """Emit the per-request telemetry record (kind="request")."""
        now = time.perf_counter()
        rec: Dict[str, Any] = {
            "kind": "request", "uid": req.uid,
            "prompt_len": req.prompt_len, "new_tokens": len(req.tokens),
            "finish_reason": req.finish_reason,
            "wall_s": now - self._submit_t.pop(req.uid, now),
        }
        ttft = self._ttft.pop(req.uid, None)
        if ttft is not None:
            rec["ttft_s"] = ttft
        self.sink.emit(rec)

    # -- one-shot batch API (back-compat) -----------------------------------

    def generate(self, prompts, max_new_tokens: int, *, temperature=0.0,
                 seed: int = 0, img_emb=None, enc_frames=None,
                 eos_id: Optional[int] = None):
        """prompts: (B, S) int32 (or a ragged list of 1-D prompts).
        Returns (B, max_new_tokens) int32; rows that stop early at EOS are
        padded by repeating their final token."""
        if img_emb is not None or enc_frames is not None:
            return self._generate_static(prompts, max_new_tokens,
                                         temperature=temperature, seed=seed,
                                         img_emb=img_emb,
                                         enc_frames=enc_frames,
                                         eos_id=eos_id)
        assert not self.sched.has_work(), \
            "generate() needs an idle engine; use submit()/run() to mix"
        prompts = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        uids = [self.submit(p, max_new_tokens, temperature=temperature,
                            eos_id=eos_id, seed=seed, stream=i)
                for i, p in enumerate(prompts)]
        results = self.run()
        out = np.zeros((len(uids), max_new_tokens), np.int32)
        for i, uid in enumerate(uids):
            t = results[uid]
            out[i, :len(t)] = t
            if len(t) < max_new_tokens:      # early EOS: repeat last token
                out[i, len(t):] = t[-1]
        return out

    def _generate_static(self, prompts, max_new_tokens, *, temperature,
                         seed, img_emb, enc_frames, eos_id):
        """Static-batch path for encoder / image-conditioned models (the
        per-request aux inputs don't continuously batch)."""
        prompts = jnp.asarray(prompts, jnp.int32)
        b, s = prompts.shape
        if s + max_new_tokens > self.max_len:
            raise ValueError("max_len too small")
        enc_out = None
        if enc_frames is not None and self._encode is not None:
            enc_out = self._encode(self.params, enc_frames)
        logits, cache = self._prefill_static(self.params, prompts, img_emb,
                                             enc_frames)
        key = jax.random.PRNGKey(seed)
        out = []
        done = np.zeros((b,), bool)
        tok = self._sample_static(logits, temperature, key)
        for i in range(max_new_tokens):
            out.append(np.asarray(tok))
            if eos_id is not None:
                done |= (out[-1] == eos_id)
                if done.all():
                    out.extend([out[-1]] * (max_new_tokens - i - 1))
                    break
            logits, cache = self._decode_static(self.params, tok, cache,
                                                img_emb, enc_out)
            key, sub = jax.random.split(key)
            tok = self._sample_static(logits, temperature, sub)
        return np.stack(out[:max_new_tokens], axis=1)

    @staticmethod
    def _sample_static(logits, temperature, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature, axis=-1).astype(jnp.int32)

    # -- introspection ------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Flat snapshot of the engine+scheduler telemetry: counters
        (submitted/admitted/evicted/…), gauges (queue_depth,
        cache_occupancy + peaks), and latency histogram summaries
        (``decode_step_s_p50`` … ``ttft_s_p99``), plus the derived
        steady-state decode throughput."""
        out = self.metrics.snapshot()
        dec = self.metrics.histograms.get("decode_step_s")
        if dec is not None and dec.total:
            out["decode_tokens_per_s"] = \
                self.metrics.counters.get("decode_tokens", 0) / dec.total
        return out

    def reset_metrics(self) -> None:
        """Drop accumulated telemetry (e.g. after a compile-warmup pass,
        so percentiles reflect the warm path); the fresh registry is
        re-shared with the scheduler and the static cache gauges
        re-seeded."""
        self.metrics = self.sched.metrics = Metrics()
        for kind, nbytes in self.cache_stats().items():
            if not kind.endswith("_arrays"):
                self.metrics.gauge(f"cache_bytes_{kind}", nbytes)

    def emit_summary(self, **extra) -> Dict[str, Any]:
        """Emit (and return) the run-level ``summary`` record through the
        sink — the serve-side analogue of the train flight recorder's
        summary."""
        rec: Dict[str, Any] = {"kind": "summary", "component": "serve"}
        rec.update(self.stats())
        rec.update(extra)
        self.sink.emit(rec)
        return rec

    def cache_stats(self) -> Dict[str, int]:
        """Decode-cache footprint by kind — byte-accurate totals plus the
        array count per kind (``<kind>_arrays``). ``linear_state`` (+ its
        log decays) is constant in both context length and max_len — the
        paper's claim; ``kv_ring`` scales with the softmax layers' window,
        not the context. Exact expectations (asserted in the serve tests):
        per linear layer ``B·H·(dk·dv + 1)·4`` bytes (fp32 state + log
        decay), per softmax layer ``2·B·n_kv·ring·head_dim·2`` (bf16 K/V)
        ``+ B·ring·4`` (int32 positions)."""
        stats = {"linear_state": 0, "kv_ring": 0, "conv": 0, "other": 0}
        arrays = dict.fromkeys(stats, 0)

        def visit(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            if name in ("m", "log_decay"):
                kind = "linear_state"
            elif name in ("k", "v", "kpos"):
                kind = "kv_ring"
            elif name.startswith("conv_"):
                kind = "conv"
            else:
                kind = "other"
            stats[kind] += leaf.nbytes
            arrays[kind] += 1
            return leaf

        jax.tree_util.tree_map_with_path(visit, self._cache["layers"])
        stats["total"] = sum(stats.values())
        stats.update({f"{k}_arrays": n for k, n in arrays.items()})
        return stats
