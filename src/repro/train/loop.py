"""Fault-tolerant training loop.

* auto-resume from the latest checkpoint (determinism: batch(step) is a
  pure function, so resumed runs are bitwise-identical),
* periodic async checkpointing (atomic; crash-safe),
* step watchdog: wall-time per step is tracked, slow steps logged — the
  single-host analogue of straggler detection; on a real cluster the same
  hook triggers the coordinator's unhealthy-host path,
* non-finite gradient steps are skipped inside the jitted step,
* SIGTERM/KeyboardInterrupt → final checkpoint, clean exit (preemption),
* optional telemetry (``sink=``, docs/observability.md): per-step phase
  walls / tokens-per-s / MFU records, the GC pauses and compiles of each
  step (``repro.obs.HostWatch``), plus a compile-time flight-recorder
  snapshot of the comm tape vs the compiled HLO. With ``sink=None`` the
  loop runs the exact uninstrumented path — no tape, no AOT lowering, no
  extra host work per step.
* each step is a ``StepTraceAnnotation("train")`` in a profiler trace,
  its phases host spans ``data``, ``step`` and ``ckpt``.
"""

from __future__ import annotations

import signal
import time
from contextlib import ExitStack, nullcontext
from typing import Callable, Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointError, CheckpointManager
from repro.configs.base import ModelConfig, RunConfig
from repro.data.pipeline import SyntheticLM
from repro.obs.host import HostWatch
from repro.resilience.guard import GuardAbort
from repro.sharding.rules import Parallelism
from repro.train.step import init_state, make_train_step


class StepWatchdog:
    """Tracks step durations; flags stragglers (> factor × median).

    The first ``warmup`` recorded durations are compile/resume spikes
    (the step wall includes trace+compile time): they are never flagged
    and never enter the rolling window, so a one-off 100× outlier can't
    poison the median every subsequent step is judged against.
    """

    def __init__(self, factor: float = 3.0, window: int = 50,
                 warmup: int = 1):
        self.times, self.factor, self.window = [], factor, window
        self.warmup = warmup
        self.seen = 0
        self.slow_steps = 0

    def record(self, dt: float) -> bool:
        self.seen += 1
        if self.seen <= self.warmup:
            return False
        self.times.append(dt)
        self.times = self.times[-self.window:]
        med = float(np.median(self.times))
        slow = len(self.times) >= 10 and dt > self.factor * med
        self.slow_steps += int(slow)
        return slow


def train(cfg: ModelConfig, run: RunConfig, data: SyntheticLM, *,
          plan: Optional[Parallelism] = None, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, log_every: int = 10,
          log_fn: Callable[[str], None] = print, max_steps=None,
          sink=None):
    """Returns (final_state, history list of metric dicts).

    ``sink``: optional :class:`repro.obs.MetricsSink`. When set, the loop
    (a) traces the step under the ``repro.comm`` tape and compiles it
    ahead-of-time ONCE (the AOT result is also the HLO the flight
    recorder cross-validates the tape against — no second compile),
    (b) emits one ``step`` record per step with phase walls
    (data/step/ckpt), tokens/s, MFU, expected-vs-compiled collective
    bytes and the step's GC pauses and compiles, and (c) turns
    resume/straggler/signal prints into structured ``event`` records.
    The caller owns the sink's lifetime.
    """
    # single-device default still honours the kernel-backend knob
    plan = plan or Parallelism(backend=run.kernel_backend)
    key = jax.random.PRNGKey(run.seed)
    state = init_state(key, cfg, run, plan)
    start_step = 0

    recorder = None
    timer = None
    if sink is not None:
        from repro.configs.base import ShapeConfig
        from repro.launch.hlo_analysis import model_flops
        from repro.obs import FlightRecorder, PhaseTimer, render_step
        n_devices = plan.mesh.size if plan.mesh is not None else 1
        shape = ShapeConfig("train-run", data.seq_len, data.global_batch,
                            "train")
        recorder = FlightRecorder(sink,
                                  model_flops_per_step=model_flops(cfg,
                                                                   shape),
                                  n_devices=n_devices)
        timer = PhaseTimer()
    phase = timer.phase if timer is not None else (lambda _n: nullcontext())
    tokens_per_step = data.global_batch * data.seq_len

    mgr = CheckpointManager(ckpt_dir, verify=run.ckpt_verify) \
        if ckpt_dir else None
    if mgr is not None:
        latest = mgr.latest_step()
        if latest is not None:
            try:
                state = mgr.restore(latest, state)
                start_step = latest
            except (CheckpointError, ValueError) as e:
                # corrupt/unreadable latest: fall back to the newest
                # checkpoint that verifies (docs/resilience.md)
                log_fn(f"[resume] checkpoint step {latest} invalid "
                       f"({type(e).__name__}); falling back")
                start_step, state, rejected = \
                    mgr.restore_latest_valid(state)
                log_fn(f"[resume] fell back to step {start_step} "
                       f"(rejected {[s for s, _ in rejected]})")
                if recorder is not None:
                    recorder.event("ckpt_fallback", bad_step=latest,
                                   restored_step=start_step,
                                   rejected=[s for s, _ in rejected],
                                   error=type(e).__name__)
            log_fn(f"[resume] restored step {start_step} from {ckpt_dir}")
            if recorder is not None:
                recorder.event("resume", step=start_step,
                               ckpt_dir=ckpt_dir)

    jitted = jax.jit(make_train_step(cfg, run, plan), donate_argnums=(0,))
    if recorder is None:
        step_fn = jitted
    else:
        # One shared compile: trace under the comm tape (the "expected"
        # collective view), compile ahead-of-time, and run the compiled
        # program directly — AOT results don't populate the jit cache,
        # so calling ``jitted`` afterwards would compile a second time.
        from repro.comm import tape
        t_c0 = time.perf_counter()
        with tape() as records:
            lowered = jitted.lower(
                state, data.microbatched(start_step, run.num_microbatches))
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t_c0
        recorder.on_compile(records=records, hlo_text=compiled.as_text(),
                            total_devices=recorder.n_devices,
                            note=f"{cfg.name} train step")
        recorder.event("compile", step=start_step, seconds=compile_s)
        step_fn = compiled

    watchdog = StepWatchdog()
    history = []
    skipped_total = 0
    total = max_steps if max_steps is not None else run.total_steps

    stop = {"now": False}

    def _sig(_sig, _frm):
        stop["now"] = True

    old_handler = signal.signal(signal.SIGTERM, _sig)
    hooks = ExitStack()
    try:
        watch = hooks.enter_context(HostWatch()) \
            if recorder is not None else None
        for step in range(start_step, total):
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                with phase("data"):
                    batch = data.microbatched(step, run.num_microbatches)
                t0 = time.perf_counter()
                with phase("step") as f:
                    state, metrics = step_fn(state, batch)
                    if f is not None:
                        f.set(metrics)
                    metrics = {k: float(v) for k, v in metrics.items()}
                dt = time.perf_counter() - t0
                slow = watchdog.record(dt)
                with phase("ckpt"):
                    if mgr is not None and (step + 1) % ckpt_every == 0:
                        mgr.save_async(step + 1, state)
            rec = None
            if recorder is not None:
                rec = recorder.on_step(step, dt, tokens=tokens_per_step,
                                       phases=timer.flush(),
                                       metrics=metrics, straggler=slow,
                                       host=watch.delta())
            metrics["step"], metrics["dt"] = step, dt
            history.append(metrics)
            skipped_total += int(metrics.get("skipped", 0))
            if metrics.get("skipped"):
                consec = int(metrics.get("consecutive_skips", 0))
                log_fn(f"[guard] step {step} skipped (non-finite update; "
                       f"consecutive {max(consec, 1)})")
                if recorder is not None:
                    recorder.event("guard_skip", step=step,
                                   consecutive=consec,
                                   total=skipped_total)
                if run.guard and \
                        consec >= run.guard_max_consecutive_skips:
                    # params are clean — skips never applied an update —
                    # so the finally-block checkpoint is safe to resume
                    # from once the cause is fixed.
                    if recorder is not None:
                        recorder.event("guard_abort", step=step,
                                       consecutive=consec)
                    raise GuardAbort(
                        f"{consec} consecutive skipped steps at step "
                        f"{step} (threshold "
                        f"{run.guard_max_consecutive_skips}) — the run "
                        "cannot make progress; a final checkpoint was "
                        "saved")
            if slow:
                log_fn(f"[watchdog] step {step} straggled: {dt:.2f}s")
            if step % log_every == 0:
                if rec is not None:
                    log_fn(render_step(rec))
                else:
                    log_fn(f"step {step:5d} loss {metrics['loss']:.4f} "
                           f"gnorm {metrics['grad_norm']:.2f} "
                           f"lr {metrics['lr']:.2e} {dt*1e3:.0f}ms")
            if stop["now"]:
                log_fn(f"[signal] interrupted at step {step}; saving")
                if recorder is not None:
                    recorder.event("signal", step=step, signal="SIGTERM")
                break
    except KeyboardInterrupt:
        log_fn("[interrupt] saving final checkpoint")
        if recorder is not None:
            recorder.event("interrupt")
    finally:
        signal.signal(signal.SIGTERM, old_handler)
        hooks.close()
        if mgr is not None:
            mgr.wait()
            mgr.save(int(state["step"]), state)
        if recorder is not None:
            recorder.summary(final_step=int(state["step"]),
                             slow_steps=watchdog.slow_steps,
                             skipped_steps=skipped_total,
                             **{f"phase_{k}_{s}": v
                                for k, h in timer.summaries().items()
                                for s, v in h.items()})
    return state, history
