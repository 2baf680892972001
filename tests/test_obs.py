"""Observability layer (docs/observability.md): sinks, histogram math,
phase timers, flight-recorder drift rules, and the instrumented train
loop + report renderer end to end."""

import json
import os
import subprocess
import sys

import numpy as np

from repro.comm.primitives import CommRecord, tape_summary
from repro.obs import (FlightRecorder, Histogram, InMemorySink, JsonlSink,
                       Metrics, NullSink, PhaseTimer, as_sink, read_jsonl,
                       render_step, scoped_timer)

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

from benchmarks.common import percentile as bench_percentile  # noqa: E402


# ---------------------------------------------------------------------------
# Histogram / percentile math.
# ---------------------------------------------------------------------------

def test_histogram_exact_quantiles_match_bench_percentile():
    """While under cap, Histogram.percentile is the SAME nearest-rank
    number benchmarks.common.percentile produces — bench JSON and
    telemetry quantiles must agree by construction."""
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 7, 10, 101):
        xs = list(rng.normal(size=n))
        h = Histogram()
        h.extend(xs)
        assert h.exact
        for p in (0, 25, 50, 90, 99, 100):
            assert h.percentile(p) == bench_percentile(xs, p), (n, p)
        assert h.min == min(xs) and h.max == max(xs)
        assert abs(h.mean - np.mean(xs)) < 1e-12


def test_histogram_small_input_quantiles_exact():
    h = Histogram()
    h.extend([3.0, 1.0, 2.0])
    assert h.percentile(0) == 1.0
    assert h.percentile(50) == 2.0
    assert h.percentile(100) == 3.0
    s = h.summary()
    assert s["count"] == 3 and s["mean"] == 2.0
    assert s["min"] == 1.0 and s["max"] == 3.0 and s["p50"] == 2.0


def test_histogram_empty():
    h = Histogram()
    assert h.percentile(50) is None
    assert h.mean is None
    s = h.summary()
    assert s["count"] == 0 and s["p50"] is None and s["min"] is None


def test_histogram_reservoir_bounded_but_exact_moments():
    h = Histogram(cap=64)
    xs = [float(i) for i in range(10_000)]
    h.extend(xs)
    assert not h.exact
    assert len(h._xs) == 64, "reservoir must stay bounded at cap"
    # count/total/min/max stay exact past the cap
    assert h.count == 10_000
    assert h.total == sum(xs)
    assert h.min == 0.0 and h.max == 9999.0
    # the sampled median is a coarse but sane estimate of the true one
    assert 1000.0 < h.percentile(50) < 9000.0


def test_histogram_reservoir_deterministic():
    a, b = Histogram(cap=32), Histogram(cap=32)
    for i in range(1000):
        a.add(float(i))
        b.add(float(i))
    assert a._xs == b._xs, "LCG reservoir must be run-to-run deterministic"


def test_histogram_merge_per_shard_exact_when_union_fits():
    """Per-shard sinks merge into one histogram: when the union of
    retained samples fits under cap the merged quantiles are exactly the
    pooled-data quantiles."""
    shard_a = [1.0, 5.0, 9.0, 13.0]
    shard_b = [2.0, 4.0, 8.0]
    ha, hb = Histogram(), Histogram()
    ha.extend(shard_a)
    hb.extend(shard_b)
    merged = ha.merge(hb)
    pool = shard_a + shard_b
    assert merged.count == len(pool)
    assert merged.total == sum(pool)
    assert merged.min == min(pool) and merged.max == max(pool)
    for p in (0, 50, 90, 100):
        assert merged.percentile(p) == bench_percentile(pool, p)


def test_histogram_merge_over_cap_stays_bounded():
    ha, hb = Histogram(cap=16), Histogram(cap=16)
    ha.extend(float(i) for i in range(16))
    hb.extend(float(i) for i in range(100, 116))
    merged = ha.merge(hb)
    assert len(merged._xs) <= merged.cap
    assert merged.count == 32
    assert merged.min == 0.0 and merged.max == 115.0


def test_metrics_registry_and_merge():
    m = Metrics()
    m.inc("requests")
    m.inc("requests", 2)
    m.gauge("queue", 3)
    m.gauge("queue", 1)         # latest wins; peak kept separately
    m.observe("lat_s", 0.1)
    m.observe("lat_s", 0.3)
    snap = m.snapshot()
    assert snap["requests"] == 3
    assert snap["queue"] == 1 and snap["queue_peak"] == 3
    assert snap["lat_s_count"] == 2 and snap["lat_s_p50"] == 0.1
    other = Metrics()
    other.inc("requests", 10)
    other.gauge("queue", 7)
    other.observe("lat_s", 0.2)
    merged = m.merge(other).snapshot()
    assert merged["requests"] == 13
    assert merged["queue_peak"] == 7
    assert merged["lat_s_count"] == 3


# ---------------------------------------------------------------------------
# Sinks.
# ---------------------------------------------------------------------------

def test_as_sink_resolution():
    assert isinstance(as_sink(None), NullSink)
    s = InMemorySink()
    assert as_sink(s) is s
    as_sink(None).emit({"kind": "step"})     # NullSink drops silently


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with JsonlSink(path) as sink:
        sink.emit({"kind": "step", "step": 0, "loss": 1.5})
        sink.emit({"kind": "step", "step": 1,
                   "loss": np.float32(1.25)})   # numpy scalar → coerced
    recs = read_jsonl(path)
    assert [r["step"] for r in recs] == [0, 1]
    assert recs[1]["loss"] == 1.25
    # lines are sorted-key json — what the CI smoke greps for
    with open(path) as f:
        assert '"kind": "step"' in f.readline()


def test_read_jsonl_tolerates_truncated_tail(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "step", "step": 0}) + "\n")
        f.write("\n")                                  # blank line
        f.write('{"kind": "step", "step"')             # crash mid-write
    recs = read_jsonl(path)
    assert len(recs) == 1 and recs[0]["step"] == 0


# ---------------------------------------------------------------------------
# Phase timing.
# ---------------------------------------------------------------------------

def test_scoped_timer_accumulates():
    out = {}
    clock = iter([0.0, 1.0, 5.0, 7.5]).__next__
    with scoped_timer("step", out, clock=clock):
        pass
    with scoped_timer("step", out, clock=clock):
        pass
    assert out["step"] == 1.0 + 2.5


def test_scoped_timer_fences_device_output():
    import jax.numpy as jnp
    out = {}
    with scoped_timer("step", out) as f:
        y = f.set(jnp.arange(1024) * 2)
    assert out["step"] > 0
    assert int(y[1]) == 2


def test_phase_timer_flush_and_summaries():
    t = PhaseTimer()
    for _ in range(3):
        with t.phase("data"):
            pass
        with t.phase("step"):
            pass
        walls = t.flush()
        assert set(walls) == {"data_s", "step_s"}
        assert t.current == {}, "flush must reset the per-step walls"
    summ = t.summaries()
    assert summ["step_s"]["count"] == 3
    assert summ["data_s"]["count"] == 3


# ---------------------------------------------------------------------------
# Flight recorder: tape vs HLO drift rules, step records, warmup.
# ---------------------------------------------------------------------------

def _tape():
    return [CommRecord("all-gather", 1000, 875, 1, 8, tag="lasp2.states"),
            CommRecord("all-gather", 1000, 875, 1, 8, tag="lasp2.states"),
            CommRecord("all-reduce", 4000, 7000, 1, 8, tag="grads")]


def test_tape_summary_empty():
    s = tape_summary([])
    assert s["total_bytes"] == 0 and s["total_steps"] == 0


def test_flight_recorder_no_drift_when_hlo_covers_tape():
    sink = InMemorySink()
    fr = FlightRecorder(sink)
    # autodiff adds collectives the tape never sees (e.g. the
    # reduce-scatter transpose of a forward gather): NOT drift
    snap = fr.on_compile(
        records=_tape(),
        hlo_counts={"all-gather": 3, "all-reduce": 1, "reduce-scatter": 1},
        hlo_bytes_by_op={"all-gather": 2000.0, "all-reduce": 7000.0,
                         "reduce-scatter": 500.0})
    assert snap.drift == []
    assert snap.expected_bytes_per_step == tape_summary(_tape())["total_bytes"]
    assert snap.tape_counts == {"all-gather": 2, "all-reduce": 1}
    (rec,) = sink.by_kind("compile")
    assert rec["tape/all-gather_count"] == 2
    assert rec["hlo/all-gather_count"] == 3
    assert rec["drift"] == []


def test_flight_recorder_flags_injected_drift():
    sink = InMemorySink()
    fr = FlightRecorder(sink)
    # inject a collective the compiled HLO does not carry
    records = _tape() + [CommRecord("all-to-all", 10, 70, 1, 8)]
    snap = fr.on_compile(
        records=records,
        hlo_counts={"all-gather": 3, "all-reduce": 1},
        hlo_bytes_by_op={"all-gather": 2000.0, "all-reduce": 7000.0})
    assert any("all-to-all" in d for d in snap.drift), snap.drift
    assert fr.drift_events == snap.drift
    (rec,) = sink.by_kind("compile")
    assert rec["drift"], "compile record must carry the drift flags"


def test_flight_recorder_flags_missing_instances():
    fr = FlightRecorder(InMemorySink())
    snap = fr.on_compile(records=_tape(),
                         hlo_counts={"all-gather": 1, "all-reduce": 1})
    assert any("tape promises 2" in d for d in snap.drift), snap.drift


def test_flight_recorder_step_records_and_warmup():
    sink = InMemorySink()
    fr = FlightRecorder(sink, model_flops_per_step=1e9, n_devices=2,
                        peak_flops=1e12, wall_warmup=1)
    fr.on_compile(records=_tape(), hlo_counts={"all-gather": 2,
                                               "all-reduce": 1})
    # first step is the compile spike: never flagged, never in the window
    rec0 = fr.on_step(0, 30.0, tokens=1000)
    assert rec0["straggler"] is False
    assert fr.expected_wall_s() is None, \
        "warmup wall must not enter the rolling window"
    for i in range(1, 13):
        fr.on_step(i, 0.1, tokens=1000)
    assert abs(fr.expected_wall_s() - 0.1) < 1e-9
    rec = fr.on_step(13, 1.0, tokens=1000)
    assert rec["straggler"] is True, \
        "post-warmup 10x spike must trip the rolling-median rule"
    # derived throughput fields on a normal step
    steps = sink.by_kind("step")
    r = steps[5]
    assert r["tokens_per_s"] == 1000 / 0.1
    assert abs(r["mfu"] - (1e9 / 0.1) / (2 * 1e12)) < 1e-12
    assert r["expected_collective_bytes"] == \
        tape_summary(_tape())["total_bytes"]
    assert r["comm_bytes_per_token"] == r["expected_collective_bytes"] / 1000
    summ = fr.summary(final_step=13)
    assert summ["steps_recorded"] == 14
    assert summ["wall_s_count"] == 13      # warmup step excluded


def test_flight_recorder_external_straggler_verdict_wins():
    fr = FlightRecorder(InMemorySink())
    for i in range(12):
        fr.on_step(i, 0.1)
    rec = fr.on_step(12, 0.1, straggler=True)   # external watchdog verdict
    assert rec["straggler"] is True


def test_render_step_one_liner():
    line = render_step({"kind": "step", "step": 7, "loss": 2.5,
                        "wall_s": 0.25, "tokens_per_s": 4096.0,
                        "mfu": 0.41})
    assert "step     7" in line and "loss 2.5000" in line
    assert "250ms" in line and "4096 tok/s" in line and "41.00%" in line


# ---------------------------------------------------------------------------
# Instrumented train loop + report renderer, end to end.
# ---------------------------------------------------------------------------

def test_train_sink_records_and_aot_parity(tmp_path):
    """train(sink=...) emits compile/step/summary records with phase
    walls + throughput, and the AOT-compiled instrumented path produces
    the SAME losses as the uninstrumented jit path."""
    from repro.configs import get_smoke
    from repro.configs.base import RunConfig
    from repro.data.pipeline import SyntheticLM

    from repro.train.loop import train

    cfg = get_smoke("linear-llama3-1b")
    run = RunConfig(num_microbatches=1, total_steps=5, warmup_steps=2,
                    learning_rate=1e-3, remat="none")
    data = SyntheticLM(cfg.vocab_size, 64, 4, seed=0)
    sink = InMemorySink()
    _, hist = train(cfg, run, data, log_every=10 ** 9,
                    log_fn=lambda *_: None, sink=sink)
    _, hist_ref = train(cfg, run, data, log_every=10 ** 9,
                        log_fn=lambda *_: None)
    np.testing.assert_array_equal([h["loss"] for h in hist],
                                  [h["loss"] for h in hist_ref])

    (comp,) = sink.by_kind("compile")
    assert comp["drift"] == [], \
        "single-device program must not flag drift (empty tape)"
    steps = sink.by_kind("step")
    assert len(steps) == 5
    for r in steps:
        assert {"step_s", "data_s", "ckpt_s", "wall_s", "loss",
                "tokens_per_s", "mfu", "straggler",
                "expected_collective_bytes", "gc_pauses", "gc_s",
                "compiles", "compile_s"} <= set(r)
        assert r["tokens"] == 4 * 64
    assert steps[0]["straggler"] is False, "compile step never flagged"
    (summ,) = sink.by_kind("summary")
    assert summ["steps_recorded"] == 5 and summ["final_step"] == 5
    assert summ["phase_step_s_count"] == 5
    events = sink.by_kind("event")
    assert any(e["event"] == "compile" for e in events)


def test_report_renders_jsonl(tmp_path):
    """scripts/report.py turns a sink file into markdown (the CI smoke
    in .github/workflows/ci.yml runs the same pipeline on a real run)."""
    path = str(tmp_path / "metrics.jsonl")
    with JsonlSink(path) as sink:
        fr = FlightRecorder(sink, model_flops_per_step=1e9)
        fr.on_compile(records=_tape(),
                      hlo_counts={"all-gather": 2, "all-reduce": 1},
                      hlo_bytes_by_op={"all-gather": 1750.0,
                                       "all-reduce": 7000.0})
        for i in range(12):
            fr.on_step(i, 0.1 if i else 2.0, tokens=256,
                       phases={"data_s": 0.01, "step_s": 0.09})
        fr.event("resume", step=3)
        fr.summary(final_step=12)
        sink.emit({"kind": "request", "uid": 0, "prompt_len": 16,
                   "new_tokens": 8, "finish_reason": "length",
                   "wall_s": 0.5, "ttft_s": 0.2})
    out = str(tmp_path / "report.md")
    script = os.path.join(ROOT, "scripts", "report.py")
    proc = subprocess.run([sys.executable, script, path, "-o", out],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    text = open(out).read()
    assert "expected (tape) bytes/step" in text
    assert "all-gather" in text and "no drift" in text
    assert "tokens_per_s" in text and "ttft_s" in text


def test_report_exits_nonzero_on_empty(tmp_path):
    path = str(tmp_path / "empty.jsonl")
    open(path, "w").close()
    script = os.path.join(ROOT, "scripts", "report.py")
    proc = subprocess.run([sys.executable, script, path],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
