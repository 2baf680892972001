"""Device time of a training step by layer, and the host's part in the
gaps between steps, from a second traced window.

Run once per ``--trace 1`` process by the readers that need it
(``bench/metrics/*_ms.train.py``), after the benchmark's own window and
after the readers listed before them have read that window:

1. the cell's jitted step is lowered and compiled on the live state
   (from the persistent cache), and the compiled module's text gives each
   instruction its layer scope and pass
   (``repro.obs.scopes.instruction_scopes``);
2. that compiled object runs for ``SECONDS`` through
   ``bench.train.window``, so the host loop and its spans are the
   benchmark's own, under a profiler trace and ``repro.obs.HostWatch``
   (GC pauses become host spans ``gc``); the advanced state goes back
   into the record;
3. :func:`reduce` reads the trace per device, and the result is printed
   on standard error.

Clock. The device's events and the host's are stamped on clocks that
differ by an offset δ (host = device + δ; 1.4-1.9 ms on the trace in
``bench/tests/data``). Each module execution carries a ``run_id`` that
the host's ``DoEnqueueProgram`` (begun before the device starts) and
``CompleteCallbacks`` (begun after it ends) carry too, so δ lies in
[max(enqueue − start), min(completion − end)]; the midpoint is taken. If
the bounds cross, nothing is attributed to the host.

Against a program whose train step opens no scopes (an older checkout),
the gaps are read and every scope reads nothing.
"""

from __future__ import annotations

import bisect
import dataclasses
import os
import re
import shutil
import sys
from collections import defaultdict
from contextlib import nullcontext

from bench.trace import CONTAINERS, _union, find_xplane, op_name

SECONDS = 4.0
WINDOW = "window"
SPANS = (WINDOW, "data", "step", "fence", "gc")
TOP = 5
PASSES = ("fwd", "remat", "bwd")
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"


def _stats(e) -> dict:
    return {k: v for k, v in e.stats}


def _int(v):
    try:
        return int(v)
    except (TypeError, ValueError):
        return None


def load(path: str) -> dict:
    """Plain data of a trace. Per device plane: ``modules`` (name, start,
    end, run_id) from the ``XLA Modules`` line and ``ops`` (full
    instruction name, start, end) from ``XLA Ops``. On the host: the
    spans named in ``SPANS``, and when the enqueue and the completion of
    each ``(run_id, device ordinal)`` began."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    enqueue, complete = {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        mods.append((e.name.split("(", 1)[0], e.start_ns,
                                     e.end_ns, _int(_stats(e).get("run_id"))))
                elif line.name == "XLA Ops":
                    for e in line.events:
                        ops.append((e.name.split(" ", 1)[0].lstrip("%"),
                                    e.start_ns, e.end_ns))
            devices[plane.name] = {"modules": mods, "ops": ops}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        host.append((e.name, e.start_ns, e.end_ns))
                    elif e.name in (ENQUEUE, COMPLETE):
                        st = _stats(e)
                        key = (_int(st.get("run_id")),
                               _int(st.get("device_ordinal")))
                        (enqueue if e.name == ENQUEUE else
                         complete)[key] = e.start_ns
    return {"devices": devices, "host": host, "enqueue": enqueue,
            "complete": complete}


def _ordinal(plane: str):
    return _int(plane.rsplit(":", 1)[-1])


def _stamp(table, run, ordinal):
    for key in ((run, ordinal), (run, None)):
        if key in table:
            return table[key]
    return None


def offsets(modules, enqueue, complete, ordinal):
    """Per module execution whose ``run_id`` the host's events carry:
    enqueue − device start (each a lower bound of δ) and completion −
    device end (each an upper bound), in ns."""
    lo, hi = [], []
    for _, s, e, run in modules:
        t = _stamp(enqueue, run, ordinal)
        if t is not None:
            lo.append(t - s)
        t = _stamp(complete, run, ordinal)
        if t is not None:
            hi.append(t - e)
    return lo, hi


def _label(spans, a, b):
    """The host span (other than the window) that covers most of
    ``[a, b]``, or ``none``."""
    best, cover = "none", 0
    for n, s, e in spans:
        if n == WINDOW:
            continue
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = n, c
    return best


def reduce_device(dev: dict, host, enqueue, complete, ordinal, scopes,
                  module=None):
    """One device's steps: clock bounds, gaps between consecutive step
    executions with their host label, and device time by scope and pass
    (ms per execution). ``scopes`` maps instruction name to ``(scope,
    pass)``; ``module`` keeps only executions of that module."""
    mods = sorted((m for m in dev["modules"]
                   if module is None or m[0] == module), key=lambda m: m[1])
    if not mods:
        return None
    n = len(mods)
    lo, hi = offsets(mods, enqueue, complete, ordinal)
    bounds = (max(lo), min(hi)) if lo and hi else None
    delta = None
    if bounds is not None and bounds[0] <= bounds[1]:
        delta = (bounds[0] + bounds[1]) / 2
    gaps = []
    for (_, _, e0, _), (_, s1, _, _) in zip(mods, mods[1:]):
        lab = "unattributed" if delta is None else \
            _label(host, e0 + delta, s1 + delta)
        gaps.append((s1 - e0, lab))
    lead = None
    wins = [(s, e) for nm, s, e in host if nm == WINDOW]
    if delta is not None and wins:
        w0 = wins[0][0]
        first = mods[0][1] + delta
        lead = (first - w0, _label(host, w0, first))

    starts = [m[1] for m in mods]
    op_ns, op_calls = defaultdict(float), defaultdict(int)
    for name, s, e in dev["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or e > mods[i][2]:
            continue
        op_calls[name] += 1
        if op_name(name) not in CONTAINERS:
            op_ns[name] += e - s
    by_scope = defaultdict(lambda: dict.fromkeys(PASSES, 0.0))
    instrs = defaultdict(dict)      # scope (None: unscoped) -> name -> ms
    for name, t in op_ns.items():
        scope, kind = scopes.get(name, (None, "fwd"))
        instrs[scope][name] = t * 1e-6 / n
        if scope is not None:
            by_scope[scope][kind] += t * 1e-6 / n
    unscoped = instrs.pop(None, {})
    busy = sum(e - s for s, e in _union(
        [(s, e) for name, s, e in dev["ops"]
         if mods[0][1] <= s and e <= mods[-1][2]]))
    return {
        "steps": n,
        "module": mods[0][0],
        "step_ms": sum(e - s for _, s, e, _ in mods) * 1e-6 / n,
        "busy_ms": busy * 1e-6 / n,
        "clock_ms": None if bounds is None else
        (bounds[0] * 1e-6, bounds[1] * 1e-6),
        "delta_ms": None if delta is None else delta * 1e-6,
        # how much later than the promptest one the host heard of a
        # step's end: a host stall while the device is done
        "late_ms": (max(hi) - min(hi)) * 1e-6 if hi else None,
        "gaps_ms": [(g * 1e-6, lab) for g, lab in gaps],
        "step_gap_ms": sum(g for g, _ in gaps) * 1e-6 / len(gaps)
        if gaps else None,
        "lead_ms": None if lead is None else (lead[0] * 1e-6, lead[1]),
        "scope_ms": {k: dict(v) for k, v in by_scope.items()},
        "unscoped_ms": sum(unscoped.values()),
        "unscoped_top": _largest(unscoped, TOP),
        "scope_top": {k: _largest(v, 3) for k, v in instrs.items()},
        "op_calls": dict(op_calls),
    }


def _largest(ms: dict, k: int):
    return sorted(ms.items(), key=lambda x: -x[1])[:k]


def reduce(data: dict, scopes=None, module=None):
    """:func:`reduce_device` for every device of a trace loaded by
    :func:`load`; ``{plane: result}`` without the devices that ran no
    such module."""
    out = {}
    for plane, dev in data["devices"].items():
        r = reduce_device(dev, data["host"], data["enqueue"],
                          data["complete"], _ordinal(plane), scopes or {},
                          module=module)
        if r is not None:
            out[plane] = r
    return out


def _module_name(hlo_text: str):
    first = hlo_text.lstrip().split("\n", 1)[0]
    if first.startswith("HloModule "):
        return first.split()[1].rstrip(",")
    return None


def _instruction_scopes(hlo_text: str) -> dict:
    try:
        from repro.obs.scopes import instruction_scopes
    except ImportError:     # a program without layer scopes
        return {}
    return instruction_scopes(hlo_text)


def _host_watch():
    try:
        from repro.obs import HostWatch
    except ImportError:     # a program without the host watch
        return nullcontext()
    return HostWatch()


def measure(record) -> dict | None:
    """The second window's reduction, made on the first call and kept in
    ``record["scoped"]``; ``None`` for other traffic than training."""
    if "scoped" in record:
        return record["scoped"]
    record["scoped"] = None
    ctx = record["ctx"]
    if ctx.traffic["kind"] != "train":
        return None
    import jax

    from bench import train
    from bench.model import ROOT

    s = record["state"]
    first = s["next"] + record["window"]["steps"]
    batch = train._feed(ctx.traffic, ctx.seed, first,
                        ctx.config["vocab_size"], s.get("batch_sharding"))
    compiled = s["step"].lower(s["state"], batch).compile()
    hlo = compiled.as_text()
    scopes = _instruction_scopes(hlo)
    run = {"state": s["state"], "step": compiled, "next": first,
           "batch_sharding": s.get("batch_sharding")}
    tdir = os.path.join(ROOT, "bench", ".traces", f"{ctx.workload}.scoped")
    shutil.rmtree(tdir, ignore_errors=True)
    watch = _host_watch()
    jax.profiler.start_trace(tdir)
    try:
        with watch:
            w = train.window(dataclasses.replace(ctx, seconds=SECONDS), run)
    finally:
        jax.profiler.stop_trace()
    s["state"], s["next"] = run["state"], first + w["steps"]
    try:
        per_dev = reduce(load(find_xplane(tdir)), scopes,
                         module=_module_name(hlo))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    if not per_dev:
        print("scoped: the second window holds no step execution",
              file=sys.stderr, flush=True)
        return None
    host = dict(getattr(watch, "counts", {}))
    result = _combine(per_dev, bool(scopes))
    result.update(window_steps_per_s=w["steps"] / w["window_s"],
                  main_steps_per_s=record["window"]["steps"]
                  / record["window"]["window_s"], host=host)
    _print(result, per_dev, hlo)
    record["scoped"] = result
    return result


def _combine(per_dev: dict, has_scopes: bool) -> dict:
    """Means over devices of the numbers the readers take."""
    devs = list(per_dev.values())
    k = len(devs)
    scope_ms = defaultdict(float)
    for d in devs:
        for name, passes in d["scope_ms"].items():
            scope_ms[name] += sum(passes.values()) / k
    gaps = [d["step_gap_ms"] for d in devs if d["step_gap_ms"] is not None]
    return {"has_scopes": has_scopes, "scope_ms": dict(scope_ms),
            "step_gap_ms": max(gaps) if gaps else None}


def scope_ms(record, *names):
    """Sum of the named scopes' ms per step, or ``None`` when the second
    window could not be read or the program opens none of them."""
    r = measure(record)
    if r is None or not r["has_scopes"]:
        return None
    found = [r["scope_ms"][n] for n in names if n in r["scope_ms"]]
    return sum(found) if found else None


def _origin(hlo_text: str, name: str) -> str:
    """The ``op_name`` metadata of one instruction, for the reader of
    what no scope claims."""
    m = re.search(rf"^\s*(?:ROOT\s+)?%?{re.escape(name)} = .*?"
                  r'op_name="([^"]*)"', hlo_text, re.M)
    return m.group(1) if m else "?"


def _print(result, per_dev, hlo_text):
    def say(msg):
        print(f"scoped: {msg}", file=sys.stderr, flush=True)

    say(f"second window {result['window_steps_per_s']:.4f} steps/s "
        f"under HostWatch, main traced window "
        f"{result['main_steps_per_s']:.4f}")
    for plane, d in per_dev.items():
        say(f"{plane} {d['module']}: {d['steps']} executions, "
            f"{d['step_ms']:.3f} ms each, ops busy {d['busy_ms']:.3f} ms")
        if d["clock_ms"] is None:
            say("  clock: no run_id shared by host and device")
        else:
            lo, hi = d["clock_ms"]
            verdict = f"delta {d['delta_ms']:.4f} ms" \
                if d["delta_ms"] is not None else \
                "bounds cross: no gap is attributed to the host"
            say(f"  clock: host - device in [{lo:.4f}, {hi:.4f}] ms, "
                f"{verdict}; the host heard of a step's end at most "
                f"{d['late_ms']:.4f} ms later than of the promptest")
        total = d["unscoped_ms"]
        for name, p in sorted(d["scope_ms"].items(),
                              key=lambda x: -sum(x[1].values())):
            t = sum(p.values())
            total += t
            say(f"  scope {name}: {t:.3f} ms (fwd {p['fwd']:.3f}, "
                f"remat {p['remat']:.3f}, bwd {p['bwd']:.3f}); largest: "
                + ", ".join(f"{i} {x:.3f}" for i, x in d["scope_top"][name]))
        share = 100.0 * d["unscoped_ms"] / d["step_ms"] if d["step_ms"] \
            else 0.0
        say(f"  unscoped: {d['unscoped_ms']:.3f} ms ({share:.2f} % of the "
            f"execution); largest: "
            + ", ".join(f"{n} {t:.3f}" for n, t in d["unscoped_top"]))
        for n, _ in d["unscoped_top"]:
            say(f"    {n}: {_origin(hlo_text, n)[-160:]}")
        say(f"  scopes + unscoped {total:.3f} ms of {d['step_ms']:.3f} ms")
        gaps = sorted(d["gaps_ms"], reverse=True)[:5]
        say("  longest gaps between steps: "
            + ", ".join(f"{g:.4f} ms ({lab})" for g, lab in gaps))
        if d["lead_ms"] is not None:
            say(f"  window start to first execution: "
                f"{d['lead_ms'][0]:.4f} ms ({d['lead_ms'][1]})")
    h = result["host"]
    if h:
        say(f"host in the window: {h['compiles']} compiles "
            f"({h['compile_s']:.4f} s), {h['gc_pauses']} GC pauses "
            f"({h['gc_s']:.4f} s)")
    else:
        say("host in the window: not watched (no HostWatch)")
