"""Device time of a training step on each chip of a cell over several
chips: layer scopes per device, and the collectives' time that no
compute on the same device overlaps, from a traced window of its own.

Run once per ``--trace 1`` process by the readers that need it
(``bench/metrics/softmax_mixer_ms.train.py``,
``exposed_collective_ms.train.py``), after ``bench/scoped.py``'s window,
whose reduction keeps only the mean over devices. As there, the cell's
jitted step is compiled again on the live state (from the persistent
cache), the compiled text gives each instruction its scope and pass
(``repro.obs.scopes``) and its opcode, and that compiled object runs for
``SECONDS`` through ``bench.train.window`` under the profiler;
``bench.scoped.reduce_device`` reads each device's scopes.

A collective is an instruction whose opcode, or whose name or called
computation for a fusion or async wrapper, is an all-gather, all-reduce,
reduce-scatter, all-to-all or collective-permute (start and done halves
included). Its exposed time is the part of its interval that no other
operation on that device covers; per step, summed over the collectives,
the union taken. Each part is labelled by the collective's scope:
``comm.<tag>`` for the program's exchanges (``repro.comm.primitives``),
``comm.<tag>.bwd`` for the mirrored exchange of the backward pass.

Against a program that opens no scopes the scopes read nothing and the
collectives are labelled ``unscoped``.
"""

from __future__ import annotations

import dataclasses
import os
import re
import shutil
import sys
from collections import defaultdict

from bench import scoped
from bench.trace import CONTAINERS, _union, find_xplane, op_name

SECONDS = 4.0
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_\-.]+)\s*=\s.*?\s"
                   r"([a-z][a-z0-9_\-]*)\(")
_CALLS = re.compile(r"\bcalls=%?([A-Za-z0-9_\-.]+)")


def _is_collective(word: str) -> bool:
    return word.startswith(COLLECTIVES)


def collective_instructions(hlo_text: str) -> set:
    """Names of the instructions of a compiled module that move data
    between devices."""
    out = set()
    for line in hlo_text.splitlines():
        m = _LINE.match(line)
        if m is None:
            continue
        name, opcode = m.groups()
        if _is_collective(opcode):
            out.add(name)
        elif opcode in ("fusion", "async-start", "async-update",
                        "async-done", "call"):
            calls = _CALLS.search(line)
            if _is_collective(name) or (calls and _is_collective(
                    calls.group(1))):
                out.add(name)
    return out


def _minus(interval, covered):
    """Length of ``interval`` outside the sorted disjoint ``covered``."""
    s, e = interval
    left = e - s
    for a, b in covered:
        if b <= s:
            continue
        if a >= e:
            break
        left -= min(b, e) - max(a, s)
    return left


def exposed(dev: dict, module: str, collectives: set, scopes: dict):
    """One device's collectives: exposed ns per step in all, and by
    label; ``None`` without executions of ``module``."""
    mods = sorted((m for m in dev["modules"] if m[0] == module),
                  key=lambda m: m[1])
    if not mods:
        return None
    lo, hi = mods[0][1], mods[-1][2]
    coll, other = [], []
    for name, s, e in dev["ops"]:
        if s < lo or e > hi or op_name(name) in CONTAINERS:
            continue
        (coll if name in collectives else other).append((name, s, e))
    busy = _union([(s, e) for _, s, e in other])
    by_label = defaultdict(float)
    for name, s, e in coll:
        scope, kind = scopes.get(name, (None, "fwd"))
        label = scope or "unscoped"
        if kind == "bwd" and not label.endswith(".bwd"):
            label += ".bwd"
        by_label[label] += _minus((s, e), busy) / len(mods)
    total = sum(_minus(iv, busy) for iv in _union(
        [(s, e) for _, s, e in coll])) / len(mods)
    return {"exposed_ns": total, "by_label": dict(by_label),
            "collective_ns": sum(e - s for _, s, e in coll) / len(mods)}


def measure(record) -> dict | None:
    """``{plane: {"scopes": bench.scoped.reduce_device(...), "exposed":
    exposed(...)}}``, made on the first call and kept in
    ``record["ranks"]``; ``None`` for other traffic than training."""
    if "ranks" in record:
        return record["ranks"]
    record["ranks"] = None
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
    scopes = scoped._instruction_scopes(hlo)
    collectives = collective_instructions(hlo)
    module = scoped._module_name(hlo)
    run = {"state": s["state"], "step": compiled, "next": first,
           "batch_sharding": s.get("batch_sharding")}
    tdir = os.path.join(ROOT, "bench", ".traces", f"{ctx.workload}.ranks")
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    try:
        w = train.window(dataclasses.replace(ctx, seconds=SECONDS), run)
    finally:
        jax.profiler.stop_trace()
    s["state"], s["next"] = run["state"], first + w["steps"]
    try:
        data = scoped.load(find_xplane(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    out = {}
    for plane, dev in sorted(data["devices"].items()):
        sc = scoped.reduce_device(dev, data["host"], data["enqueue"],
                                  data["complete"], scoped._ordinal(plane),
                                  scopes, module=module)
        if sc is not None:
            out[plane] = {"scopes": sc,
                          "exposed": exposed(dev, module, collectives,
                                             scopes)}
    if not out:
        print("ranks: the window holds no step execution", file=sys.stderr,
              flush=True)
        return None
    _print(out, bool(scopes))
    record["ranks"] = {"devices": out, "has_scopes": bool(scopes)}
    return record["ranks"]


def worst_scope_ms(record, name: str):
    """The largest over devices of one scope's ms per step, or ``None``
    when the window could not be read or no device ran the scope."""
    r = measure(record)
    if r is None or not r["has_scopes"]:
        return None
    found = [sum(d["scopes"]["scope_ms"][name].values())
             for d in r["devices"].values()
             if name in d["scopes"]["scope_ms"]]
    return max(found) if found else None


def critical_device(r) -> str:
    """The device on the step's critical path: the one whose operations,
    bare collectives left out, fill the most of a step. The others wait
    for it in their next collective, so their exposed time is that wait
    and not the exchange."""
    return max(r["devices"].items(),
               key=lambda x: x[1]["scopes"]["busy_ms"]
               - x[1]["exposed"]["exposed_ns"] * 1e-6)[0]


def _print(out, has_scopes):
    def say(msg):
        print(f"ranks: {msg}", file=sys.stderr, flush=True)

    for plane, d in out.items():
        sc, ex = d["scopes"], d["exposed"]
        say(f"{plane}: {sc['steps']} executions, {sc['step_ms']:.3f} ms "
            f"each, ops busy {sc['busy_ms']:.3f} ms")
        if has_scopes:
            for name, p in sorted(sc["scope_ms"].items(),
                                  key=lambda x: -sum(x[1].values())):
                say(f"  scope {name}: {sum(p.values()):.3f} ms (fwd "
                    f"{p['fwd']:.3f}, remat {p['remat']:.3f}, bwd "
                    f"{p['bwd']:.3f})")
        say(f"  collectives {ex['collective_ns'] * 1e-6:.3f} ms a step, "
            f"exposed {ex['exposed_ns'] * 1e-6:.3f} ms: "
            + ", ".join(f"{k} {v * 1e-6:.3f}" for k, v in sorted(
                ex["by_label"].items(), key=lambda x: -x[1])))
