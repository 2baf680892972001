"""The comparison that decides ``correct``: numbers read against the plain
reference, each held to a limit of its own from ``bench/limits/<cell>.json``
(set from readings of the program over many seeds and of the float8
control, as ``PERF.md`` records).

Every number here is a gap, so a reading is within its limit when it is
no larger. A cell without a limits file is never correct; a reading that
the file does not name is printed and not compared.
"""

from __future__ import annotations

import json
import os

import numpy as np

from bench.model import ROOT

# A leaf whose reference gradient is under this share of the median
# leaf's is left out of the norm comparisons: it moves by round-off alone.
TINY_LEAF = 1e-3


def load_limits(workload: str):
    path = os.path.join(ROOT, "bench", "limits", f"{workload}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def loss_gap(program, reference) -> float:
    return max(abs(p - r) / abs(r) for p, r in zip(program, reference))


def leaf_gap(program: dict, reference: dict, ref_grad: dict) -> tuple:
    """Worst leaf of |‖a‖ - ‖r‖| / max(‖r‖, median leaf ‖r‖); leaves whose
    reference gradient is tiny are left out. Returns (gap, leaf)."""
    med_g = float(np.median(list(ref_grad.values())))
    med = float(np.median(list(reference.values())))
    worst = (0.0, None)
    for name, r in reference.items():
        if ref_grad[name] < TINY_LEAF * med_g:
            continue
        gap = abs(program[name] - r) / max(r, med)
        if gap > worst[0]:
            worst = (gap, name)
    return worst


def train_readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"losses": [...], "grad": {leaf: norm},
    "change": {leaf: norm}}."""
    g, g_leaf = leaf_gap(prog["grad"], ref["grad"], ref["grad"])
    ch, ch_leaf = leaf_gap(prog["change"], ref["change"], ref["grad"])
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad_gap": g, "change_gap": ch,
            "_worst_leaves": {"grad_gap": g_leaf, "change_gap": ch_leaf}}


def judge(readings: dict, limits) -> tuple:
    """(correct, {name: {"value", "limit"}}) over the numbers the limits
    file compares; a reading it does not name is reported apart."""
    if limits is None:
        return False, {n: {"value": v, "limit": None}
                       for n, v in readings.items() if not n.startswith("_")}
    check = {n: {"value": readings[n], "limit": lim}
             for n, lim in limits["limits"].items()}
    return all(c["value"] <= c["limit"] for c in check.values()), check
