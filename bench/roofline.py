"""A kernel's share of its roofline, from the trace.

For every call of the kernel's Pallas programs inside the traced window,
the least time the chip could take (``bench.counts.least_time`` of the
call's counted FLOPs and bytes at the chip's peaks), summed and divided
by the calls' summed device time. All calls of one program in a cell have
the shape the cell gives (``kernel_shapes``)."""

from __future__ import annotations

from bench import counts


def share(record, programs, shape: dict):
    """Percent, or None when the trace holds no call of these programs.
    Also returns which bound (compute or memory) holds the most of the
    least time."""
    tr = record["trace"]
    peaks = record["ctx"].peaks
    least, spent, by_bound = 0.0, 0.0, {"compute": 0.0, "memory": 0.0}
    for prog in programs:
        calls = tr["op_calls"].get(prog, 0)
        if not calls:
            continue
        t, bound = counts.least_time(*counts.kernel_work(prog, **shape),
                                     peaks)
        least += calls * t
        by_bound[bound] += calls * t
        spent += tr["op_s"][prog]
    if spent <= 0:
        return None, None
    return 100.0 * least / spent, max(by_bound, key=by_bound.get)
