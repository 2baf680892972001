"""Comm/compute overlap scheduling for SP exchanges.

XLA's latency-hiding scheduler overlaps a collective with any compute
that is *dataflow-independent* of it (on TPU the collective becomes an
``all-gather-start`` / ``all-gather-done`` pair with the independent
compute scheduled between them). The scheduler here therefore controls
dependency structure, not threads:

``mode="overlap"`` (default) — double-buffered: the cheap chunk-summary
  pass fills buffer A (the exchange payload), the exchange is issued,
  and the heavy intra-chunk kernel fills buffer B while the states are
  in flight; the inter-chunk combine consumes both. This is paper
  Alg. 2's line ordering (summaries → AllGather → intra-chunk) realized
  as a dependency graph — the paper's comm/compute overlap claim.

``mode="none"`` — an ``optimization_barrier`` makes the exchange operand
  depend on the intra-chunk output, forcing the collective to start only
  after compute finishes. This is the A/B baseline
  ``benchmarks/comm_strategies.py`` measures overlap against.

The barrier is wrapped in a ``custom_vjp`` that passes cotangents
straight through, so the serialization applies to the forward schedule
only, which is what the A/B compares.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

MODES = ("overlap", "none")


@jax.custom_vjp
def _serialize(payload, anchor):
    """Make ``payload`` data-depend on ``anchor`` (identity values)."""
    payload, anchor = jax.lax.optimization_barrier((payload, anchor))
    return payload, anchor


def _serialize_fwd(payload, anchor):
    return _serialize(payload, anchor), None


def _serialize_bwd(_, cot):
    return cot


_serialize.defvjp(_serialize_fwd, _serialize_bwd)


@dataclass(frozen=True)
class DoubleBufferedScheduler:
    """Orders one SP exchange against the intra-chunk compute."""

    mode: str = "overlap"

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(
                f"unknown overlap mode {self.mode!r}; expected one of "
                f"{MODES}")

    def run(self, payload, exchange, compute):
        """Returns ``(exchange_result, compute_result)``.

        ``exchange``: payload -> exchanged value (must contain the
        collective). ``compute``: () -> pytree, independent of the
        exchange (the intra-chunk kernel).
        """
        if self.mode == "none":
            out = compute()
            payload, out = _serialize(payload, out)
            return exchange(payload), out
        exchanged = exchange(payload)   # issued first → in flight …
        out = compute()                 # … while the intra kernel runs
        return exchanged, out
