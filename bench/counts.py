"""Operations and bytes, counted from shapes.

Model FLOPs are the work the model needs, not what the program runs:
recomputation (remat) and padding are not counted, and the input
embedding, a gather, is no matrix product.

Per token, a layer's matrix products take ``2N`` FLOPs forward for its
``N`` matrix parameters and ``4N`` backward; the head is a matrix product
over the vocabulary slice (``vocab_size · d``). What a mixer computes
beyond its products (the scores and states of attention) its kind counts
(``mixing_flops`` in ``bench/kinds/<kind>.py``), twice that backward.

Kernel counts (``kernel_work``) are per call of each Pallas kernel, from
the shapes it is called with; their bytes are the least a call must move
between HBM and the chip: every input read once and every output written
once.
"""

from __future__ import annotations

from bench import kinds


def _layers(c: dict):
    """The kind modules of every layer: (mixer, mlp) per pattern position,
    and how often the pattern repeats."""
    return [(kinds.kind(p["mixer"]), kinds.kind(p["mlp"]))
            for p in kinds.pattern(c)], kinds.groups(c)


def matmul_params(c: dict) -> int:
    """Matrix parameters that every token multiplies: the projections of
    every layer's kinds plus the head over the vocabulary slice. The input
    embedding is left out."""
    pos, g = _layers(c)
    return g * sum(m.matmul_params(c) + f.matmul_params(c) for m, f in pos) \
        + c["vocab_size"] * c["hidden_size"]


def _mixing(c: dict, seq_len) -> int:
    pos, g = _layers(c)
    return g * sum(m.mixing_flops(c, seq_len) + f.mixing_flops(c, seq_len)
                   for m, f in pos)


def forward_flops_per_token(c: dict, seq_len: int = None) -> int:
    """Forward FLOPs per token in a row of ``seq_len`` tokens (a kind whose
    count depends on it needs it)."""
    return 2 * matmul_params(c) + _mixing(c, seq_len)


def train_flops_per_token(c: dict, seq_len: int = None) -> int:
    """Forward and backward: three times the forward."""
    return 3 * forward_flops_per_token(c, seq_len)


def decode_flops_per_token(c: dict, context: int = None) -> int:
    """One decode step after ``context`` tokens: the matrix products and
    each kind's recurrent work (for linear attention the state update and
    read, 4·dk·dv a head)."""
    pos, g = _layers(c)
    return 2 * matmul_params(c) + g * sum(
        m.decode_mixing_flops(c, context) + f.decode_mixing_flops(c, context)
        for m, f in pos)


# ---------------------------------------------------------------------------
# Pallas kernels, per call
# ---------------------------------------------------------------------------

def kernel_work(name: str, *, bh: int, s: int = 1, dk: int, dv: int,
                block: int = 128, in_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of one call. ``in_bytes``: width of q/k/v/o and
    their gradients (bf16: 2); states, decays and their gradients are
    fp32.

    lasp2_chunk_fwd   reads q, k, v, log_a; writes o and the final state.
    lasp2_chunk_bwd_dq   reads k, v, log_a, dO; writes dq. Per block it
        forms dO·vᵀ and its product with k (2·C·(dk+dv) per token), the
        carried-state term dO·Mᵀ and the state update (4·dk·dv).
    lasp2_chunk_bwd_dkv  reads q, k, v, log_a, dO, o and dM; writes dk, dv
        and dlog_a. Per token: the two score matrices and their products
        (4·C·(dk+dv)), the state terms of dk and dv and the suffix-state
        update (6·dk·dv).
    lasp2_decode_step    reads q, k, v (bf16), log_a and the state (fp32);
        writes o (fp32) and the state. Per head: kᵀv, the decayed sum
        a·M + kᵀv and q·M' (2·dk·dv each).
    """
    C = block
    if name == "lasp2_chunk_fwd":
        flops = bh * s * (2 * C * (dk + dv) + 4 * dk * dv)
        nbytes = (bh * s * ((2 * dk + dv) * in_bytes + 4)
                  + bh * s * dv * in_bytes + bh * dk * dv * 4)
    elif name == "lasp2_chunk_bwd_dq":
        flops = bh * s * (2 * C * (dk + dv) + 4 * dk * dv)
        nbytes = (bh * s * ((dk + 2 * dv) * in_bytes + 4)
                  + bh * s * dk * in_bytes)
    elif name == "lasp2_chunk_bwd_dkv":
        flops = bh * s * (4 * C * (dk + dv) + 6 * dk * dv)
        nbytes = (bh * s * ((2 * dk + 3 * dv) * in_bytes + 4)
                  + bh * dk * dv * 4
                  + bh * s * ((dk + dv) * in_bytes + 4))
    elif name == "lasp2_decode_step":
        flops = bh * (6 * dk * dv)
        nbytes = (bh * ((2 * dk + dv) * in_bytes + 4 + dv * 4)
                  + 2 * bh * dk * dv * 4)
    else:
        raise KeyError(f"no work count for kernel {name!r}")
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of compute time at peak FLOP/s and
    memory time at peak HBM bandwidth, and which of the two it is."""
    tc, tm = flops / peaks["flops"], nbytes / peaks["hbm_bw"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def prefill_flops(c: dict, n: int) -> int:
    """A prompt of ``n`` tokens: every token through the layers, and the
    head once, for the last position."""
    head = c["vocab_size"] * c["hidden_size"]
    return n * (2 * (matmul_params(c) - head) + _mixing(c, n)) + 2 * head
