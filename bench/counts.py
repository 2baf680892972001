"""Operations and bytes, counted from shapes.

Model FLOPs are the work the model needs, not what the program runs:
recomputation (remat) and padding are not counted, and the input
embedding, a gather, is no matrix product.

Per token and layer of a dense model with matrix parameters ``N`` a
forward pass takes ``2N`` FLOPs and a backward ``4N``. The head is a
matrix product over the vocabulary slice (``vocab_size · d``). Linear
attention is counted by its chunked form with block ``C`` (per token and
head, ``dk``/``dv`` the head widths):

    scores  q kᵀ inside the block     2·C·dk
    scores·v                          2·C·dv
    q·M (state read)                  2·dk·dv
    kᵀv (state update)                2·dk·dv

so ``2·C·(dk+dv) + 4·dk·dv`` forward, and twice that backward.

Kernel counts (``kernel_work``) are per call of each Pallas kernel, from
the shapes it is called with; their bytes are the least a call must move
between HBM and the chip: every input read once and every output written
once.
"""

from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Matrix parameters that every token multiplies: attention and MLP
    projections of every layer plus the head over the vocabulary slice.
    The input embedding is left out."""
    d, f = c["hidden_size"], c["intermediate_size"]
    hq = c["num_attention_heads"] * c["head_dim"]
    hkv = c["num_key_value_heads"] * c["head_dim"]
    per_layer = d * hq + 2 * d * hkv + hq * d + 3 * d * f
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * d


def linear_attention_flops_fwd(c: dict) -> int:
    """Forward FLOPs per token of one linear-attention layer, chunked."""
    C = c["linear_attention"]["block_size"]
    dh, h = c["head_dim"], c["num_attention_heads"]
    return h * (2 * C * (dh + dh) + 4 * dh * dh)


def n_linear_layers(c: dict) -> int:
    p = c["layer_pattern"]
    return c["num_hidden_layers"] // len(p) * sum(m == "linear" for m in p)


def forward_flops_per_token(c: dict) -> int:
    return 2 * matmul_params(c) + n_linear_layers(c) * \
        linear_attention_flops_fwd(c)


def train_flops_per_token(c: dict) -> int:
    """Forward and backward: three times the forward."""
    return 3 * forward_flops_per_token(c)


def decode_flops_per_token(c: dict) -> int:
    """One recurrent step per token: the matrix products and, per linear
    layer and head, the state update and read (4·dk·dv)."""
    dh, h = c["head_dim"], c["num_attention_heads"]
    return 2 * matmul_params(c) + n_linear_layers(c) * h * 4 * dh * dh


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
    return n * (2 * (matmul_params(c) - head) + n_linear_layers(c)
                * linear_attention_flops_fwd(c)) + 2 * head
