"""Per-kernel Pallas sweeps (interpret mode) vs the ref.py oracles,
plus the kernel-gradient battery for the custom_vjp backward kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.core.linear_attention import RESET_LOG_A
from repro.kernels.lasp2_chunk import MAX_TILE, lasp2_chunk_fwd, seq_tile
from repro.kernels.ref import flash_attention_ref, linear_attention_ref

TOL = {jnp.float32: 3e-4, jnp.bfloat16: 4e-2}
GRAD_TOL = 1e-3


@pytest.mark.parametrize("s,dk,dv", [(256, 64, 64), (512, 128, 128),
                                     (256, 32, 64), (128, 128, 64),
                                     (2048, 128, 128), (4096, 128, 128),
                                     (6144, 128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("decay", [False, True])
def test_lasp2_chunk_kernel_sweep(rng, s, dk, dv, dtype, decay):
    bh = 3
    ks = jax.random.split(rng, 4)
    q = (jax.random.normal(ks[0], (bh, s, dk)) * 0.3).astype(dtype)
    k = (jax.random.normal(ks[1], (bh, s, dk)) * 0.3).astype(dtype)
    v = (jax.random.normal(ks[2], (bh, s, dv)) * 0.5).astype(dtype)
    la = (-jnp.abs(jax.random.normal(ks[3], (bh, s))) * 0.03) if decay \
        else jnp.zeros((bh, s))
    o, st, ld = lasp2_chunk_fwd(q, k, v, la, block_size=128, interpret=True)
    oref, stref = linear_attention_ref(q, k, v, la)
    t = TOL[dtype]
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(oref, np.float32), rtol=t, atol=t)
    np.testing.assert_allclose(st, stref, rtol=t, atol=t)
    np.testing.assert_allclose(ld, jnp.sum(la, -1), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sq,sk,hq,hkv,dh", [
    (256, 256, 4, 2, 64), (128, 128, 8, 1, 64), (256, 256, 4, 4, 128),
    (128, 256, 4, 2, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 64), (False, 64)])
@pytest.mark.parametrize("block", [64, None])
def test_flash_kernel_sweep(rng, sq, sk, hq, hkv, dh, dtype, causal,
                            window, block):
    """Forward at 64-row blocks and at the tiles ``pick_tiles`` takes."""
    b = 2
    ks = jax.random.split(rng, 3)
    q = (jax.random.normal(ks[0], (b, hq, sq, dh)) * 0.4).astype(dtype)
    k = (jax.random.normal(ks[1], (b, hkv, sk, dh)) * 0.4).astype(dtype)
    v = (jax.random.normal(ks[2], (b, hkv, sk, dh)) * 0.5).astype(dtype)
    o = flash_attention(q, k, v, causal=causal, sliding_window=window,
                        block_q=block, block_k=block, interpret=True)
    oref = flash_attention_ref(q, k, v, causal=causal,
                               sliding_window=window)
    t = TOL[dtype]
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(oref, np.float32), rtol=t, atol=t)


@pytest.mark.parametrize("dk,dv", [(32, 32), (64, 128), (128, 64)])
@pytest.mark.parametrize("decay", [False, True])
def test_lasp2_decode_kernel_sweep(rng, dk, dv, decay):
    """Single-step recurrent decode kernel == oracle recurrence, and
    chaining steps from a chunked-prefill state continues the scan."""
    from repro.core import linear_attention as la
    from repro.kernels.lasp2_chunk import lasp2_chunk_fwd

    bh, s, split = 4, 32, 24
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (bh, s, dk)) * 0.3
    k = jax.random.normal(ks[1], (bh, s, dk)) * 0.3
    v = jax.random.normal(ks[2], (bh, s, dv)) * 0.5
    la_ = (-jnp.abs(jax.random.normal(ks[3], (bh, s))) * 0.05) if decay \
        else jnp.zeros((bh, s))
    ref = la.sequential_oracle(q, k, v, la_)
    # prefill the first `split` tokens with the chunked kernel...
    _, st, ld = lasp2_chunk_fwd(q[:, :split], k[:, :split], v[:, :split],
                                la_[:, :split], block_size=8,
                                interpret=True)
    # ...then decode the rest one step at a time
    from repro.kernels.lasp2_decode import lasp2_decode_step
    outs = []
    for t in range(split, s):
        o, st, ld = lasp2_decode_step(q[:, t], k[:, t], v[:, t], la_[:, t],
                                      st, ld, interpret=True)
        outs.append(o)
    o_dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(o_dec, np.asarray(ref.o)[:, split:],
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(st, ref.state, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(ld, ref.log_decay, rtol=1e-5, atol=1e-5)


def test_linear_decode_op_dispatch(rng):
    ks = jax.random.split(rng, 4)
    b, h, dk, dv = 2, 4, 32, 64
    q = jax.random.normal(ks[0], (b, h, dk)) * 0.3
    k = jax.random.normal(ks[1], (b, h, dk)) * 0.3
    v = jax.random.normal(ks[2], (b, h, dv)) * 0.5
    la_ = -jnp.abs(jax.random.normal(ks[3], (b, h))) * 0.05
    st = jax.random.normal(ks[0], (b, h, dk, dv)).astype(jnp.float32)
    ld = jnp.zeros((b, h), jnp.float32)
    o1, s1, l1 = ops.linear_decode_op(q, k, v, la_, st, ld, backend="xla")
    o2, s2, l2 = ops.linear_decode_op(q, k, v, la_, st, ld,
                                      backend="interpret")
    np.testing.assert_allclose(o1, o2, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(s1, s2, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(l1, l2, rtol=1e-6, atol=1e-6)


def test_ops_dispatch_linear(rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (2, 4, 256, 32)) * 0.3
    k = jax.random.normal(ks[1], (2, 4, 256, 32)) * 0.3
    v = jax.random.normal(ks[2], (2, 4, 256, 32)) * 0.5
    o_xla, st_xla, _ = ops.linear_attention_op(q, k, v, backend="xla")
    o_int, st_int, _ = ops.linear_attention_op(q, k, v, backend="interpret")
    np.testing.assert_allclose(o_xla, o_int, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(st_xla, st_int, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("s", [17, 129, 251])
def test_ops_linear_awkward_lengths(rng, s):
    """Arbitrary (incl. prime) prompt lengths must keep full-size blocks
    via zero right-padding — output, state and log decay stay exact."""
    from repro.core import linear_attention as la
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (1, 2, s, 16)) * 0.3
    k = jax.random.normal(ks[1], (1, 2, s, 16)) * 0.3
    v = jax.random.normal(ks[2], (1, 2, s, 24)) * 0.5
    la_ = -jnp.abs(jax.random.normal(ks[3], (1, 2, s))) * 0.05
    ref = la.sequential_oracle(q, k, v, la_)
    for backend in ("xla", "interpret"):
        o, st, ld = ops.linear_attention_op(q, k, v, la_, block_size=128,
                                            backend=backend)
        assert o.shape[-2] == s
        np.testing.assert_allclose(o, ref.o, rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(st, ref.state, rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(ld, ref.log_decay, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 64), (False, 64)])
def test_ops_dispatch_flash(rng, causal, window):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (2, 4, 256, 64)) * 0.4
    k = jax.random.normal(ks[1], (2, 2, 256, 64)) * 0.4
    v = jax.random.normal(ks[2], (2, 2, 256, 64)) * 0.5
    o_xla = ops.flash_attention_op(q, k, v, causal=causal,
                                   sliding_window=window, backend="xla")
    o_int = ops.flash_attention_op(q, k, v, causal=causal,
                                   sliding_window=window,
                                   backend="interpret")
    np.testing.assert_allclose(o_xla, o_int, rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# Kernel gradients: the lasp2_chunk custom_vjp backward kernels.
# ---------------------------------------------------------------------------

def _grad_case(rng, s=256, dk=32, dv=48, scale=0.05):
    ks = jax.random.split(rng, 7)
    b, h = 2, 3
    q = jax.random.normal(ks[0], (b, h, s, dk)) * 0.3
    k = jax.random.normal(ks[1], (b, h, s, dk)) * 0.3
    v = jax.random.normal(ks[2], (b, h, s, dv)) * 0.5
    la_ = -jnp.abs(jax.random.normal(ks[3], (b, h, s))) * scale
    cot = (jax.random.normal(ks[4], (b, h, s, dv)),       # dO
           jax.random.normal(ks[5], (b, h, dk, dv)),      # dM (state)
           jax.random.normal(ks[6], (b, h)))              # dA (log decay)
    return q, k, v, la_, cot


def _op_loss(backend, cot, block_size=64):
    co, cs, cl = cot

    def loss(q, k, v, la_):
        o, st, ld = ops.linear_attention_op(q, k, v, la_,
                                            block_size=block_size,
                                            backend=backend)
        return (jnp.sum(o.astype(jnp.float32) * co) + jnp.sum(st * cs)
                + jnp.sum(ld * cl))

    return loss


@pytest.mark.parametrize("decay", [False, True, "resets"])
def test_lasp2_chunk_grads_match_chunk_scan_autodiff(rng, decay):
    """jax.grad through the Pallas custom_vjp (interpret) == XLA autodiff
    of chunk_scan, pulling on ALL THREE outputs (o, state, log_decay) —
    the faithful SP backward pulls on o and state; data-dependent decay
    additionally needs d log_a. ``resets``: document starts (state resets)
    on the first token of a tile, on a chunk boundary inside a tile and in
    the middle of a chunk, over two tiles of several 128-token chunks."""
    block = 64
    if decay == "resets":
        block, s = 128, 2 * MAX_TILE
        tile = seq_tile(s, block)
        assert block < tile < s
        q, k, v, la_, cot = _grad_case(rng, s=s, dk=16, dv=16)
        la_ = la_.at[..., [block, tile // 2 + 37, tile]].set(RESET_LOG_A)
    else:
        q, k, v, la_, cot = _grad_case(rng)
    if not decay:
        la_ = jnp.zeros_like(la_)
    g_int = jax.grad(_op_loss("interpret", cot, block),
                     argnums=(0, 1, 2, 3))(q, k, v, la_)
    g_xla = jax.grad(_op_loss("xla", cot, block), argnums=(0, 1, 2, 3))(
        q, k, v, la_)
    for name, gi, gx in zip("q k v log_a".split(), g_int, g_xla):
        np.testing.assert_allclose(gi, gx, rtol=GRAD_TOL, atol=GRAD_TOL,
                                   err_msg=f"d{name}")


def test_lasp2_chunk_grads_match_sequential_oracle(rng):
    """Same gradients vs the O(S) oracle (independent derivation)."""
    from repro.core import linear_attention as la
    q, k, v, la_, cot = _grad_case(rng, s=128)
    co, cs, cl = cot

    def oracle_loss(q_, k_, v_, a_):
        out = la.sequential_oracle(q_, k_, v_, a_)
        return (jnp.sum(out.o.astype(jnp.float32) * co)
                + jnp.sum(out.state * cs) + jnp.sum(out.log_decay * cl))

    g_int = jax.grad(_op_loss("interpret", cot), argnums=(0, 1, 2, 3))(
        q, k, v, la_)
    g_ref = jax.grad(oracle_loss, argnums=(0, 1, 2, 3))(q, k, v, la_)
    for name, gi, gr in zip("q k v log_a".split(), g_int, g_ref):
        np.testing.assert_allclose(gi, gr, rtol=GRAD_TOL, atol=GRAD_TOL,
                                   err_msg=f"d{name}")


def test_lasp2_chunk_grads_state_cotangent_only(rng):
    """Pulling ONLY on the end-of-chunk state (the Alg. 4 dM path)."""
    q, k, v, la_, cot = _grad_case(rng, s=128)
    cot = (jnp.zeros_like(cot[0]), cot[1], jnp.zeros_like(cot[2]))
    g_int = jax.grad(_op_loss("interpret", cot), argnums=(0, 1, 2, 3))(
        q, k, v, la_)
    g_xla = jax.grad(_op_loss("xla", cot), argnums=(0, 1, 2, 3))(
        q, k, v, la_)
    assert float(jnp.max(jnp.abs(g_int[0]))) == 0.0   # dq: o untouched
    for name, gi, gx in zip("q k v log_a".split(), g_int, g_xla):
        np.testing.assert_allclose(gi, gx, rtol=GRAD_TOL, atol=GRAD_TOL,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("s", [97, 130])
def test_lasp2_chunk_grads_padding_path(rng, s):
    """Awkward (non-block-multiple) lengths differentiate through the
    zero-padding path in ops.linear_attention_op."""
    q, k, v, la_, _ = _grad_case(rng, s=s, dk=16, dv=16)
    ks = jax.random.split(rng, 2)
    co = jax.random.normal(ks[0], q.shape[:-1] + (16,))
    cs = jax.random.normal(ks[1], q.shape[:2] + (16, 16))
    cot = (co, cs, jnp.zeros(q.shape[:2]))
    g_int = jax.grad(_op_loss("interpret", cot), argnums=(0, 1, 2, 3))(
        q, k, v, la_)
    g_xla = jax.grad(_op_loss("xla", cot), argnums=(0, 1, 2, 3))(
        q, k, v, la_)
    for name, gi, gx in zip("q k v log_a".split(), g_int, g_xla):
        np.testing.assert_allclose(gi, gx, rtol=GRAD_TOL, atol=GRAD_TOL,
                                   err_msg=f"d{name}")


def test_lasp2_chunk_grad_bf16_inputs(rng):
    """bf16 q/k/v: cotangents flow back in bf16 with fp32 kernel math."""
    q, k, v, la_, cot = _grad_case(rng, s=128)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    g_int = jax.grad(_op_loss("interpret", cot), argnums=(0, 1, 2))(
        qb, kb, vb, la_)
    g_xla = jax.grad(_op_loss("xla", cot), argnums=(0, 1, 2))(
        qb, kb, vb, la_)
    for gi, gx in zip(g_int, g_xla):
        assert gi.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(gi, np.float32),
                                   np.asarray(gx, np.float32),
                                   rtol=4e-2, atol=4e-2)


# ---------------------------------------------------------------------------
# Flash-attention causal offset (sq != sk shapes).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,sk,window", [(128, 256, None), (64, 256, None),
                                          (128, 256, 96)])
def test_flash_offset_matches_xla_mask(rng, sq, sk, window):
    """Regression: for sq < sk (prefill-with-cache / ring-decode shapes)
    query row i sits at global position (sk - sq) + i. The Pallas kernel
    used to mask with LOCAL q indices — each query then saw only the
    first sq keys instead of its full causal prefix."""
    from repro.core.lasp2h import _softmax_attend, causal_mask
    b, hq, hkv, dh = 2, 4, 2, 64
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, hq, sq, dh)) * 0.4
    k = jax.random.normal(ks[1], (b, hkv, sk, dh)) * 0.4
    v = jax.random.normal(ks[2], (b, hkv, sk, dh)) * 0.5
    mask = causal_mask(sq, sk, q_offset=sk - sq,
                       sliding_window=window)[None, None]
    ref = _softmax_attend(q, k, v, scale=dh ** -0.5, mask=mask)
    o_int = ops.flash_attention_op(q, k, v, causal=True,
                                   sliding_window=window, block_q=64,
                                   block_k=64, backend="interpret")
    np.testing.assert_allclose(o_int, ref, rtol=3e-4, atol=3e-4)
    # the XLA fallback and the kernel now share one mask convention
    o_xla = ops.flash_attention_op(q, k, v, causal=True,
                                   sliding_window=window, backend="xla")
    np.testing.assert_allclose(o_int, o_xla, rtol=3e-4, atol=3e-4)
    # sanity: with the bug, the last query ignored keys in
    # [sq, q_offset + row] — perturbing one of those must change o.
    if window is None:
        v2 = v.at[:, :, sk - 2].add(1.0)
        o2 = ops.flash_attention_op(q, k, v2, causal=True, block_q=64,
                                    block_k=64, backend="interpret")
        assert float(jnp.max(jnp.abs(o2 - o_int))) > 1e-3


def _vmem_bytes(shape, dtype):
    """Bytes of a block in VMEM: the last two dims padded to the (8, 128)
    tiling (16 sublanes for 16-bit types)."""
    itemsize = np.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    sub = 8 * 4 // itemsize
    return (int(np.prod(lead)) * -(-rows // sub) * sub
            * -(-lanes // 128) * 128 * itemsize)


def test_kernel_vmem_footprint_static(monkeypatch):
    """BlockSpec tiles must fit VMEM (16 MB/core budget, fp32 scratch).
    The chunk kernels' footprint is read from their own ``pallas_call``s at
    the ``linear-train-8k`` shape (B·H 16, S 8192, d 128, bf16), in bf16 and
    in fp32 inputs: every in/out block double-buffered, plus scratch."""
    from jax.experimental import pallas as pl

    from repro.kernels.lasp2_chunk import lasp2_chunk

    bq, bk, dh = 128, 128, 128
    flash_tiles = (bq * dh + 2 * bk * dh + bq * dh) * 4 + bq * dh * 4
    # flash bwd dkv pass: q/k/v/do tiles + lse/delta rows + 2 accumulators
    flash_bwd_tiles = (2 * bq * dh + 2 * bk * dh + 2 * bq) * 4 \
        + 2 * bk * dh * 4
    assert flash_tiles < 16 * 2 ** 20
    assert flash_bwd_tiles < 16 * 2 ** 20

    calls = {}

    def capture(kernel, *, grid, in_specs, out_specs, out_shape,
                scratch_shapes=(), name=None, **kw):
        def run(*operands):
            outs = out_shape if isinstance(out_shape, list) else [out_shape]
            blocks = ([(sp.block_shape, op.dtype)
                       for sp, op in zip(in_specs, operands)]
                      + [(sp.block_shape, o.dtype) for sp, o in zip(
                          out_specs if isinstance(out_specs, list)
                          else [out_specs], outs)])
            calls[name, operands[0].dtype] = (grid, blocks, [
                (sc.shape, sc.dtype) for sc in scratch_shapes])
            zeros = [jnp.zeros(o.shape, o.dtype) for o in outs]
            return zeros if isinstance(out_shape, list) else zeros[0]
        return run

    def loss(q, k, v, la_):
        o, st, ld = lasp2_chunk(q, k, v, la_)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(st) + jnp.sum(ld)

    bh, s, d = 16, 8192, 128
    monkeypatch.setattr(pl, "pallas_call", capture)
    jax.clear_caches()
    try:
        for dtype in (jnp.bfloat16, jnp.float32):
            x = jax.ShapeDtypeStruct((bh, s, d), dtype)
            jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2, 3)), x, x, x,
                           jax.ShapeDtypeStruct((bh, s), jnp.float32))
    finally:
        jax.clear_caches()      # drop the traces that hold the stand-in
    tile = seq_tile(s, 128)
    assert len(calls) == 6
    for (name, dtype), (grid, blocks, scratch) in calls.items():
        assert grid == (bh, s // tile), name
        # sequence blocks are tiles of T tokens; the state is one block
        assert all(tile in shape for shape, _ in blocks
                   if shape != (1, d, d)), name
        footprint = (2 * sum(_vmem_bytes(*b) for b in blocks)
                     + sum(_vmem_bytes(*sc) for sc in scratch))
        assert footprint < 16 * 2 ** 20, (name, dtype, footprint)


def test_seq_tile_rule():
    """Tokens per grid step of the chunk kernels, a static choice per
    shape. At the ``linear-train-8k`` shape (S 8192, chunk 128) it is the
    tile PERF.md records: 1024 tokens, 8 steps a row instead of 64."""
    assert seq_tile(8192, 128) == 1024
    # lengths the kernel is not given as such (ops pads them first): no
    # multiple of the chunk divides them, so the tile is the chunk
    for s in (129, 251):
        assert seq_tile(s, 128) == 128
    assert seq_tile(17, 17) == 17                 # a short prompt: one chunk
    assert seq_tile(256, 128) == 256              # 129 and 251, padded
    assert seq_tile(61 * 128, 128) == 128         # a prime chunk count
    for c in (32, 64, 100, 128):
        for s in range(c, 70 * c + 1, c):
            t = seq_tile(s, c)
            assert s % t == 0 and t % c == 0 and c <= t <= max(c, MAX_TILE)
            assert t == c or t % 128 == 0 or t == s


# ---------------------------------------------------------------------------
# Flash-attention gradients: the custom_vjp two-pass backward kernels.
# ---------------------------------------------------------------------------

def _flash_case(rng, sq, sk, hq, hkv, dh, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    b = 2
    q = (jax.random.normal(ks[0], (b, hq, sq, dh)) * 0.4).astype(dtype)
    k = (jax.random.normal(ks[1], (b, hkv, sk, dh)) * 0.4).astype(dtype)
    v = (jax.random.normal(ks[2], (b, hkv, sk, dh)) * 0.5).astype(dtype)
    co = jax.random.normal(ks[3], (b, hq, sq, dh))
    return q, k, v, co


def _flash_loss(backend, co, causal, window, **kw):
    def loss(q, k, v):
        o = ops.flash_attention_op(q, k, v, causal=causal,
                                   sliding_window=window, backend=backend,
                                   **kw)
        return jnp.sum(o.astype(jnp.float32) * co)
    return loss


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 64)])
def test_flash_grads_match_xla_autodiff(rng, hq, hkv, causal, window):
    """jax.grad through the flash custom_vjp (interpret) == XLA autodiff
    of the masked-softmax fallback, across GQA ratios and windows."""
    q, k, v, co = _flash_case(rng, 256, 256, hq, hkv, 64)
    kw = dict(block_q=64, block_k=64)
    g_int = jax.grad(_flash_loss("interpret", co, causal, window, **kw),
                     argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(_flash_loss("xla", co, causal, window),
                     argnums=(0, 1, 2))(q, k, v)
    for name, gi, gx in zip("q k v".split(), g_int, g_xla):
        np.testing.assert_allclose(gi, gx, rtol=GRAD_TOL, atol=GRAD_TOL,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("sq,sk,window,block", [
    (128, 256, None, 64), (64, 256, 96, 64), (128, 512, None, 64),
    (512, 1536, 384, None)])
def test_flash_grads_offset_shapes(rng, sq, sk, window, block):
    """sq != sk (prefill-with-cache q_offset = sk - sq) backward parity,
    at 64-row blocks and at the picked tiles (fp32, window 384: 128 × 128,
    the window band trimmed over several kv tiles)."""
    q, k, v, co = _flash_case(rng, sq, sk, 4, 2, 64)
    kw = dict(block_q=block, block_k=block)
    g_int = jax.grad(_flash_loss("interpret", co, True, window, **kw),
                     argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(_flash_loss("xla", co, True, window),
                     argnums=(0, 1, 2))(q, k, v)
    for name, gi, gx in zip("q k v".split(), g_int, g_xla):
        np.testing.assert_allclose(gi, gx, rtol=GRAD_TOL, atol=GRAD_TOL,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("sq,sk,block", [
    (100, 100, 64), (129, 257, 64), (251, 251, 64), (100, 100, None),
    (129, 257, None), (700, 700, None)])
def test_flash_grads_awkward_lengths(rng, sq, sk, block):
    """Odd (non-block-multiple) lengths run the Pallas path via the
    mask-safe pad+slice in ops.flash_attention_op — forward AND backward
    (padded-key grads masked to zero, padded-query cotangents sliced).
    With picked tiles a length pads to the next multiple of 128 (700 to
    768, two fp32 tiles of 384) or is one tile (100)."""
    q, k, v, co = _flash_case(rng, sq, sk, 4, 2, 32)
    kw = dict(block_q=block, block_k=block)
    o_int = ops.flash_attention_op(q, k, v, backend="interpret", **kw)
    o_xla = ops.flash_attention_op(q, k, v, backend="xla")
    assert o_int.shape[-2] == sq
    np.testing.assert_allclose(o_int, o_xla, rtol=3e-4, atol=3e-4)
    g_int = jax.grad(_flash_loss("interpret", co, True, None, **kw),
                     argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(_flash_loss("xla", co, True, None),
                     argnums=(0, 1, 2))(q, k, v)
    for name, gi, gx in zip("q k v".split(), g_int, g_xla):
        np.testing.assert_allclose(gi, gx, rtol=GRAD_TOL, atol=GRAD_TOL,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("sq,sk,dh,dtype,block", [
    (64, 256, 32, jnp.float32, 64), (1024, 2048, 64, jnp.bfloat16, None),
    (512, 1024, 64, jnp.float32, None)])
def test_flash_grads_traced_q_offset(rng, sq, sk, dh, dtype, block):
    """The LASP-2H sharded path passes the rank offset t·C as a traced
    scalar: the kernel masks at runtime (band untrimmed) and the
    custom_vjp returns a float0 cotangent for it. At picked tiles (bf16:
    1024 × 1024, fp32: 512 × 512) the offset sk - sq puts a full tile and
    a diagonal-crossing one in a call, and an offset off the tile grid
    masks both; bf16 is held to the fp32 reference of its own values."""
    q, k, v, co = _flash_case(rng, sq, sk, 4, 2, dh, dtype=dtype)
    up = [x.astype(jnp.float32) for x in (q, k, v)]
    tol = GRAD_TOL if dtype == jnp.float32 else 4e-2
    for off in (0, (sk - sq) // 3, sk - sq):
        gi = jax.jit(jax.grad(
            lambda a, b, c, o_: jnp.sum(ops.flash_attention_op(
                a, b, c, causal=True, backend="interpret", block_q=block,
                block_k=block, q_offset=o_).astype(jnp.float32) * co),
            argnums=(0, 1, 2)))(q, k, v, jnp.int32(off))
        gx = jax.grad(
            lambda a, b, c: jnp.sum(ops.flash_attention_op(
                a, b, c, causal=True, backend="xla", q_offset=off) * co),
            argnums=(0, 1, 2))(*up)
        for name, a_, b_ in zip("q k v".split(), gi, gx):
            assert a_.dtype == dtype
            np.testing.assert_allclose(np.asarray(a_, np.float32), b_,
                                       rtol=tol, atol=tol,
                                       err_msg=f"d{name} @offset {off}")


@pytest.mark.parametrize("block", [64, None])
def test_flash_grads_bf16_inputs(rng, block):
    """bf16 q/k/v: cotangents flow back in bf16 with fp32 kernel math."""
    q, k, v, co = _flash_case(rng, 128, 128, 4, 2, 64, dtype=jnp.bfloat16)
    kw = dict(block_q=block, block_k=block)
    g_int = jax.grad(_flash_loss("interpret", co, True, None, **kw),
                     argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(_flash_loss("xla", co, True, None),
                     argnums=(0, 1, 2))(q, k, v)
    for gi, gx in zip(g_int, g_xla):
        assert gi.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(gi, np.float32),
                                   np.asarray(gx, np.float32),
                                   rtol=4e-2, atol=4e-2)


def test_flash_mask_value_dtype_aware():
    """The masked-logit fill is finfo-derived (no -1e30 literal): finite
    in every float dtype, including fp16 where -1e30 overflows."""
    from repro.kernels.flash_attention import mask_value
    for dt in (jnp.float32, jnp.bfloat16, jnp.float16):
        mv = mask_value(dt)
        assert np.isfinite(np.asarray(mv, dt)), dt
        assert mv < -1e4
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.float16(-1e30))   # the literal it replaces


def test_flash_causal_band_static_trim():
    """Causal grid trimming: the kv band never schedules blocks strictly
    above the diagonal — with a sliding window the band is narrower than
    the kv axis; fully-padded kv blocks are excluded via kv_len."""
    from repro.kernels.flash_attention import _kv_band, _q_band
    # causal, no window, q_offset=0: widest extent = full prefix
    lo, hi, w = _kv_band(nq=4, nkv_real=4, block_q=64, block_k=64,
                         q_offset=0, causal=True, sliding_window=None)
    assert w == 4 and int(hi(0)) == 0 and int(hi(3)) == 3
    # sliding window 64: each q block needs <= 2 kv blocks — real trim
    lo, hi, w = _kv_band(nq=8, nkv_real=8, block_q=64, block_k=64,
                         q_offset=0, causal=True, sliding_window=64)
    assert w == 2
    assert int(lo(4)) == 3 and int(hi(4)) == 4
    # right-padded keys (kv_len < sk): padded blocks never scheduled
    lo, hi, w = _kv_band(nq=2, nkv_real=2, block_q=64, block_k=64,
                         q_offset=0, causal=False, sliding_window=None)
    assert w == 2
    # transposed (dk/dv) band under a window is likewise narrow
    lo, hi, w = _q_band(nq=8, nkv=8, block_q=64, block_k=64, q_offset=0,
                        causal=True, sliding_window=64)
    assert w == 2


@pytest.mark.parametrize("sq,sk,window,dtype,tiles", [
    # one rank of hybrid-train-sp4-64k: q 16,384 against K/V 65,536
    (16384, 65536, None, jnp.bfloat16, (1024, 1024)),
    (16384, 65536, None, jnp.float32, (512, 512)),
    # a short prefill against its cache: 1408 = 11 × 128
    (384, 1408, None, jnp.bfloat16, (384, 128)),
    (1536, 4096, None, jnp.bfloat16, (768, 1024)),
    # a 100-token prompt is one whole tile; padded lengths
    (100, 100, None, jnp.bfloat16, (100, 100)),
    (768, 768, None, jnp.float32, (384, 384)),
    # the 1/4 hybrid's window 2048 and smaller windows: a quarter of it
    (4096, 4096, 2048, jnp.bfloat16, (512, 512)),
    (4096, 4096, 1024, jnp.float32, (256, 256)),
    (4096, 4096, 64, jnp.bfloat16, (128, 128)),
])
def test_flash_pick_tiles(sq, sk, window, dtype, tiles):
    from repro.kernels.flash_attention import pick_tiles
    assert pick_tiles(sq, sk, sliding_window=window, dtype=dtype) == tiles


def test_flash_pick_tiles_divide_padded_lengths():
    """Every picked tile divides its (128-padded) length and is a 128
    multiple, or is the whole length when that is at most 128; the
    dispatch pads an odd length by less than 128."""
    from repro.kernels.flash_attention import (MAX_TILE_K, MAX_TILE_Q,
                                               pick_tiles)
    for n in list(range(1, 300)) + list(range(300, 70000, 997)):
        padded = n if n <= 128 else -(-n // 128) * 128
        assert padded - n < 128
        bq, bk = pick_tiles(padded, padded)
        for t, cap in ((bq, MAX_TILE_Q), (bk, MAX_TILE_K)):
            assert padded % t == 0
            assert t == padded <= 128 or (t % 128 == 0 and t <= cap)


def test_flash_picked_tiles_reach_the_kernel(monkeypatch):
    """flash_attention_op hands the kernel the picked tiles (bf16, window
    2048: 512 × 512 at 4096) and honours explicit ones."""
    from repro.kernels import flash_attention as fa
    seen = []
    real = fa._flash

    def spy(*a):
        seen.append(a[9:11])
        return real(*a)

    monkeypatch.setattr(fa, "_flash", spy)
    q = jnp.zeros((1, 1, 4096, 128), jnp.bfloat16)
    jax.eval_shape(lambda x: ops.flash_attention_op(
        x, x, x, sliding_window=2048, backend="interpret"), q)
    jax.eval_shape(lambda x: ops.flash_attention_op(
        x, x, x, block_q=64, block_k=256, backend="interpret"), q)
    assert seen == [(512, 512), (64, 256)]


def test_flash_two_term_products():
    """An fp32 tile against a bf16 one goes to the MXU as two bf16 terms:
    the product keeps 16 significant bits of the fp32 side, far closer to
    the fp32 product than one bf16 rounding of it."""
    from repro.kernels.flash_attention import _NN, _dot
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    p = jax.random.uniform(ks[0], (128, 256), jnp.float32)
    v = jax.random.normal(ks[1], (256, 128), jnp.float32).astype(
        jnp.bfloat16)
    exact = np.asarray(p, np.float64) @ np.asarray(v, np.float64)
    two = np.asarray(_dot(p, v, _NN), np.float64)
    one = np.asarray(_dot(p.astype(jnp.bfloat16), v, _NN), np.float64)
    scale = np.max(np.abs(exact))
    err_two = np.max(np.abs(two - exact)) / scale
    err_one = np.max(np.abs(one - exact)) / scale
    assert err_two < 2e-5, err_two
    assert err_one > 30 * err_two, (err_one, err_two)
