"""Every Pallas kernel compiles for a TPU v5e at Linear-Llama3-1B widths.

The chip's compiler is installed here and compiles for a chip that is
described, not attached: it refuses what interpret mode accepts (block
shapes off the (8, 128) tiling, ops with no Mosaic lowering, scalar
stores to vector memory, kernels that need more VMEM than a core has).
Nothing runs; each case asserts that the compiled module holds a
``tpu_custom_call`` per kernel it dispatches. The topology is described
inside a fixture, never at import: only one process at a time may load
the TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lasp2_chunk import lasp2_chunk, lasp2_chunk_fwd
from repro.kernels.lasp2_decode import lasp2_decode_step
from repro.launch.hlo_analysis import tpu_kernels

BH, S, D = 16, 4096, 128        # Linear-Llama3-1B: 16 heads of 128, 4k ctx
S_TRAIN = 8192                  # the linear-train-8k row
WINDOW = 2048                   # the 1/4 hybrid's softmax window
SLOTS = 4                       # decode batch
ODD = 100                       # a prompt length off every block multiple
S_HYBRID = 65536                # the hybrid-train-sp4-64k row, over 4 chips


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _chunk_fwd(sds, s=S):
    q = sds((BH, s, D))
    return (lambda q_, k_, v_, la_: lasp2_chunk_fwd(q_, k_, v_, la_),
            (q, q, q, sds((BH, s), jnp.float32)))


def _chunk_grad(sds, s=S):
    def loss(q_, k_, v_, la_):
        o, st, ld = lasp2_chunk(q_, k_, v_, la_)
        return (jnp.sum(o.astype(jnp.float32)) + jnp.sum(st)
                + jnp.sum(ld))
    q = sds((BH, s, D))
    return (jax.grad(loss, argnums=(0, 1, 2, 3)),
            (q, q, q, sds((BH, s), jnp.float32)))


def _decode(sds):
    n = SLOTS * BH
    q = sds((n, D))
    return (lambda *a: lasp2_decode_step(*a),
            (q, q, q, sds((n,), jnp.float32), sds((n, D, D), jnp.float32),
             sds((n,), jnp.float32)))


def _flash_fwd(sds):
    q = sds((1, BH, S, D))
    return (lambda q_, k_, v_: flash_attention(q_, k_, v_,
                                               sliding_window=WINDOW),
            (q, q, q))


def _flash_grad(sds):
    def loss(q_, k_, v_):
        o = flash_attention(q_, k_, v_, sliding_window=WINDOW)
        return jnp.sum(o.astype(jnp.float32))
    q = sds((1, BH, S, D))
    return jax.grad(loss, argnums=(0, 1, 2)), (q, q, q)


def _flash_grad_traced_offset(sds, s=S, dtype=jnp.bfloat16):
    """The LASP-2H sequence-parallel path: the rank offset is traced."""
    def loss(q_, k_, v_, off):
        o = flash_attention(q_, k_, v_, q_offset=off)
        return jnp.sum(o.astype(jnp.float32))
    q = sds((1, BH, s // 4, D), dtype)
    kv = sds((1, BH, s, D), dtype)
    return (jax.grad(loss, argnums=(0, 1, 2)),
            (q, kv, kv, sds((), jnp.int32)))


def _flash_grad_prefill(sds):
    """A prefill against its cache: static offset sk - sq, the window."""
    def loss(q_, k_, v_):
        o = flash_attention(q_, k_, v_, sliding_window=WINDOW)
        return jnp.sum(o.astype(jnp.float32))
    kv = sds((1, BH, S, D))
    return jax.grad(loss, argnums=(0, 1, 2)), (sds((1, BH, S // 4, D)),
                                               kv, kv)


def _odd_prompt_grads(sds):
    """An odd prompt length through the dispatch serving uses: blocks are
    the whole (unaligned) sequence, or the sequence is padded."""
    def loss(q_, k_, v_):
        o, _, _ = ops.linear_attention_op(q_, k_, v_, backend="pallas")
        a = ops.flash_attention_op(q_, k_, v_, sliding_window=WINDOW,
                                   backend="pallas")
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(
            a.astype(jnp.float32))
    q = sds((SLOTS, BH, ODD, D))
    return jax.grad(loss, argnums=(0, 1, 2)), (q, q, q)


def _short_chunk_grads(sds):
    """A prompt of three 64-token chunks (S 192, no 128-token divisor):
    one tile holds the whole sequence, so the (1, 1, T) log-decay rows
    span the array."""
    def loss(q_, k_, v_):
        o, st, _ = ops.linear_attention_op(q_, k_, v_, backend="pallas")
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(st)
    q = sds((SLOTS, BH, 192, D))
    return jax.grad(loss, argnums=(0, 1, 2)), (q, q, q)


_FLASH = {"flash_attention_fwd", "flash_attention_bwd_dq",
          "flash_attention_bwd_dkv"}
CASES = {
    "lasp2_chunk fwd": (_chunk_fwd, {"lasp2_chunk_fwd"}),
    "lasp2_chunk grad": (_chunk_grad, {"lasp2_chunk_fwd",
                                       "lasp2_chunk_bwd_dq",
                                       "lasp2_chunk_bwd_dkv"}),
    # the linear-train-8k row: tiles of several chunks a grid step
    "lasp2_chunk fwd 8k": (functools.partial(_chunk_fwd, s=S_TRAIN),
                           {"lasp2_chunk_fwd"}),
    "lasp2_chunk grad 8k": (functools.partial(_chunk_grad, s=S_TRAIN),
                            {"lasp2_chunk_fwd", "lasp2_chunk_bwd_dq",
                             "lasp2_chunk_bwd_dkv"}),
    "lasp2_decode_step": (_decode, {"lasp2_decode_step"}),
    "flash fwd": (_flash_fwd, {"flash_attention_fwd"}),
    "flash grad": (_flash_grad, _FLASH),
    "flash grad traced offset": (_flash_grad_traced_offset, _FLASH),
    # one rank of hybrid-train-sp4-64k: q 16,384 against K/V 65,536
    "flash grad traced offset 64k": (
        functools.partial(_flash_grad_traced_offset, s=S_HYBRID), _FLASH),
    # fp32 inputs pick smaller tiles and keep fp32 products
    "flash grad traced offset 64k fp32": (
        functools.partial(_flash_grad_traced_offset, s=S_HYBRID,
                          dtype=jnp.float32), _FLASH),
    "flash grad prefill": (_flash_grad_prefill, _FLASH),
    "three 64-token chunks grads": (_short_chunk_grads, {
        "lasp2_chunk_fwd", "lasp2_chunk_bwd_dq", "lasp2_chunk_bwd_dkv"}),
    "odd prompt length grads": (_odd_prompt_grads, _FLASH | {
        "lasp2_chunk_fwd", "lasp2_chunk_bwd_dq", "lasp2_chunk_bwd_dkv"}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    build, expected = CASES[case]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = build(sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert set(tpu_kernels(compiled.as_text())) == expected
