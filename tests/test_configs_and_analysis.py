"""Config registry sanity + HLO-analysis unit tests."""

import numpy as np
import pytest

from repro.configs import ALL_IDS, ARCH_IDS, get_config, get_smoke
from repro.configs.base import SHAPES
from repro.launch import hlo_analysis as H
from repro.launch.mesh import DATA_AXIS, SEQ_AXIS

EXPECT_B = {"codeqwen1.5-7b": 7.2, "qwen1.5-110b": 111, "granite-34b": 34,
            "starcoder2-15b": 15, "hymba-1.5b": 1.5, "mamba2-2.7b": 2.7,
            "llama-3.2-vision-90b": 88, "moonshot-v1-16b-a3b": 29,
            "phi3.5-moe-42b-a6.6b": 42, "whisper-base": 0.072,
            "linear-llama3-1b": 1.3}


def test_registry_complete():
    assert len(ARCH_IDS) == 10
    assert len(SHAPES) == 4          # 40 cells
    for a in ALL_IDS:
        cfg = get_config(a)
        assert cfg.padded_vocab % 128 == 0
        assert get_smoke(a).param_count() < 5e6


@pytest.mark.parametrize("arch", ALL_IDS)
def test_param_counts_in_band(arch):
    n = get_config(arch).param_count() / 1e9
    lo, hi = 0.55 * EXPECT_B[arch], 1.5 * EXPECT_B[arch]
    assert lo <= n <= hi, f"{arch}: {n:.2f}B outside [{lo:.1f},{hi:.1f}]"


def test_exact_assigned_dims():
    c = get_config("qwen1.5-110b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (80, 8192, 64, 8, 49152, 152064)
    assert c.qkv_bias
    m = get_config("mamba2-2.7b")
    assert (m.n_layers, m.d_model, m.mamba.d_state) == (64, 2560, 128)
    assert m.d_ff == 0
    h = get_config("hymba-1.5b")
    assert (h.d_model, h.n_heads, h.n_kv_heads, h.vocab_size,
            h.mamba.d_state) == (1600, 25, 5, 32001, 16)
    mo = get_config("moonshot-v1-16b-a3b")
    assert (mo.moe.num_experts, mo.moe.top_k) == (64, 6)
    ph = get_config("phi3.5-moe-42b-a6.6b")
    assert (ph.moe.num_experts, ph.moe.top_k) == (16, 2)


# --- HLO analysis unit tests -------------------------------------------------

FAKE_HLO = """
ENTRY %main {
  %ag = f32[8,2,4,32,64]{4,3,2,1,0} all-gather(%x), channel_id=1, replica_groups=[16,16]<=[256], dimensions={0}
  %ar = bf16[1024]{0} all-reduce(%y), replica_groups={{0,1,2,3}}, to_apply=%sum
  %rs = f32[128]{0} reduce-scatter(%z), replica_groups=[32,8]<=[256], dimensions={0}
  %cp = bf16[64,64]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
  %ags = (f32[4,8], f32[32,8]) all-gather-start(%v), replica_groups=[2,8]<=[16], dimensions={0}
  %agd = f32[32,8] all-gather-done(%ags)
}
"""


def test_parse_collectives():
    colls = H.parse_collectives(FAKE_HLO, 256)
    ops = sorted(c.op for c in colls)
    assert ops == ["all-gather", "all-gather", "all-reduce",
                   "collective-permute", "reduce-scatter"]
    ag = next(c for c in colls if c.op == "all-gather"
              and c.result_bytes == 8 * 2 * 4 * 32 * 64 * 4)
    assert ag.group_size == 16
    ar = next(c for c in colls if c.op == "all-reduce")
    assert ar.result_bytes == 1024 * 2 and ar.group_size == 4
    # start op: tuple type → only the result half counted
    ags = next(c for c in colls if c.op == "all-gather"
               and c.group_size == 8)
    assert ags.result_bytes == (4 * 8 + 32 * 8) * 4 // 2


def test_traffic_model():
    c = H.Collective("all-reduce", 1000, 4)
    assert abs(c.traffic_bytes - 2 * 3 / 4 * 1000) < 1e-9
    c = H.Collective("all-gather", 1600, 16)
    assert abs(c.traffic_bytes - 15 / 16 * 1600) < 1e-9
    c = H.Collective("reduce-scatter", 100, 8)
    assert abs(c.traffic_bytes - 700) < 1e-9


def test_cost_vector_algebra():
    a = H.CostVector(10, 20, 5, {"all-gather": 5})
    b = H.CostVector(1, 2, 1, {"all-gather": 1})
    c = (a - b).scale(3) + b
    assert c.flops == 28 and c.hbm_bytes == 56
    assert c.coll_by_op["all-gather"] == 13


def test_roofline_terms_dominance():
    t = H.roofline_terms(H.CostVector(
        flops=H.PEAK_FLOPS, hbm_bytes=H.HBM_BW * 2, coll_bytes=H.ICI_BW))
    assert t["dominant"] == "memory"
    np.testing.assert_allclose(t["compute_s"], 1.0)
    np.testing.assert_allclose(t["memory_s"], 2.0)
    np.testing.assert_allclose(t["collective_s"], 1.0)


def test_cost_extrapolation_recovers_linear_model():
    """The roofline's c0 + A(c1 + G·c2) solve is exact for linear costs."""
    c0, c1, c2 = (H.CostVector(5, 7, 1, {}), H.CostVector(11, 3, 2, {}),
                  H.CostVector(2, 9, 4, {}))
    f = lambda a, g: c0 + (c1 + c2.scale(g)).scale(a)
    f11, f12, f21 = f(1, 1), f(1, 2), f(2, 1)
    c2_ = f12 - f11
    c1_ = (f21 - f11) - c2_
    c0_ = f11 - c1_ - c2_
    got = c0_ + (c1_ + c2_.scale(88)).scale(16)
    want = f(16, 88)
    np.testing.assert_allclose(got.flops, want.flops)
    np.testing.assert_allclose(got.hbm_bytes, want.hbm_bytes)
    np.testing.assert_allclose(got.coll_bytes, want.coll_bytes)


# --- per-axis replica-group classification (2D DP×SP budgets) --------------

class _FakeDev:
    def __init__(self, i):
        self.id = i


class _FakeMesh:
    """Stands in for a (2, 4) (data, sequence) mesh: device (d, s) has
    global id d*4 + s (row-major, as make_training_mesh lays out)."""

    axis_names = (DATA_AXIS, SEQ_AXIS)

    @property
    def devices(self):
        return np.array([[_FakeDev(d * 4 + s) for s in range(4)]
                         for d in range(2)])


def test_parse_replica_groups_explicit_and_iota():
    assert H.parse_replica_groups(
        "x = f32[2] all-reduce(y), replica_groups={{0,1,2,3},{4,5,6,7}}"
    ) == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert H.parse_replica_groups(
        "x = f32[2] all-reduce(y), replica_groups=[2,4]<=[8]"
    ) == [[0, 1, 2, 3], [4, 5, 6, 7]]
    # transposed iota: [4,2]<=[2,4]T(1,0) -> columns of the (2,4) layout
    assert H.parse_replica_groups(
        "x = f32[2] all-reduce(y), replica_groups=[4,2]<=[2,4]T(1,0)"
    ) == [[0, 4], [1, 5], [2, 6], [3, 7]]
    assert H.parse_replica_groups("x = f32[2] add(y)") is None
    # XLA's all-devices spellings: absent attribute OR empty braces
    assert H.parse_replica_groups(
        "x = f32[2] all-reduce(y), replica_groups={}, to_apply=%add"
    ) is None
    # collective-permute: source_target_pairs, each pair a 2-device group
    assert H.parse_replica_groups(
        "x = f32[2] collective-permute(y), "
        "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}"
    ) == [[0, 1], [1, 2], [2, 3], [3, 0]]


def test_permute_axis_classification():
    # a ring strictly inside the sequence axis of the (2,4) mesh must NOT
    # be attributed to the data axis
    mesh = _FakeMesh()
    ring = [[0, 1], [1, 2], [2, 3], [3, 0], [4, 5], [5, 6], [6, 7], [7, 4]]
    assert H.group_axes(ring, mesh) == (SEQ_AXIS,)
    hlo = ("%cp = f32[4] collective-permute(f32[4] %p), "
           "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}")
    counts = H.collective_axis_counts(hlo, mesh)
    assert counts == {("collective-permute", (SEQ_AXIS,)): 1}


def test_group_axes_classification():
    mesh = _FakeMesh()
    assert H.group_axes([[0, 1, 2, 3], [4, 5, 6, 7]], mesh) == (SEQ_AXIS,)
    assert H.group_axes([[0, 4], [1, 5], [2, 6], [3, 7]], mesh) \
        == (DATA_AXIS,)
    assert H.group_axes([[0, 1, 2, 3, 4, 5, 6, 7]], mesh) \
        == (DATA_AXIS, SEQ_AXIS)
    # no replica_groups attribute == every non-trivial axis
    assert H.group_axes(None, mesh) == (DATA_AXIS, SEQ_AXIS)


def test_collective_axis_counts_end_to_end():
    hlo = """
HloModule m
  %ag = (f32[1], f32[8]) all-gather-start(f32[1] %p), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %ar = f32[4] all-reduce(f32[4] %q), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %zg = f32[16] all-gather(f32[8] %r), replica_groups={{0,4},{1,5},{2,6},{3,7}}, dimensions={0}
"""
    counts = H.collective_axis_counts(hlo, _FakeMesh())
    assert counts[("all-gather", (SEQ_AXIS,))] == 1
    assert counts[("all-reduce", (DATA_AXIS, SEQ_AXIS))] == 1
    assert counts[("all-gather", (DATA_AXIS,))] == 1


# Shapes of TPU compiled HLO: one async all-gather split into start /
# update / done fusion computations sharing a channel_id, a synchronous
# one, a reduce-scatter written as an all-reduce-scatter fusion, and ring
# permutes that all carry channel_id=1 in the entry computation.
_TPU_HLO = """\
%fused_computation.1 (param_0.1: f32[4,8]) -> (f32[4,8], f32[16,8]) {
  %all-gather.11 = f32[16,8]{1,0} all-gather(%param_0.1), channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}
}
%async_collective_fusion.1 (param_0.2: f32[4,8]) -> f32[16,8] {
  %all-gather.13 = f32[16,8]{1,0} all-gather(%param_0.2), channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}
}
%fused_computation.2 (param_0.3: f32[4,8]) -> f32[16,8] {
  %all-gather.15 = f32[16,8]{1,0} all-gather(%param_0.3), channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}
}
%all-reduce-scatter (input: f32[16,8]) -> f32[4,8] {
  %all-reduce.7 = f32[16,8]{1,0} all-reduce(%input), channel_id=5, replica_groups={{0,1,2,3}}, to_apply=%add
}
ENTRY %main (p: f32[4,8]) -> f32[16,8] {
  %all-gather.16 = f32[16,8]{1,0} all-gather(%p), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}
  %collective-permute-start = (f32[4,8], f32[4,8]) collective-permute-start(%p), channel_id=1, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %collective-permute-start.1 = (f32[4,8], f32[4,8]) collective-permute-start(%p), channel_id=1, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %psum.7 = f32[64]{0} all-reduce(%c), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add
}
"""


def test_tpu_async_collectives_counted_once():
    counts = H.collective_counts(_TPU_HLO, 4)
    assert counts == {"all-gather": 2, "reduce-scatter": 1,
                      "all-reduce": 1, "collective-permute": 2}
    (rs,) = [c for c in H.parse_collectives(_TPU_HLO, 4)
             if c.op == "reduce-scatter"]
    assert rs.result_bytes == 4 * 8 * 4          # the scattered piece


def test_tpu_kernels_named_from_metadata():
    hlo = "\n".join(
        f'  %x.{i} = f32[8]{{0}} custom-call(%a), custom_call_target='
        f'"tpu_custom_call", metadata={{op_name="{name}/pallas_call"}}'
        for i, name in enumerate([
            "jit(f)/lasp2_chunk_fwd",
            "jit(f)/transpose(jvp(flash_attention_bwd_dq))",
            "jit(f)/jvp(flash_attention_fwd)",
            "jit(f)/jvp(flash_attention_fwd)"]))
    hlo += '\n  %y = f32[8]{0} custom-call(%a), custom_call_target="other"'
    assert H.tpu_kernels(hlo) == {"lasp2_chunk_fwd": 1,
                                  "flash_attention_bwd_dq": 1,
                                  "flash_attention_fwd": 2}


def test_device_peak_flops_keyed_by_kind():
    import jax

    class Dev:
        device_kind = "TPU v5 lite"

    assert H.device_peak_flops(Dev()) == 197e12
    assert H.device_peak_flops(jax.devices()[0]) is None   # the CPU


@pytest.mark.parametrize("env", [None, "/cache/from/env"])
def test_compile_cache_dir(monkeypatch, env):
    import jax

    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        got = compile_cache.enable_compile_cache()
        want = env or str(compile_cache.CHECKOUT_CACHE_DIR)
        assert got == want
        # JAX's own reading of the variable stands: no path is set in code
        assert jax.config.jax_compilation_cache_dir == (
            prev if env else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert compile_cache.CHECKOUT_CACHE_DIR.parent.joinpath(
        "chip_smoke.py").exists()
