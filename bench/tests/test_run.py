"""The command itself: no result without an accelerator; the counts and
the per-layer readers it finds by name."""

import json
import os

import pytest

from bench import counts, kinds, run as B
from bench.model import load_json


def test_no_accelerator_no_result(capsys):
    rc = B.main(["--workload", "linear-train-8k", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no accelerator" in out.err


def test_unknown_device_kind_is_an_error():
    from bench.peaks import UnknownDevice, peaks_for
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")


def test_every_per_layer_metric_has_a_reader():
    bench = B.load_benchmark()
    names = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert callable(B.metric_reader(m["name"]))
        assert set(m["workloads"]) <= names
    for w in bench["workloads"]:
        cell, config, traffic = B.load_cell(bench, w["name"])
        assert traffic["kind"] in ("train", "serve")
        assert os.path.exists(os.path.join(
            B.ROOT, "bench", "limits", f"{w['name']}.json"))


def test_counts_of_the_configuration():
    c = load_json("bench/configs/qwen1.5-1.8b-linear.json")
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5504
    assert counts.matmul_params(c) == 8 * per_layer + 37984 * 2048
    # chunked linear attention: 2C(dk+dv) + 4 dk dv per token and head
    la = 16 * (2 * 128 * 256 + 4 * 128 * 128)
    assert kinds.kind("linear").mixing_flops(c, 8192) == la
    # the kinds' sums give what the linear-only counts gave
    fwd = 2 * counts.matmul_params(c) + 8 * la
    assert counts.forward_flops_per_token(c) == fwd == 981860352
    assert counts.train_flops_per_token(c, 8192) == 3 * fwd == 2945581056
    assert counts.decode_flops_per_token(c) == 973471744
    assert counts.prefill_flops(c, 300) == 248038948864
    f, b = counts.kernel_work("lasp2_chunk_fwd", bh=16, s=8192, dk=128,
                              dv=128)
    assert f == 16 * 8192 * (2 * 128 * 256 + 4 * 128 * 128)
    # q, k, v, o in bf16, log_a fp32, state fp32
    assert b == 16 * 8192 * (4 * 128 * 2 + 4) + 16 * 128 * 128 * 4
    t, bound = counts.least_time(f, b, {"flops": 197e12, "hbm_bw": 819e9})
    assert bound == "memory" and t == pytest.approx(b / 819e9)


def test_benchmark_file_is_well_formed():
    with open(os.path.join(B.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
