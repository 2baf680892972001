"""The trace reduction on a trace recorded on a TPU v5e
(``bench/tests/record_trace.py``): three steps of a jitted function
holding the ``lasp2_chunk_fwd`` Pallas kernel, each after a 50 ms host
pause in a ``data`` span."""

import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "trace.xplane.pb")


@pytest.fixture(scope="module")
def parsed():
    return trace.load(DATA, spans=("data", "step", "fence", "window"))


def test_op_name():
    assert trace.op_name("%lasp2_chunk_fwd.1 = (bf16[16]) custom-call(x)") \
        == "lasp2_chunk_fwd"
    assert trace.op_name("%convert_reduce_fusion = f32[] fusion(x)") \
        == "convert_reduce_fusion"
    assert trace.op_name("%all-gather-start.3 = f32[4] all-gather-start(x)") \
        == "all-gather-start"


def test_device_and_spans(parsed):
    assert list(parsed["devices"]) == ["/device:TPU:0"]
    names = [n for n, _, _ in parsed["devices"]["/device:TPU:0"]]
    assert names.count("lasp2_chunk_fwd") == 3
    assert [n for n, _, _ in parsed["host"]].count("data") == 3


def test_reduce_over_the_data_and_step_spans(parsed):
    host = parsed["host"]
    data_spans = [(s, e) for n, s, e in host if n == "data"]
    fences = [(s, e) for n, s, e in host if n == "fence"]
    # a window from the first pause to the last fence
    win = ("window", data_spans[0][0], fences[-1][1])
    r = trace.reduce({"devices": parsed["devices"], "host": host + [win]})
    assert r["window_s"] == pytest.approx((win[2] - win[1]) * 1e-9)
    # three steps of about 0.37 ms of device work in about 155 ms
    assert 0.9e-3 < r["busy_s"] < 1.3e-3
    assert 0.99 < r["idle_share"] < 1.0
    assert r["op_calls"]["lasp2_chunk_fwd"] == 3
    kernel = r["op_s"]["lasp2_chunk_fwd"]
    assert 0.3e-3 < kernel < 0.4e-3
    # busy is the union, so it is no more than the sum of the op times
    assert r["busy_s"] <= sum(r["op_s"].values()) + 1e-12
    # the three longest gaps are the 50 ms host pauses
    gaps = r["idle_gaps"]
    assert [lab for lab, _ in gaps[:3]] == ["data"] * 3
    assert all(0.045 < g < 0.06 for _, g in gaps[:3])
    assert r["device_ops"][0][0] == "convert_reduce_fusion"


def test_no_window_reads_nothing(parsed):
    assert trace.reduce(parsed) is None
