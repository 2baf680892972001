"""The plain reference against the program at a tiny size on the CPU:
three training steps (loss, first gradient, change of the weights) and
served tokens against the reference's logits."""

from bench import loadgen
from bench.tests import harness


def test_training_agrees_to_rounding():
    r = harness.run("train")
    assert r["correct"], r["check"]
    for name in ("loss_gap", "grad_gap", "change_gap"):
        assert r["check"][name]["value"] < 1e-5
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_serving_agrees_to_rounding():
    r = harness.run("serve")
    assert r["correct"], r["check"]
    assert r["check"]["logit_gap"]["value"] < 1e-4
    assert r["failed"] == 0
    assert set(r["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                 "itl_p95_ms", "setup_s"}


def test_every_seed_gets_the_same_work():
    t = harness.ctx("serve").traffic
    a = loadgen.serve_schedule(t, 1, 5.0, 500)
    b = loadgen.serve_schedule(t, 2**31 + 5, 5.0, 500)
    key = lambda rs: (sorted(len(r.prompt) for r in rs),
                      sorted(r.max_new for r in rs))
    assert key(a) == key(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    gaps = lambda rs: sorted(round(y.due - x.due, 9)
                             for x, y in zip(rs, rs[1:]))
    assert len(gaps(a)) == len(gaps(b))


def test_training_rows_differ_and_reset_at_documents():
    t = harness.ctx("train").traffic
    b0 = loadgen.train_batch(t, 7, 0, 500)
    b1 = loadgen.train_batch(t, 7, 1, 500)
    assert (b0["tokens"] != b1["tokens"]).any()
    r = b0["resets"][0, 0]
    assert r[0] and r.sum() > 1
    lab = b0["labels"][0, 0]
    # a document's last token predicts nothing
    ends = r[1:]
    assert (lab[:-1][ends] == -1).all() and lab[-1] == -1
