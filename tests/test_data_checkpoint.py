"""Data pipeline determinism + checkpoint atomicity/resume/resharding."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import SyntheticLM, doc_segments


def test_data_determinism():
    d1 = SyntheticLM(1000, 128, 8, seed=7)
    d2 = SyntheticLM(1000, 128, 8, seed=7)
    b1, b2 = d1.batch(5), d2.batch(5)
    for k in b1:
        np.testing.assert_array_equal(b1[k], b2[k])
    b3 = d1.batch(6)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_data_labels_shifted():
    d = SyntheticLM(1000, 64, 2, seed=0, pack_documents=False)
    b = d.batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
    assert (b["labels"][:, -1] == -1).all()


def test_packing_resets_and_segments():
    d = SyntheticLM(1000, 256, 4, seed=1, mean_doc_len=64)
    b = d.batch(0)
    assert b["resets"][:, 0].all()
    segs = doc_segments(b["resets"])
    assert (np.diff(segs, axis=1) >= 0).all()
    assert segs.max() >= 2   # actually packed multiple docs


def test_microbatched_shapes():
    d = SyntheticLM(1000, 32, 8, seed=0)
    mb = d.microbatched(0, 4)
    assert mb["tokens"].shape == (4, 2, 32)
    with pytest.raises(ValueError):
        d.microbatched(0, 3)


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"params": {"w": jnp.arange(12.0).reshape(3, 4)},
            "step": jnp.int32(7)}
    mgr.save(7, tree)
    assert mgr.latest_step() == 7
    out = mgr.restore(7, jax.tree.map(jnp.zeros_like, tree))
    np.testing.assert_array_equal(out["params"]["w"], tree["params"]["w"])
    assert int(out["step"]) == 7


def test_checkpoint_keep_k_and_atomic(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.zeros((4,))}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    assert mgr.all_steps() == [3, 4]
    # a stale tmp dir must not be listed as a checkpoint
    os.makedirs(os.path.join(str(tmp_path), "step_00000099.tmp"))
    assert mgr.latest_step() == 4


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    tree = {"w": jnp.ones((128, 128))}
    mgr.save_async(11, tree)
    mgr.wait()
    out = mgr.restore(11, {"w": jnp.zeros((128, 128))})
    np.testing.assert_array_equal(out["w"], tree["w"])


def test_checkpoint_shape_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        mgr.restore(1, {"w": jnp.zeros((5,))})


def test_checkpoint_elastic_reshard(tmp_path):
    """Checkpoint written unsharded restores under explicit shardings
    (the elastic-scaling path: any mesh can adopt the state)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr.save(3, tree)
    from repro.launch.mesh import DATA_AXIS
    mesh = jax.make_mesh((1,), (DATA_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = {"w": NamedSharding(mesh, P(DATA_AXIS, None))}
    out = mgr.restore(3, jax.tree.map(jnp.zeros_like, tree), shardings=sh)
    np.testing.assert_array_equal(out["w"], tree["w"])
    assert out["w"].sharding == sh["w"]
