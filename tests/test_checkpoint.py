"""Checkpoint store: round trip, pruning, async save, crash-safe tmp dirs."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointStore


def tree():
    return {
        "a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "b": {"c": jnp.ones((5,), jnp.bfloat16), "d": jnp.asarray(3, jnp.int32)},
    }


def test_save_restore_roundtrip(tmp_path):
    store = CheckpointStore(tmp_path, keep=2)
    t = tree()
    store.save(7, t)
    out = store.restore(jax.eval_shape(lambda: t), step=7)
    for l1, l2 in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_keep_last_n_prunes(tmp_path):
    store = CheckpointStore(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        store.save(s, tree())
    assert store.all_steps() == [3, 4]


def test_async_save(tmp_path):
    store = CheckpointStore(tmp_path, keep=3, use_async=True)
    store.save(1, tree())
    store.wait()
    assert store.latest_step() == 1


def test_partial_tmp_dir_is_ignored(tmp_path):
    store = CheckpointStore(tmp_path, keep=3)
    store.save(5, tree())
    # simulate a crash mid-save: orphan tmp dir with garbage
    bad = tmp_path / "step_0000000009.tmp"
    bad.mkdir()
    (bad / "leaf_00000.npy").write_bytes(b"garbage")
    assert store.latest_step() == 5
    out = store.restore(jax.eval_shape(lambda: tree()))
    assert int(np.asarray(jax.tree.leaves(out)[-1])) == 3
