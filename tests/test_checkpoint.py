"""Fault-tolerance: checkpoint atomicity, retention, resume, corruption."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer


def _state(x):
    return {"params": {"w": jnp.full((4, 3), x)},
            "step": jnp.asarray(int(x), jnp.int32)}


def test_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(_state(7.0), step=7)
    restored, step = ck.restore(_state(0.0))
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.full((4, 3), 7.0))


def test_restore_latest_and_retention(tmp_path):
    ck = Checkpointer(str(tmp_path), keep_last_k=2)
    for s in (1, 2, 3, 4):
        ck.save(_state(float(s)), step=s)
    assert ck.list_steps() == [3, 4]      # retention pruned 1, 2
    _, step = ck.restore(_state(0.0))
    assert step == 4


def test_async_save(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(_state(5.0), step=5, blocking=False)
    ck.wait()
    assert ck.list_steps() == [5]


def test_corrupt_manifest_skipped(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(_state(1.0), step=1)
    ck.save(_state(2.0), step=2)
    # corrupt the newest manifest -> restore falls back to step 1
    with open(tmp_path / "step_0000000002" / "manifest.json", "w") as f:
        f.write("{not json")
    assert ck.list_steps() == [1]
    _, step = ck.restore(_state(0.0))
    assert step == 1


def test_tmp_dirs_ignored(tmp_path):
    """A crash mid-write leaves step_*.tmp — must be invisible to restore."""
    ck = Checkpointer(str(tmp_path))
    ck.save(_state(1.0), step=1)
    os.makedirs(tmp_path / "step_0000000009.tmp")
    assert ck.list_steps() == [1]


def test_no_checkpoint_raises(tmp_path):
    ck = Checkpointer(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        ck.restore(_state(0.0))


def test_elastic_restore_with_shardings(tmp_path):
    """Restore onto explicit (1-device) shardings — the elastic-restart
    path where the mesh changed between save and restore."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    ck = Checkpointer(str(tmp_path))
    ck.save(_state(3.0), step=3)
    sh = {"params": {"w": NamedSharding(mesh, P("data", "model"))},
          "step": NamedSharding(mesh, P())}
    restored, step = ck.restore(_state(0.0), shardings=sh)
    assert restored["params"]["w"].sharding == sh["params"]["w"]
