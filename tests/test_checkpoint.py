"""Checkpoint store: crash-safety, retention, mesh-independent restore.

The fast tests run in-process on the default (1-device) host; the
cross-mesh restore round-trip runs in a subprocess with 8 forced host
devices, like the other multi-device suites.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.checkpoint import (
    CheckpointManager,
    latest_step,
    restore_tree,
    save_tree,
)


def tree_eq(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(
            __import__("jax").tree.leaves(a), __import__("jax").tree.leaves(b)
        )
    )


class TestSaveRestore:
    def test_round_trip(self, tmp_path):
        tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
                "b": np.ones(4, dtype=np.int32)}
        path = save_tree(tree, str(tmp_path), 7)
        assert os.path.isdir(path)
        assert latest_step(str(tmp_path)) == 7
        out = restore_tree({"w": 0, "b": 0}, str(tmp_path), 7)
        assert tree_eq(out, tree)

    def test_latest_step_discovery_ignores_tmp_and_noise(self, tmp_path):
        assert latest_step(str(tmp_path / "missing")) is None
        save_tree({"x": np.zeros(2)}, str(tmp_path), 3)
        save_tree({"x": np.zeros(2)}, str(tmp_path), 11)
        os.makedirs(tmp_path / "step_000000099.tmp")  # orphaned staging
        (tmp_path / "notes.txt").write_text("ignored")
        assert latest_step(str(tmp_path)) == 11

    def test_same_step_overwrite_replaces_whole_snapshot(self, tmp_path):
        save_tree({"x": np.zeros(4), "y": np.zeros(2)}, str(tmp_path), 5)
        save_tree({"x": np.full(4, 9.0)}, str(tmp_path), 5)
        out = restore_tree({"x": 0}, str(tmp_path), 5)
        assert np.array_equal(np.asarray(out["x"]), np.full(4, 9.0))
        # the stale second leaf did not survive the overwrite
        files = os.listdir(tmp_path / "step_000000005")
        assert sorted(files) == ["leaf_00000.npy", "manifest.json"]

    def test_failed_write_cleans_staging_dir(self, tmp_path):
        class Poison:
            def __array__(self, dtype=None):
                raise RuntimeError("leaf write failure")

        save_tree({"ok": np.zeros(2)}, str(tmp_path), 1)
        with pytest.raises(RuntimeError, match="leaf write failure"):
            save_tree({"a": np.zeros(2), "b": Poison()}, str(tmp_path), 2)
        # no orphaned .tmp, no half-published step, step 1 untouched
        assert [n for n in os.listdir(tmp_path) if n.endswith(".tmp")] == []
        assert latest_step(str(tmp_path)) == 1
        assert tree_eq(restore_tree({"ok": 0}, str(tmp_path), 1),
                       {"ok": np.zeros(2)})

    def test_leaf_count_mismatch_raises(self, tmp_path):
        save_tree({"x": np.zeros(2)}, str(tmp_path), 1)
        with pytest.raises(ValueError, match="leaves"):
            restore_tree({"x": 0, "y": 0}, str(tmp_path), 1)


class TestCheckpointManager:
    def test_restore_latest_empty_store(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        assert cm.restore_latest({"x": 0}) == (None, None)

    def test_async_save_then_restore_latest(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.save({"x": np.arange(4.0)}, 10)
        cm.save({"x": np.arange(4.0) * 2}, 20)
        tree, step = cm.restore_latest({"x": 0})
        assert step == 20
        assert np.array_equal(np.asarray(tree["x"]), np.arange(4.0) * 2)

    def test_async_write_error_raised_by_wait(self, tmp_path, monkeypatch):
        from repro.checkpoint import store

        def failing_save(tree, directory, step):
            raise OSError("disk full")

        monkeypatch.setattr(store, "save_tree", failing_save)
        cm = CheckpointManager(str(tmp_path))
        cm.save({"x": np.arange(4.0)}, 10)
        with pytest.raises(OSError, match="disk full"):
            cm.wait()
        cm.wait()  # reported once, then cleared
        cm.save({"x": np.arange(4.0)}, 20)
        with pytest.raises(OSError, match="disk full"):
            cm.save({"x": np.arange(4.0)}, 30)  # the next save reports it

    def test_retention_keeps_last_k(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        for s in (1, 2, 3, 4):
            cm.save({"x": np.full(2, float(s))}, s)
        kept = sorted(
            int(n.split("_")[1]) for n in os.listdir(tmp_path)
            if n.startswith("step_")
        )
        assert kept == [3, 4]
        tree, step = cm.restore_latest({"x": 0})
        assert step == 4 and float(np.asarray(tree["x"])[0]) == 4.0


CROSS_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import tempfile
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.checkpoint import CheckpointManager

    def mesh_of(k):
        devs = np.asarray(jax.devices()[:k], dtype=object).reshape((k,))
        return Mesh(devs, ("data",))

    tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    big = mesh_of(8)
    sharded = jax.device_put(tree["w"], NamedSharding(big, P("data")))

    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d)
        cm.save({"w": sharded}, 42)
        small = mesh_of(2)
        out, step = cm.restore_latest(
            {"w": 0}, mesh=small, spec_tree={"w": P("data")})
        assert step == 42
        restored = out["w"]
        assert restored.sharding.mesh.devices.shape == (2,)
        assert np.array_equal(np.asarray(restored), tree["w"])
    print("CROSS_MESH_OK")
""")


@pytest.mark.slow
def test_cross_mesh_restore_round_trip():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run(
        [sys.executable, "-c", CROSS_MESH_SCRIPT], capture_output=True,
        text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, (proc.stderr[-3000:], proc.stdout[-500:])
    assert "CROSS_MESH_OK" in proc.stdout
