"""Checkpoint invariants: roundtrip, atomicity, corruption fallback, GC,
async disk thread."""
import json
import os
import shutil
import threading
import weakref
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import async_ckpt, xdfs_ckpt
from repro.checkpoint.async_ckpt import AsyncCheckpointer


def _tree(seed=0):
    k = jax.random.key(seed)
    return {
        "a": jax.random.normal(k, (33, 17), jnp.float32),
        "b": {"w": jax.random.normal(jax.random.fold_in(k, 1), (128,), jnp.bfloat16)},
        "step": jnp.int32(7),
    }


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    xdfs_ckpt.save(t, str(tmp_path), step=10)
    like = jax.eval_shape(lambda: t)
    restored, step = xdfs_ckpt.restore(str(tmp_path), like)
    assert step == 10
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_no_tmp_dirs_visible_after_save(tmp_path):
    xdfs_ckpt.save(_tree(), str(tmp_path), step=1)
    assert not list(Path(tmp_path).glob("*.tmp"))


def test_corrupt_newest_falls_back(tmp_path, caplog):
    t0, t1 = _tree(0), _tree(1)
    xdfs_ckpt.save(t0, str(tmp_path), step=1)
    xdfs_ckpt.save(t1, str(tmp_path), step=2)
    # corrupt a leaf of step 2
    victim = next(Path(tmp_path).glob("step_00000002/leaf_*.bin"))
    raw = bytearray(victim.read_bytes())
    raw[0] ^= 0xFF
    victim.write_bytes(bytes(raw))
    like = jax.eval_shape(lambda: t0)
    with caplog.at_level("WARNING", logger=xdfs_ckpt.__name__):
        restored, step = xdfs_ckpt.restore(str(tmp_path), like)
    assert step == 1  # fell back past the corrupt step
    np.testing.assert_array_equal(
        np.asarray(restored["a"]), np.asarray(t0["a"])
    )
    # the walk-back is never silent: the skipped step and its cause are logged
    (rec,) = caplog.records
    assert "skipping step 2" in rec.getMessage()
    assert "checksum mismatch" in rec.getMessage()


def test_device_failure_is_not_a_walk_back(tmp_path, monkeypatch):
    """A failure placing the arrays is no sign of a bad step: it propagates
    instead of restoring an older step."""
    xdfs_ckpt.save(_tree(0), str(tmp_path), step=1)
    xdfs_ckpt.save(_tree(1), str(tmp_path), step=2)

    def broken_put(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(xdfs_ckpt.jax, "device_put", broken_put)
    with pytest.raises(RuntimeError, match="device lost"):
        xdfs_ckpt.restore(str(tmp_path), jax.eval_shape(_tree))


def test_keep_last_gc(tmp_path):
    for s in range(6):
        xdfs_ckpt.save(_tree(s), str(tmp_path), step=s, keep_last=2)
    steps = sorted(p.name for p in Path(tmp_path).glob("step_*"))
    assert steps == ["step_00000004", "step_00000005"]


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep_last=3)
    futs = [ck.save(_tree(s), s) for s in range(3)]
    ck.wait()
    assert all(f.done() and f.exception() is None for f in futs)
    assert xdfs_ckpt.latest_step(str(tmp_path)) == 2
    ck.close()


def _host_tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": rng.standard_normal((33, 17), dtype=np.float32),
        "b": {"w": np.arange(128, dtype=np.int32)},
        "step": np.float32(7.5),
    }


def _spy_copies(monkeypatch):
    """Weak references to the device copies ``save`` takes, one list per
    save, and the copies' buffer pointers."""
    saves, ptrs = [], []
    real_copy, real_save = async_ckpt._copy, AsyncCheckpointer.save

    def save(self, tree, step):
        saves.append([])
        return real_save(self, tree, step)

    def copy(x):
        c = real_copy(x)
        if isinstance(c, jax.Array):
            saves[-1].append(weakref.ref(c))
            ptrs.append(c.unsafe_buffer_pointer())
        return c

    monkeypatch.setattr(async_ckpt, "_copy", copy)
    monkeypatch.setattr(AsyncCheckpointer, "save", save)
    return saves, ptrs


def _on_device(refs):
    """Whether a device copy of ``refs`` still holds its device memory."""
    return any(r() is not None and not r().is_deleted() for r in refs)


@pytest.mark.parametrize("make", [_tree, _host_tree], ids=["device", "host"])
def test_async_save_survives_a_step_that_donates_the_state(make, tmp_path, monkeypatch):
    """The snapshot is the checkpoint's own: the step after ``save`` may
    donate the state, and the committed step still restores bit for bit to
    the state at the call. The device copy is gone once the save is done."""
    state = make()
    like = jax.eval_shape(lambda: state)
    want = [np.array(x) for x in jax.tree.leaves(state)]
    state_ptrs = {x.unsafe_buffer_pointer() for x in jax.tree.leaves(state)
                  if isinstance(x, jax.Array)}
    saves, snap_ptrs = _spy_copies(monkeypatch)
    ck = AsyncCheckpointer(str(tmp_path))
    fut = ck.save(state, 1)
    step = jax.jit(lambda t: jax.tree.map(lambda x: x * 2 + 1, t), donate_argnums=0)
    jax.block_until_ready(step(state))
    assert all(x.is_deleted() for x in jax.tree.leaves(state) if isinstance(x, jax.Array))
    fut.result(timeout=60)
    ck.close()
    assert len(snap_ptrs) == len(state_ptrs)
    assert not state_ptrs & set(snap_ptrs)
    assert not _on_device(saves[0])
    restored, s = xdfs_ckpt.restore(str(tmp_path), like)
    assert s == 1
    for a, b in zip(jax.tree.leaves(restored), want):
        a = np.asarray(a)
        assert a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


def test_queued_saves_hold_one_snapshot_on_the_device(tmp_path, monkeypatch):
    """While commits lag behind saves, queued saves wait on the host: at
    most one save's device copy exists, and the save after that one waits
    for its transfer."""
    saves, _ = _spy_copies(monkeypatch)
    gate = threading.Event()
    real_save = xdfs_ckpt.save

    def slow_save(*a, **k):
        assert gate.wait(timeout=60)
        return real_save(*a, **k)

    monkeypatch.setattr(xdfs_ckpt, "save", slow_save)
    ck = AsyncCheckpointer(str(tmp_path), depth=2)
    # one save in the disk thread, two in its queue, one held by the copy
    # thread, one on the device: no commit ends while the gate is shut
    futs = []
    for i in range(5):
        futs.append(ck.save(_tree(i), i))
        assert sum(_on_device(refs) for refs in saves) <= 1
    sixth = threading.Thread(target=lambda: futs.append(ck.save(_tree(5), 5)))
    sixth.start()
    sixth.join(timeout=0.5)
    assert sixth.is_alive() and len(futs) == 5
    gate.set()
    sixth.join(timeout=60)
    assert [f.result(timeout=60) for f in futs] == [
        str(tmp_path / f"step_{i:08d}") for i in range(6)]
    ck.close()
    assert not any(_on_device(refs) for refs in saves)
    like = jax.eval_shape(lambda: _tree())
    for i in (3, 4, 5):
        restored, _ = xdfs_ckpt.restore(str(tmp_path), like, step=i)
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(_tree(i))):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        xdfs_ckpt.restore(str(tmp_path / "nope"), {"a": jnp.zeros(3)})


def test_cluster_checkpoint_roundtrip_gc_and_failover(tmp_path):
    """Opt-in cluster mode: shards stripe over the fleet with rf=2, the
    manifest is the commit point, keep_last GC reclaims old steps'
    blocks, and a restore survives a dead data node."""
    from repro.cluster import ClusterClient, DataNode, MetaNode

    meta = MetaNode(replication=2, heartbeat_timeout=0.5,
                    tick_interval=0.1).start()
    nodes = [
        DataNode(meta.address, str(tmp_path / f"n{i}"), node_id=f"n{i}",
                 heartbeat_interval=0.05).start()
        for i in range(3)
    ]
    cli = ClusterClient(meta.address, block_size=256 << 10)
    try:
        like = jax.eval_shape(_tree)
        for s in (3, 4, 5):
            xdfs_ckpt.save(_tree(s), "ckpt", step=s, keep_last=2,
                           cluster=cli)
        assert xdfs_ckpt.latest_step("ckpt", cluster=cli) == 5
        # GC: only the last two steps' files remain in the namespace
        steps = {n.split("/")[1] for n in cli.list("ckpt/")}
        assert steps == {"step_00000004", "step_00000005"}
        restored, step = xdfs_ckpt.restore("ckpt", like, step=5, cluster=cli)
        assert step == 5
        for a, b in zip(jax.tree.leaves(_tree(5)), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # a dead node must not lose the checkpoint (rf=2 replicas)
        nodes[0].kill()
        restored, step = xdfs_ckpt.restore("ckpt", like, cluster=cli)
        assert step == 5
        for a, b in zip(jax.tree.leaves(_tree(5)), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        cli.close()
        for n in nodes[1:]:
            n.stop()
        meta.stop()


def test_cluster_checkpoint_survives_metanode_death(tmp_path):
    """The control plane is no longer the single point of checkpoint
    loss: with a journaled MetaNode, a save / kill-metanode / restart /
    restore cycle round-trips — and ``cluster=`` accepts plain metanode
    addresses (a throwaway failover client per call) as well as a live
    ``ClusterClient``."""
    from repro.cluster import DataNode, MetaNode
    from repro.core.faults import RetryPolicy

    jdir = tmp_path / "wal"
    meta = MetaNode(replication=2, heartbeat_timeout=0.5,
                    tick_interval=0.1, journal_dir=str(jdir)).start()
    port = meta.address[1]
    nodes = [
        DataNode(meta.address, str(tmp_path / f"n{i}"), node_id=f"n{i}",
                 heartbeat_interval=0.05,
                 policy=RetryPolicy(attempts=4, base_delay=0.05,
                                    connect_timeout=2.0)).start()
        for i in range(2)
    ]
    try:
        like = jax.eval_shape(_tree)
        # address form instead of a client instance
        xdfs_ckpt.save(_tree(1), "ckpt", step=1, cluster=meta.address)
        meta.kill()  # crash between save and restore
        meta = MetaNode(replication=2, heartbeat_timeout=0.5,
                        tick_interval=0.1, port=port,
                        journal_dir=str(jdir)).start()
        assert xdfs_ckpt.latest_step("ckpt", cluster=meta.address) == 1
        restored, step = xdfs_ckpt.restore("ckpt", like,
                                           cluster=meta.address)
        assert step == 1
        for a, b in zip(jax.tree.leaves(_tree(1)),
                        jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the recovered control plane keeps checkpointing: next step
        # saves and becomes the latest
        xdfs_ckpt.save(_tree(2), "ckpt", step=2, cluster=meta.address)
        assert xdfs_ckpt.latest_step("ckpt", cluster=meta.address) == 2
    finally:
        for n in nodes:
            n.stop()
        meta.stop()
