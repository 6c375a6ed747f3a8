"""The span recorder (``repro.core.spans``) and the spans on the checkpoint
and serving paths."""
import collections
import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import xdfs_ckpt
from repro.checkpoint.async_ckpt import AsyncCheckpointer
from repro.core import spans

SAVE_SPANS = {"ckpt.serialize", "ckpt.crc", "ckpt.send", "ckpt.finalize"}
RESTORE_SPANS = {"restore.fetch", "restore.crc", "restore.place"}
# spans that carry one leaf's bytes each
LEAF_SPANS = {"ckpt.serialize", "ckpt.crc", "restore.fetch", "restore.crc",
              "restore.place"}


def _since(t0, names=None):
    return [r for r in spans.records()
            if r[1] >= t0 and (names is None or r[0] in names)]


def _tree(seed=0):
    k = jax.random.key(seed)
    return {
        "a": jax.random.normal(k, (300, 170), jnp.float32),
        "b": {"w": jax.random.normal(jax.random.fold_in(k, 1), (1280,), jnp.bfloat16)},
        "step": jnp.int32(7),
    }


def _nbytes(tree):
    return sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree))


def test_nested_and_threaded_spans_are_recorded_in_order():
    t0 = time.perf_counter()
    with spans.span("outer", 10):
        with spans.span("inner", 3) as sp:
            sp.nbytes += 1
    done = threading.Event()

    def worker():
        with spans.span("thread", 7):
            done.set()

    th = threading.Thread(target=worker)
    th.start()
    th.join(timeout=10)
    assert not th.is_alive() and done.is_set()
    got = _since(t0, {"outer", "inner", "thread"})
    # a span enters the ring when it ends: the inner one first
    assert [(n, b) for n, _, _, b in got] == [("inner", 4), ("outer", 10), ("thread", 7)]
    (_, i0, i1, _), (_, o0, o1, _), (_, w0, _, _) = got
    assert o0 <= i0 <= i1 <= o1 <= w0


def test_ring_drops_the_oldest_and_counts_them(monkeypatch):
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=4))
    monkeypatch.setattr(spans, "_dropped", 0)
    for i in range(7):
        with spans.span(f"r{i}"):
            pass
    assert [r[0] for r in spans.records()] == ["r3", "r4", "r5", "r6"]
    assert spans.dropped() == 3


def test_span_lands_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        t0 = time.perf_counter()
        with spans.span("probe"):
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    (_, r0, r1, _), = _since(t0, {"probe"})
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    found = [e.duration_ns / 1e9
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:CPU")
             for line in plane.lines for e in line.events if e.name == "xdfs.probe"]
    assert len(found) == 1
    assert abs(found[0] - (r1 - r0)) < 1e-3


def test_a_fresh_jit_records_its_trace_and_lowering():
    with spans.span("warm"):   # JAX is imported: the compile events are heard
        pass
    t0 = time.perf_counter()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    names = {r[0] for r in _since(t0)}
    assert {"jit.trace", "jit.lower"} <= names


@pytest.fixture
def store(tmp_path):
    from repro.cluster import ClusterClient, DataNode, MetaNode

    meta = MetaNode(replication=2).start()
    nodes = [DataNode(meta.address, str(tmp_path / f"n{i}"), node_id=f"n{i}").start()
             for i in range(3)]
    cli = ClusterClient(meta.address, block_size=64 << 10)
    try:
        yield cli
    finally:
        cli.close()
        for n in nodes:
            n.stop()
        meta.stop()


@pytest.mark.parametrize("where", ["local", "cluster"])
def test_save_and_restore_record_their_spans(where, tmp_path, request):
    tree = _tree()
    like = jax.eval_shape(lambda: tree)
    t0 = time.perf_counter()
    if where == "local":
        ck = AsyncCheckpointer(str(tmp_path / "ckpt"))
        ck.save(tree, 3).result(timeout=60)
        ck.close()
        restored, _ = xdfs_ckpt.restore(str(tmp_path / "ckpt"), like)
        want = SAVE_SPANS | RESTORE_SPANS | {"ckpt.snapshot", "ckpt.d2h", "ckpt.queued",
                                             "io.write"}
    else:
        cli = request.getfixturevalue("store")
        xdfs_ckpt.save(tree, "ckpt", step=3, cluster=cli)
        restored, _ = xdfs_ckpt.restore("ckpt", like, cluster=cli)
        want = SAVE_SPANS | RESTORE_SPANS | {"cluster.lookup"}
    jax.block_until_ready(restored)
    got = _since(t0)
    assert want <= {r[0] for r in got}
    nbytes = collections.Counter()
    for name, _, _, b in got:
        nbytes[name] += b
    for name in LEAF_SPANS:
        assert nbytes[name] == _nbytes(tree), name
    if where == "local":
        assert nbytes["ckpt.snapshot"] == _nbytes(tree)
        assert nbytes["ckpt.d2h"] == _nbytes(tree)
        # the server's writes hold every leaf and the manifest
        assert nbytes["io.write"] > _nbytes(tree)
