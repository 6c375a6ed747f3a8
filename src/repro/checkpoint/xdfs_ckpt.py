"""Sharded checkpointing over the xDFS session API.

Save = one persistent upload session: every pytree leaf (and the JSON
manifest) is ``put`` through an ``XdfsClient`` as an in-memory source, so
all checkpoint bytes flow through the negotiated multi-channel session —
one negotiation per save, EOFR channel reuse between leaves, and the
MTEDP single-writer vectored sink on the server side. Restore = one
download session: ``get_bytes`` futures pipeline the leaf reads.

Layout:
  <dir>/step_<N>.tmp/...   (in-flight)
  <dir>/step_<N>/manifest.json + <leaf_id>.bin   (committed via atomic rename)

Fault-tolerance invariants (tested):
  * a torn save never becomes visible (atomic rename of the step dir);
  * restore picks the newest COMPLETE step;
  * checksum mismatch -> that step is rejected and the previous one loads;
  * keep_last bounds disk usage.

Cluster mode (opt-in): pass ``cluster=ClusterClient(...)`` — or just a
metanode address / list of metanode addresses, and a client is built
and closed per call — to ``save`` / ``restore`` / ``latest_step`` and
every leaf stripes across the fleet of data nodes with the MetaNode's
replication factor — sharded JAX checkpoint shards become replicated
cluster blocks, and a data node dying between save and restore costs
nothing. With a journaled, multi-metanode control plane, so does the
MetaNode: commits are write-ahead journaled and standbys take over, so
a checkpoint save survives metanode death mid-run and a restore works
against whichever metanode currently leads. ``directory`` then names a
prefix in the cluster namespace instead of a local path; the manifest
is written LAST, so it is the commit point (restore only considers
steps whose manifest exists — the same torn-save invariant as the
atomic rename, without needing a rename primitive). The single-node
local path stays the default and is untouched.
"""
from __future__ import annotations

import json
import logging
import os
import shutil
import zlib
from contextlib import contextmanager
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np

from repro.cluster.wire import ClusterError
from repro.core import spans
from repro.core.header import ProtocolError

log = logging.getLogger(__name__)

BLOCK = 4 << 20
N_CHANNELS = 2
ENGINE = "mtedp"


@contextmanager
def _session(root: Path, integrity: bool = False):
    """A loopback xDFS session rooted at ``root`` (server + client pair)."""
    from repro.core.api import XdfsClient, XdfsServer

    with spans.span("session.open"):
        srv = XdfsServer(engine=ENGINE, root=str(root)).start()
        cli = XdfsClient.connect(
            srv.address, n_channels=N_CHANNELS, engine=ENGINE, block_size=BLOCK,
            integrity=integrity,
        )
    try:
        yield cli
    finally:
        with spans.span("session.close"):
            cli.close()
            srv.stop()


def _leaf_files(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for i, (path, leaf) in enumerate(flat):
        name = f"leaf_{i:05d}.bin"
        out.append((jax.tree_util.keystr(path), name, leaf))
    return out


def _serialize(keypath: str, fname: str, leaf) -> tuple:
    """A leaf's bytes and its manifest entry."""
    with spans.span("ckpt.serialize") as sp:
        arr = np.asarray(jax.device_get(leaf))
        raw = arr.tobytes()
        sp.nbytes = len(raw)
    with spans.span("ckpt.crc", len(raw)):
        crc = zlib.crc32(raw) & 0xFFFFFFFF
    return raw, {"key": keypath, "file": fname, "shape": list(arr.shape),
                 "dtype": str(arr.dtype), "crc32": crc}


def _place(meta: dict, raw: bytes, sh):
    """Check a restored leaf's bytes against the manifest, then put it on
    the device."""
    with spans.span("restore.crc", len(raw)):
        ok = (zlib.crc32(raw) & 0xFFFFFFFF) == meta["crc32"]
    if not ok:
        raise IOError(f"checksum mismatch in {meta['file']}")
    with spans.span("restore.place", len(raw)):
        arr = np.frombuffer(raw, dtype=meta["dtype"]).reshape(meta["shape"])
        return jax.device_put(arr, sh) if sh is not None else jax.device_put(arr)


def _step_prefix(directory: str, step: int) -> str:
    return f"{directory.rstrip('/')}/step_{step:08d}"


@contextmanager
def _as_client(cluster):
    """Accept a live ``ClusterClient`` (caller owns it) or one-or-more
    metanode addresses (a throwaway failover client is built and closed
    around the call)."""
    if hasattr(cluster, "put") and hasattr(cluster, "list"):
        yield cluster
        return
    from repro.cluster import ClusterClient

    cli = ClusterClient(cluster)
    try:
        yield cli
    finally:
        cli.close()


def _cluster_steps(directory: str, cluster) -> list:
    """Committed steps in the cluster namespace = those whose manifest
    (the last file written) exists."""
    prefix = directory.rstrip("/") + "/step_"
    steps = set()
    for name in cluster.list(prefix):
        rest = name[len(prefix):]
        if rest.endswith("/manifest.json"):
            steps.add(int(rest.split("/")[0]))
    return sorted(steps)


def _save_cluster(tree: Any, directory: str, step: int, keep_last: int,
                  cluster) -> str:
    prefix = _step_prefix(directory, step)
    manifest = {"step": step, "leaves": []}
    for keypath, fname, leaf in _leaf_files(tree):
        raw, meta = _serialize(keypath, fname, leaf)
        manifest["leaves"].append(meta)
        with spans.span("ckpt.send", len(raw)):
            cluster.put(f"{prefix}/{fname}", data=raw)
    # manifest LAST = the commit point (restore ignores manifest-less steps)
    with spans.span("ckpt.send"):
        cluster.put(f"{prefix}/manifest.json",
                    data=json.dumps(manifest).encode())
    with spans.span("ckpt.finalize"):
        for old in _cluster_steps(directory, cluster)[:-keep_last]:
            for name in cluster.list(_step_prefix(directory, old) + "/"):
                cluster.delete(name)
    return prefix


def _restore_one_cluster(directory: str, step: int, like: Any,
                         shardings: Any, cluster):
    prefix = _step_prefix(directory, step)
    manifest = json.loads(cluster.get(f"{prefix}/manifest.json"))
    leaves_like, treedef = jax.tree_util.tree_flatten(like)
    sh_leaves = (
        jax.tree_util.tree_flatten(shardings)[0] if shardings is not None else
        [None] * len(leaves_like)
    )
    if len(manifest["leaves"]) != len(leaves_like):
        raise ValueError(
            f"leaf count mismatch: ckpt {len(manifest['leaves'])} "
            f"vs {len(leaves_like)}"
        )
    out = []
    for meta, sh in zip(manifest["leaves"], sh_leaves):
        with spans.span("restore.fetch") as sp:
            raw = cluster.get(f"{prefix}/{meta['file']}")
            sp.nbytes = len(raw)
        out.append(_place(meta, raw, sh))
    return jax.tree_util.tree_unflatten(treedef, out)


def save(tree: Any, directory: str, step: int, keep_last: int = 3,
         cluster=None, resume: bool = False, integrity: bool = False) -> str:
    """Blocking sharded save; returns the committed directory.

    ``cluster`` (opt-in): a ``repro.cluster.ClusterClient`` — leaves
    stripe across the fleet of data nodes instead of a local step dir.

    ``resume`` (opt-in, implies ``integrity``): a save interrupted
    mid-step left its ``.tmp`` dir and per-file resume sidecars behind;
    a re-save with ``resume=True`` keeps them and re-``put``\\ s every
    leaf with the RESUME protocol, so complete leaves cost a CRC
    exchange and zero data bytes, and a torn leaf only re-sends its
    missing/stale blocks.
    """
    if cluster is not None:
        if resume:
            raise ValueError("resume is not supported for cluster saves")
        with _as_client(cluster) as cli:
            return _save_cluster(tree, directory, step, keep_last, cli)
    integrity = integrity or resume
    base = Path(directory)
    base.mkdir(parents=True, exist_ok=True)
    rel = f"step_{step:08d}.tmp"
    tmp = base / rel
    final = base / f"step_{step:08d}"
    if tmp.exists() and not resume:
        shutil.rmtree(tmp)
    tmp.mkdir(exist_ok=True)
    manifest = {"step": step, "leaves": []}
    with _session(base, integrity=integrity) as cli:
        # one negotiation for the whole step; leaves pipeline depth-2
        # through the session worker (bounded host memory: only the leaf in
        # flight and the one being prepared are materialized)
        prev = None
        for keypath, fname, leaf in _leaf_files(tree):
            raw, meta = _serialize(keypath, fname, leaf)
            manifest["leaves"].append(meta)
            fut = cli.put(None, f"{rel}/{fname}", data=raw, resume=resume)
            if prev is not None:
                with spans.span("ckpt.send"):
                    prev.result()
            prev = fut
        with spans.span("ckpt.send"):
            if prev is not None:
                prev.result()
            cli.put(None, f"{rel}/manifest.json",
                    data=json.dumps(manifest).encode(), resume=resume).result()
    with spans.span("ckpt.finalize"):
        # integrity puts keep resume sidecars next to the data files; a
        # fully landed step no longer needs them, so don't commit them
        from repro.core.resume import SIDECAR_SUFFIX

        for sc in tmp.glob("*" + SIDECAR_SUFFIX):
            sc.unlink()
        if final.exists():  # re-save after fault recovery: replace the old step
            shutil.rmtree(final)
        os.replace(tmp, final)  # atomic commit
        _gc(base, keep_last)
    return str(final)


def _gc(base: Path, keep_last: int):
    steps = sorted(p for p in base.glob("step_*") if not p.name.endswith(".tmp"))
    for p in steps[:-keep_last]:
        shutil.rmtree(p, ignore_errors=True)


def latest_step(directory: str, cluster=None) -> Optional[int]:
    if cluster is not None:
        with _as_client(cluster) as cli:
            steps = _cluster_steps(directory, cli)
        return steps[-1] if steps else None
    base = Path(directory)
    if not base.exists():
        return None
    steps = []
    for p in sorted(base.glob("step_*")):
        if p.name.endswith(".tmp") or not (p / "manifest.json").exists():
            continue
        steps.append(int(p.name.split("_")[1]))
    return steps[-1] if steps else None


def restore(directory: str, like: Any, step: Optional[int] = None,
            shardings: Any = None, cluster=None) -> Any:
    """Restore into the structure of ``like`` (ShapeDtypeStructs or arrays).

    Walks back to older steps if the newest is corrupt (checksum), and
    logs each step it skips.
    ``cluster`` (opt-in): restore from the cluster namespace instead of
    a local directory — per-block CRCs and replica failover come from
    the ``ClusterClient``, and the leaf-level checksum walk-back across
    steps is the same as the local path."""
    if cluster is not None:
        with _as_client(cluster) as cli:
            candidates = _cluster_steps(directory, cli)
            if step is not None:
                candidates = [s for s in candidates if s == step]
            return _walk_back(
                candidates,
                lambda s: _restore_one_cluster(directory, s, like, shardings, cli),
                f"{directory!r} in cluster",
            )
    base = Path(directory)
    candidates = sorted(
        int(p.name.split("_")[1])
        for p in base.glob("step_*")
        if not p.name.endswith(".tmp") and (p / "manifest.json").exists()
    )
    if step is not None:
        candidates = [s for s in candidates if s == step]
    return _walk_back(
        candidates,
        lambda s: _restore_one(base / f"step_{s:08d}", like, shardings),
        directory,
    )


# What a corrupt, torn or lost step raises. Anything else, a failure on the
# device among them, propagates instead of restoring an older step.
_STEP_ERRORS = (OSError, ValueError, ProtocolError, ClusterError)


def _walk_back(candidates: list, load, where: str):
    """Load the newest loadable step; log every step skipped and why."""
    last_err: Optional[Exception] = None
    for s in reversed(candidates):
        try:
            return load(s), s
        except _STEP_ERRORS as e:
            log.warning("restore: skipping step %d in %s: %s: %s",
                        s, where, type(e).__name__, e)
            last_err = e
    raise FileNotFoundError(f"no restorable checkpoint in {where}: {last_err}")


def _restore_one(d: Path, like: Any, shardings: Any):
    manifest = json.loads((d / "manifest.json").read_text())
    leaves_like, treedef = jax.tree_util.tree_flatten(like)
    sh_leaves = (
        jax.tree_util.tree_flatten(shardings)[0] if shardings is not None else
        [None] * len(leaves_like)
    )
    if len(manifest["leaves"]) != len(leaves_like):
        raise ValueError(
            f"leaf count mismatch: ckpt {len(manifest['leaves'])} vs {len(leaves_like)}"
        )
    def finish(meta, fut, sh):
        with spans.span("restore.fetch") as sp:
            raw = fut.result().data
            sp.nbytes = len(raw)
        return _place(meta, raw, sh)

    out = []
    with _session(d) as cli:
        # depth-2 pipeline: leaf k+1 streams while leaf k is checksummed
        # and placed on device, so only ~one leaf is resident at a time
        fut = prev = None
        for meta, sh in zip(manifest["leaves"], sh_leaves):
            nxt = cli.get_bytes(meta["file"])
            if fut is not None:
                out.append(finish(prev[0], fut, prev[1]))
            fut, prev = nxt, (meta, sh)
        if fut is not None:
            out.append(finish(prev[0], fut, prev[1]))
    return jax.tree_util.tree_unflatten(treedef, out)
