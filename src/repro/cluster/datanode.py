"""DataNode: one storage node of the cluster = one ``XdfsServer``.

A data node is deliberately thin: the tuned single-host datapath (the
persistent session API with its zero-copy, syscall-batched engines) IS
the block transport, unchanged. This module only adds the control-plane
glue:

* a block store — the wrapped ``XdfsServer``'s root directory, holding
  one ``blk_<id>.bin`` file per block (clients and peers read/write
  them over ordinary xDFS sessions);
* registration + periodic heartbeats to the MetaNode, each carrying a
  **full block report** (scanned from the store, so the report is the
  ground truth even after a crash/restart);
* execution of the commands piggybacked on heartbeat replies:
  ``replicate`` pushes a block to a peer data node over a pooled xDFS
  session (node-to-node copy on the same zero-copy path — file-backed
  ``put`` means mmap/sendfile end to end), ``drop`` unlinks it.

Control traffic rides a :class:`~repro.cluster.leader.ControlChannel`:
``meta_address`` may be one ``(host, port)`` or a *list* of metanode
addresses, and the node fails over between them (transport faults back
off and rotate; ``not_leader`` rejections hop to the hinted leader). A
heartbeat answered with the ``unregistered`` error code — the metanode
restarted with a blank namespace, or a fresh standby promoted — makes
the node re-``REGISTER`` and retry, so a control-plane wipe heals
itself on the next beat. Command batches are **epoch-fenced**: a reply
stamped with a lower leader epoch than the channel has ever seen comes
from a deposed leader and its replicate/drop commands are discarded
(``stats["fenced_commands"]``).

``kill()`` simulates a crash for tests and demos: the server stops
accepting, in-flight sessions die, and heartbeats stop — the MetaNode's
failure detector takes it from there.
"""
from __future__ import annotations

import os
import threading
import uuid
from collections import deque
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from repro.cluster.leader import ControlChannel
from repro.cluster.scrub import Scrubber
from repro.cluster.wire import (
    CMD_DROP,
    CMD_REPLICATE,
    ERR_UNREGISTERED,
    ClusterError,
    ClusterMsg,
    block_name,
)
from repro.core.api import SessionPool, XdfsServer
from repro.core.engines.base import store_free_bytes
from repro.core.faults import RetryPolicy
from repro.core.resume import ManifestSidecar, ResumeSidecar, sweep_sidecars

BLOCK_PREFIX = "blk_"
BLOCK_SUFFIX = ".bin"
# recent control/command failures kept for inspection; older ones are
# counted in stats["errors_dropped"] instead of growing without bound
ERROR_BUFFER = 64


class DataNode:
    """One cluster storage node: an ``XdfsServer`` block store plus the
    MetaNode control loop. ``auto_heartbeat=False`` hands the beat to
    the caller (:meth:`heartbeat_once`) for deterministic tests."""

    def __init__(self, meta_address, root: str,
                 node_id: Optional[str] = None, engine: str = "mtedp",
                 host: str = "127.0.0.1",
                 heartbeat_interval: float = 0.5,
                 auto_heartbeat: bool = True,
                 n_channels: int = 2, batch_frames: int = 1,
                 pool: Optional[SessionPool] = None,
                 connect_timeout: float = 10.0,
                 policy: Optional[RetryPolicy] = None,
                 durability: int = 0,
                 capacity_bytes: Optional[int] = None,
                 scrub_rate: Optional[float] = None,
                 scrub_interval: Optional[float] = None,
                 clock=None, scrub_sleep=None):
        # two attempts preserves the historical redial-once behaviour;
        # pass a policy to trade it for deeper backoff
        self.policy = policy or RetryPolicy(attempts=2,
                                            connect_timeout=connect_timeout,
                                            io_timeout=10.0)
        self._ctrl = ControlChannel(meta_address, policy=self.policy)
        self.root = Path(root)
        self.node_id = node_id or f"dn-{uuid.uuid4().hex[:8]}"
        self.heartbeat_interval = heartbeat_interval
        self.auto_heartbeat = auto_heartbeat
        self.server = XdfsServer(engine=engine, root=str(self.root),
                                 host=host, durability=durability,
                                 capacity_bytes=capacity_bytes)
        self.capacity_bytes = capacity_bytes
        # at-rest verification: a rate-limited pass over the store pairing
        # block files with their .xdfs-manifest sidecars; injectable
        # clock/sleep keep chaos tests deterministic
        import time as _time

        self.scrubber = Scrubber(str(self.root), rate_limit=scrub_rate,
                                 clock=clock or _time.monotonic,
                                 sleep=scrub_sleep or _time.sleep)
        self.scrub_interval = scrub_interval
        self._scrub_thread: Optional[threading.Thread] = None
        # blocks the scrubber condemned: excluded from block reports
        # (the MetaNode treats them as gone and re-replicates) and
        # advertised under "corrupt" until the drop command lands
        self._corrupt: set = set()
        # node-to-node transport: one pooled session per peer, so many
        # re-replication copies to the same survivor share a negotiation.
        # integrity=True: a re-replicated block lands with a manifest at
        # the target, so the rebuilt replica is scrubbable too
        self.pool = pool or SessionPool(n_channels=n_channels,
                                        engine=engine,
                                        batch_frames=batch_frames,
                                        integrity=True,
                                        durability=durability)
        self._owns_pool = pool is None
        self._hb_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.errors: deque = deque(maxlen=ERROR_BUFFER)
        self.stats: Dict[str, int] = {
            "heartbeats": 0, "dropped": 0, "reregisters": 0,
            "fenced_commands": 0, "errors_dropped": 0, "scrub_corrupt": 0,
        }

    @property
    def meta_address(self) -> Tuple[str, int]:
        """The metanode address currently in use (failover-aware)."""
        return self._ctrl.current

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "DataNode":
        self.root.mkdir(parents=True, exist_ok=True)
        # a crashed transfer leaves orphan sidecars and atomic-commit temp
        # files; no session is live at startup, so sweeping is safe
        sweep_sidecars(str(self.root))
        self.server.start()
        self.register()
        if self.auto_heartbeat:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop,
                name=f"heartbeat-{self.node_id}", daemon=True)
            self._hb_thread.start()
        if self.scrub_interval is not None:
            self._scrub_thread = threading.Thread(
                target=self._scrub_loop,
                name=f"scrub-{self.node_id}", daemon=True)
            self._scrub_thread.start()
        return self

    @property
    def address(self) -> Tuple[str, int]:
        return self.server.address

    def kill(self) -> None:
        """Crash the node: sever every open session (clients holding
        pooled sessions see the peer die mid-transfer), stop serving
        blocks, and stop heartbeating. The MetaNode notices via its
        failure detector."""
        self._stop.set()
        self._ctrl.close()
        self.server.abort()
        if self._hb_thread is not None:
            self._hb_thread.join(5.0)
        if self._scrub_thread is not None:
            self._scrub_thread.join(5.0)
        if self._owns_pool:
            self.pool.close()

    def stop(self) -> None:
        self.kill()

    def __enter__(self) -> "DataNode":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- control loop ------------------------------------------------------

    def _meta_request(self, msg: ClusterMsg, body: dict) -> dict:
        """One request over the failover control channel."""
        return self._ctrl.call(msg, body)

    def register(self) -> dict:
        host, port = self.server.address
        return self._meta_request(ClusterMsg.REGISTER, {
            "node_id": self.node_id, "host": host, "port": port,
        })

    def block_ids(self) -> List[str]:
        """The store's ground truth, scanned fresh for every report.
        Blocks the scrubber condemned are EXCLUDED — the MetaNode must
        not count a corrupt replica as a live copy."""
        out = []
        for p in self.root.glob(f"{BLOCK_PREFIX}*{BLOCK_SUFFIX}"):
            bid = p.name[len(BLOCK_PREFIX):-len(BLOCK_SUFFIX)]
            if bid not in self._corrupt:
                out.append(bid)
        return sorted(out)

    def free_bytes(self) -> int:
        """Advertised store headroom (statvfs, or the synthetic capacity
        minus current usage when ``capacity_bytes`` is set)."""
        return store_free_bytes(str(self.root), self.capacity_bytes)

    # -- scrubbing ---------------------------------------------------------

    def scrub_once(self):
        """One deterministic scrub pass; condemned block ids feed the
        next heartbeat (and stay condemned until the drop lands)."""
        report = self.scrubber.scrub_once()
        for path in report.corrupt + report.missing:
            name = os.path.basename(path)
            if name.startswith(BLOCK_PREFIX) and name.endswith(BLOCK_SUFFIX):
                bid = name[len(BLOCK_PREFIX):-len(BLOCK_SUFFIX)]
                if bid not in self._corrupt:
                    self._corrupt.add(bid)
                    self.stats["scrub_corrupt"] += 1
        return report

    def _scrub_loop(self) -> None:
        while not self._stop.wait(self.scrub_interval):
            try:
                self.scrub_once()
            except Exception as e:  # noqa: BLE001 - scrub must not die
                self._note_error(e)

    def heartbeat_once(self) -> List[dict]:
        """Send one heartbeat + block report; execute every command the
        MetaNode piggybacked on the reply (unless the reply is fenced as
        coming from a deposed leader). A metanode that forgot us —
        restarted blank, or a freshly promoted standby whose journal
        predates our registration — answers ``unregistered``; recover by
        re-registering and beating again. Returns the executed commands."""
        body = {"node_id": self.node_id, "blocks": self.block_ids(),
                "free_bytes": self.free_bytes()}
        if self._corrupt:
            body["corrupt"] = sorted(self._corrupt)
        try:
            reply = self._meta_request(ClusterMsg.HEARTBEAT, body)
        except ClusterError as e:
            if e.code != ERR_UNREGISTERED:
                raise
            self.stats["reregisters"] += 1
            self.register()
            reply = self._meta_request(ClusterMsg.HEARTBEAT, body)
        self.stats["heartbeats"] += 1
        if self._ctrl.stale(reply):
            # a deposed leader answered before noticing its demotion:
            # executing its commands could resurrect deleted blocks or
            # drop live ones, so the whole batch is a no-op
            self.stats["fenced_commands"] += len(reply.get("commands", ()))
            return []
        cmds = reply.get("commands", [])
        for cmd in cmds:
            try:
                self._execute(cmd)
            except Exception as e:  # noqa: BLE001 - a failed copy must not
                # kill the beat loop; the MetaNode replans after the grace
                self._note_error(e)
        return cmds

    def _heartbeat_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_interval):
            try:
                self.heartbeat_once()
            except Exception as e:  # noqa: BLE001 - meta may be restarting
                self._note_error(e)

    def _note_error(self, e: BaseException) -> None:
        if len(self.errors) == self.errors.maxlen:
            self.stats["errors_dropped"] += 1
        self.errors.append(e)

    # -- command execution -------------------------------------------------

    def _execute(self, cmd: dict) -> None:
        op = cmd.get("op")
        if op == CMD_REPLICATE:
            self._replicate(cmd["block_id"], cmd["target"])
        elif op == CMD_DROP:
            self._drop(cmd["block_id"])
        else:
            raise ValueError(f"unknown cluster command {op!r}")

    def _replicate(self, block_id: str, target: dict) -> None:
        """Node-to-node copy: push one block file to a peer data node
        over a pooled xDFS session (file-backed put = the zero-copy
        mmap/sendfile send path, negotiated once per peer)."""
        path = self.root / block_name(block_id)
        addr = (target["host"], int(target["port"]))
        try:
            cli = self.pool.lease(addr)
            cli.put(str(path), block_name(block_id)).result()
        except Exception:
            self.pool.invalidate(addr)
            raise

    def _drop(self, block_id: str) -> None:
        path = self.root / block_name(block_id)
        try:
            os.unlink(path)
            self.stats["dropped"] += 1
        except FileNotFoundError:
            pass
        # GC the block's transfer state with it: a dangling sidecar would
        # make the scrubber report the block as "missing" forever
        ResumeSidecar(str(path)).clear()
        ManifestSidecar(str(path)).clear()
        self._corrupt.discard(block_id)
