"""MetaNode: the cluster's metadata/placement service (NameNode-style).

One MetaNode fronts a fleet of data nodes (each an ``XdfsServer`` — see
``datanode.py``). It owns the namespace (file -> ordered block list),
the placement policy (``placement.py``), and the failure detector; it
never touches block bytes. Data nodes register, then send periodic
heartbeats carrying a **full block report**; clients ask for placement
plans (put) and block locations (get) and move blocks themselves over
ordinary xDFS sessions, so the MetaNode stays off the datapath.

Control flow is pull-based: the MetaNode commands a data node only by
piggybacking ``replicate`` / ``drop`` commands on its next heartbeat
reply. That makes recovery idempotent — a node that crashes and comes
back simply beats again and picks up fresh commands computed from the
then-current state.

Durability (``journal_dir=``): every namespace mutation is a
write-ahead record (``cluster/journal.py``) appended-and-fsynced
BEFORE the reply goes out, with periodic atomic-replace snapshots that
truncate the journal. Restart = load snapshot -> replay journal ->
reconcile against the next round of full block reports: a crashed
MetaNode comes back with every acknowledged commit intact and heals
the soft state (liveness, locations, in-flight copies) from reality
rather than trusting a stale image of it.

Failover (``peers=``): run N metanodes over the same protocol. Exactly
one acts as **leader**; standbys tail the leader's journal via ``SYNC``
polls, reject mutating requests with ``not_leader`` (clients and
datanodes fail over along their address lists), and promote themselves
— bumping the **epoch** — when the leader's lease expires. Every OK
reply carries the leader epoch (``wire.EPOCH_FIELD``); receivers fence
replies from deposed leaders, which is what makes a zombie leader's
stale replicate/drop commands harmless. See ``cluster/leader.py`` and
docs/ARCHITECTURE.md ("Leader epochs and fencing").

The failure detector, re-replication planner, and leader lease are
driven by an injectable ``clock`` (same idiom as ``core/autotune.py``'s
controllers) so tests advance time deterministically; ``start()``
additionally runs a real ticker thread for live clusters.
"""
from __future__ import annotations

import socket
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Set, Tuple

from repro.cluster import placement
from repro.cluster.journal import (
    REC_COMMIT,
    REC_DELETE,
    REC_EPOCH,
    REC_MOVE,
    REC_MOVE_DONE,
    REC_REGISTER,
    recover,
)
from repro.cluster.leader import ControlChannel, LeaderLease
from repro.cluster.wire import (
    CMD_DROP,
    CMD_REPLICATE,
    EPOCH_FIELD,
    ERR_NOT_LEADER,
    ERR_UNREGISTERED,
    ClusterError,
    ClusterMsg,
    new_block_id,
    recv_msg,
    send_msg,
)
from repro.core.faults import RetriesExhausted, RetryPolicy

DEFAULT_REPLICATION = 2
# a commanded copy that has not shown up in a block report after this
# many timeouts is presumed failed and re-planned
REPLICATION_GRACE_TIMEOUTS = 3.0
# standbys promote after this many heartbeat timeouts without a
# successful SYNC (rank-staggered; see leader.LeaderLease)
LEASE_TIMEOUTS = 3.0
# snapshot + truncate the journal after this many appended records
SNAPSHOT_EVERY = 256
# journal records buffered in memory for standby SYNC catch-up; a
# standby further behind than this receives a full snapshot instead
SYNC_TAIL_MAX = 4096
# error-buffer bound (standby sync failures, ticker faults); overflow
# increments stats["errors_dropped"] instead of growing the heap
ERROR_BUFFER = 64

ROLE_LEADER = "leader"
ROLE_STANDBY = "standby"


class FailureDetector:
    """Heartbeat bookkeeping: a node is alive while its last beat is
    within ``timeout`` of ``clock()``. ``sweep()`` returns the nodes
    that died since the previous sweep; a later beat revives a node."""

    def __init__(self, timeout: float = 2.0,
                 clock: Callable[[], float] = time.monotonic):
        self.timeout = timeout
        self._clock = clock
        self._last: Dict[str, float] = {}
        self._dead: Set[str] = set()

    def beat(self, node_id: str) -> None:
        self._last[node_id] = self._clock()
        self._dead.discard(node_id)

    def is_alive(self, node_id: str) -> bool:
        last = self._last.get(node_id)
        return (last is not None and node_id not in self._dead
                and self._clock() - last <= self.timeout)

    def alive(self) -> Set[str]:
        return {n for n in self._last if self.is_alive(n)}

    def sweep(self) -> List[str]:
        now = self._clock()
        newly_dead = sorted(
            n for n, last in self._last.items()
            if n not in self._dead and now - last > self.timeout
        )
        self._dead.update(newly_dead)
        return newly_dead

    def forget(self, node_id: str) -> None:
        self._last.pop(node_id, None)
        self._dead.discard(node_id)


@dataclass
class NodeInfo:
    node_id: str
    host: str
    port: int
    blocks: Set[str] = field(default_factory=set)
    # soft state, refreshed by every heartbeat and deliberately NOT
    # journaled: after a recovery it re-derives within one beat
    free_bytes: Optional[int] = None

    @property
    def address(self) -> Tuple[str, int]:
        return (self.host, self.port)

    def as_dict(self) -> dict:
        return {"node_id": self.node_id, "host": self.host,
                "port": self.port}


class MetaNode:
    """The metadata/placement service. Thread-safe; all state under one
    lock. Usable fully in-process (handlers are plain methods) or as a
    TCP service via :meth:`start`."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 replication: int = DEFAULT_REPLICATION,
                 heartbeat_timeout: float = 2.0,
                 tick_interval: Optional[float] = None,
                 auto_rebalance: bool = False,
                 clock: Callable[[], float] = time.monotonic,
                 journal_dir: Optional[str] = None,
                 journal_fsync: bool = True,
                 snapshot_every: int = SNAPSHOT_EVERY,
                 peers: Tuple[Tuple[str, int], ...] = (),
                 meta_id: Optional[str] = None,
                 lease_timeout: Optional[float] = None,
                 rank: int = 0,
                 policy: Optional[RetryPolicy] = None):
        self.host = host
        self._port = port
        self.replication = max(1, int(replication))
        self.heartbeat_timeout = heartbeat_timeout
        self.tick_interval = (heartbeat_timeout / 4.0
                              if tick_interval is None else tick_interval)
        self.auto_rebalance = auto_rebalance
        self._clock = clock
        self.detector = FailureDetector(heartbeat_timeout, clock)
        self._lock = threading.RLock()
        self.nodes: Dict[str, NodeInfo] = {}
        self.files: Dict[str, dict] = {}  # name -> {size, block_size, blocks}
        self.locations: Dict[str, Set[str]] = {}  # block id -> node ids
        self._commands: Dict[str, List[dict]] = {}  # node id -> queued cmds
        self._inflight: Dict[Tuple[str, str], float] = {}  # (blk, dst) -> t
        self._pending_drops: List[Tuple[str, str, str]] = []  # blk, src, dst
        self.lost_blocks: Set[str] = set()
        self.meta_id = meta_id or f"meta-{id(self) & 0xFFFF:04x}"
        self.stats: Dict[str, int] = {
            "heartbeats": 0, "plans": 0, "commits": 0, "lookups": 0,
            "re_replications": 0, "rebalance_moves": 0,
            "journal_records": 0, "snapshots": 0, "replayed_records": 0,
            "promotions": 0,
            "errors_dropped": 0, "corrupt_reported": 0,
            "full_nodes_avoided": 0,
        }
        self.errors: deque = deque(maxlen=ERROR_BUFFER)
        # -- durability ------------------------------------------------
        self.seq = 0  # journal sequence of the last applied record
        self.epoch = 0  # current leader epoch (0 = pre-election)
        self.journal = None
        self.snapshot_every = max(1, int(snapshot_every))
        self._records_since_snapshot = 0
        self._tail: deque = deque(maxlen=SYNC_TAIL_MAX)
        if journal_dir is not None:
            self.journal, state, records = recover(journal_dir,
                                                   fsync=journal_fsync)
            if state is not None:
                self._load_state(state)
            for seq, tag, body in records:
                if seq <= self.seq:
                    # already reflected in the snapshot (a crash landed
                    # between write_snapshot's os.replace and the
                    # journal truncate); re-applying would re-reclaim
                    continue
                self._apply(tag, body)
                self.seq = seq
                self.stats["replayed_records"] += 1
            # every recovered node gets a full timeout to re-attach
            # before the detector may declare it dead
            for node_id in self.nodes:
                self.detector.beat(node_id)
        # -- failover --------------------------------------------------
        self.peers = [(p[0], int(p[1])) for p in peers]
        self.role = ROLE_STANDBY if self.peers else ROLE_LEADER
        self.policy = policy or RetryPolicy(
            attempts=1, connect_timeout=2.0,
            io_timeout=max(2.0, heartbeat_timeout))
        self._upstream: Optional[ControlChannel] = None
        self._leader_addr: Optional[Tuple[str, int]] = None
        self.lease = LeaderLease(
            lease_timeout if lease_timeout is not None
            else LEASE_TIMEOUTS * heartbeat_timeout,
            rank=rank, clock=clock)
        self._lsock: Optional[socket.socket] = None
        self._threads: List[threading.Thread] = []
        self._conns: List[socket.socket] = []
        self._stopping = False

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "MetaNode":
        lsock = socket.socket()
        lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lsock.bind((self.host, self._port))
        lsock.listen(64)
        lsock.settimeout(0.25)
        self._lsock = lsock
        self._resolve_role()
        acc = threading.Thread(target=self._accept_loop,
                               name="meta-accept", daemon=True)
        acc.start()
        self._threads.append(acc)
        if self.tick_interval > 0:
            tk = threading.Thread(target=self._tick_loop,
                                  name="meta-tick", daemon=True)
            tk.start()
            self._threads.append(tk)
        return self

    def _resolve_role(self) -> None:
        """Join the metanode group: if any peer currently leads with an
        epoch at least ours, follow it (a restarted deposed leader
        rejoins as standby instead of split-braining); otherwise assume
        leadership with a bumped, journaled epoch."""
        best = None
        for addr in self.peers:
            try:
                ch = ControlChannel([addr], policy=self.policy)
                try:
                    info = ch.call(ClusterMsg.PING, {})
                finally:
                    ch.close()
            except (RetriesExhausted, ClusterError, OSError):
                continue
            if (info.get("role") == ROLE_LEADER
                    and info.get(EPOCH_FIELD, 0) >= self.epoch):
                if best is None or info[EPOCH_FIELD] > best[1]:
                    best = (addr, info[EPOCH_FIELD])
        with self._lock:
            if best is not None:
                self.role = ROLE_STANDBY
                self._leader_addr = best[0]
                self.epoch = max(self.epoch, best[1])
                self.lease.renew()
            else:
                self._assume_leadership(self.epoch + 1)
        if self.role == ROLE_STANDBY and self._upstream is None:
            self._upstream = ControlChannel(self.peers, policy=self.policy,
                                            what="leader")

    @property
    def address(self) -> Tuple[str, int]:
        assert self._lsock is not None, "metanode not started"
        return self._lsock.getsockname()[:2]

    def kill(self) -> None:
        """Crash the metanode: no snapshot, no goodbye — the listener
        and every open control connection are severed. Whatever the
        journal fsynced is all a restart gets (that is the point)."""
        self._stopping = True
        if self._lsock is not None:
            try:
                self._lsock.close()
            except OSError:
                pass
        for conn in list(self._conns):
            try:
                conn.close()
            except OSError:
                pass
        if self._upstream is not None:
            self._upstream.close()
        if self.journal is not None:
            self.journal.close()
        for t in self._threads:
            t.join(5.0)

    def stop(self, timeout: float = 5.0) -> None:
        """Graceful shutdown: checkpoint the journal into a snapshot
        (fast restart), then close."""
        if self.journal is not None and not self._stopping:
            try:
                self.snapshot()
            except OSError:
                pass
        self.kill()

    def __enter__(self) -> "MetaNode":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _accept_loop(self) -> None:
        while not self._stopping:
            try:
                conn, _ = self._lsock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            self._conns.append(conn)
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            while not self._stopping:
                try:
                    msg, body = recv_msg(conn)
                except (ConnectionError, OSError):
                    return
                try:
                    send_msg(conn, ClusterMsg.OK, self.dispatch(msg, body))
                except ClusterError as e:
                    err = {"error": str(e)}
                    if e.code:
                        err["code"] = e.code
                    if e.hint:
                        err["leader"] = list(e.hint)
                    send_msg(conn, ClusterMsg.ERR, err)
        except OSError:
            pass
        finally:
            try:
                self._conns.remove(conn)
            except ValueError:
                pass
            try:
                conn.close()
            except OSError:
                pass

    def _tick_loop(self) -> None:
        while not self._stopping:
            time.sleep(self.tick_interval)
            try:
                if self.role == ROLE_LEADER:
                    self.tick()
                    if self.auto_rebalance:
                        self.rebalance()
                    self.maybe_snapshot()
                else:
                    self.standby_poll()
            except Exception as e:  # noqa: BLE001 - the ticker must survive
                self._note_error(e)

    def _note_error(self, e: BaseException) -> None:
        if len(self.errors) == self.errors.maxlen:
            self.stats["errors_dropped"] += 1
        self.errors.append(e)

    # -- durability: journal append / apply / snapshot ---------------------

    def _append(self, tag: str, body: dict) -> None:
        """Write-ahead: the record is on disk (fsynced) before the
        caller applies it or acks the client; locked by caller."""
        self.seq += 1
        if self.journal is not None:
            self.journal.append(self.seq, tag, body)
        self._tail.append((self.seq, tag, body))
        self._records_since_snapshot += 1
        self.stats["journal_records"] += 1

    def _apply(self, tag: str, body: dict) -> None:
        """Apply one journal record to in-memory state. Replay, live
        mutation, and standby SYNC all funnel through here, so the
        three can never drift."""
        if tag == REC_REGISTER:
            node_id = body["node_id"]
            self.nodes[node_id] = NodeInfo(
                node_id, body["host"], int(body["port"]),
                self.nodes.get(node_id, NodeInfo(node_id, "", 0)).blocks,
            )
            self._commands.setdefault(node_id, [])
        elif tag == REC_COMMIT:
            old = self.files.get(body["name"])
            self.files[body["name"]] = {
                "size": int(body["size"]),
                "block_size": int(body["block_size"]),
                "blocks": [{"id": b["id"], "offset": int(b["offset"]),
                            "length": int(b["length"]),
                            "crc32": int(b["crc32"])}
                           for b in body["blocks"]],
            }
            # optimistic locations so an immediate get works before the
            # writers' next block reports arrive (and so a restarted
            # metanode can serve lookups before its first reports)
            for b in body["blocks"]:
                self.locations.setdefault(b["id"], set()).update(b["nodes"])
            if old is not None:
                # overwrite: reclaim only blocks the new version dropped
                # — a duplicated record (replay racing a snapshot) has
                # old == new and must not drop the live blocks
                kept = {b["id"] for b in body["blocks"]}
                stale = [b for b in old["blocks"] if b["id"] not in kept]
                if stale:
                    self._reclaim({"blocks": stale})
        elif tag == REC_DELETE:
            meta = self.files.pop(body["name"], None)
            if meta is not None:
                self._reclaim(meta)
        elif tag == REC_MOVE:
            mv = (body["block_id"], body["src"], body["dst"])
            if mv not in self._pending_drops:
                self._pending_drops.append(mv)
        elif tag == REC_MOVE_DONE:
            self._pending_drops = [
                (b, s, d) for (b, s, d) in self._pending_drops
                if not (b == body["block_id"] and d == body["dst"])]
        elif tag == REC_EPOCH:
            self.epoch = int(body["epoch"])
        else:
            raise ClusterError(f"unknown journal record tag {tag!r}")

    def _state_snapshot(self) -> dict:
        # every container is copied, never aliased: handle_sync's reply
        # is JSON-serialized AFTER the lock is released, racing live
        # commits if the snapshot held references into self.files
        with self._lock:
            return {
                "schema": 1,
                "seq": self.seq,
                "epoch": self.epoch,
                "nodes": [{**n.as_dict(), "blocks": sorted(n.blocks)}
                          for n in self.nodes.values()],
                "files": {name: {"size": m["size"],
                                 "block_size": m["block_size"],
                                 "blocks": [dict(b) for b in m["blocks"]]}
                          for name, m in self.files.items()},
                "locations": {b: sorted(h)
                              for b, h in self.locations.items()},
                "pending_drops": [list(m) for m in self._pending_drops],
            }

    def _load_state(self, state: dict) -> None:
        self.seq = int(state.get("seq", 0))
        self.epoch = int(state.get("epoch", 0))
        self.nodes = {
            n["node_id"]: NodeInfo(n["node_id"], n["host"], int(n["port"]),
                                   set(n.get("blocks", ())))
            for n in state.get("nodes", ())
        }
        self.files = {
            name: {"size": int(m["size"]),
                   "block_size": int(m["block_size"]),
                   "blocks": [dict(b) for b in m["blocks"]]}
            for name, m in (state.get("files") or {}).items()
        }
        self.locations = {b: set(h)
                          for b, h in (state.get("locations") or {}).items()}
        self._pending_drops = [tuple(m)
                               for m in state.get("pending_drops", ())]
        for node_id in self.nodes:
            self._commands.setdefault(node_id, [])

    def snapshot(self) -> None:
        """Atomic-replace snapshot + journal truncation (no-op without
        a journal)."""
        if self.journal is None:
            return
        with self._lock:  # capture + truncate atomically vs. appends
            state = self._state_snapshot()
            self.journal.write_snapshot(state)
            self._records_since_snapshot = 0
            self.stats["snapshots"] += 1

    def maybe_snapshot(self) -> None:
        if (self.journal is not None
                and self._records_since_snapshot >= self.snapshot_every):
            self.snapshot()

    # -- failover: leadership, standby sync --------------------------------

    def _assume_leadership(self, epoch: int) -> None:
        """Become the leader at ``epoch`` (journaled so a restart keeps
        the fencing order); locked by caller or single-threaded start."""
        with self._lock:
            self._append(REC_EPOCH, {"epoch": epoch,
                                     "meta_id": self.meta_id})
            self._apply(REC_EPOCH, {"epoch": epoch})
            self.role = ROLE_LEADER
            # give every known node a full timeout to find us before
            # the detector may declare it dead
            for node_id in self.nodes:
                self.detector.beat(node_id)

    def promote(self) -> None:
        """Standby -> leader: bump past every epoch we have ever seen
        (our own and the deposed leader's)."""
        seen = self.epoch
        if self._upstream is not None:
            seen = max(seen, self._upstream.epoch)
        self._assume_leadership(seen + 1)
        self.stats["promotions"] += 1

    def standby_poll(self) -> None:
        """One SYNC round against the peer list: tail new journal
        records (or a full snapshot when too far behind), renew the
        lease on success, and promote when the lease has expired."""
        if self.role != ROLE_STANDBY:
            return
        try:
            reply = self._upstream.call(ClusterMsg.SYNC, {"since": self.seq})
        except (RetriesExhausted, ClusterError, OSError) as e:
            self._note_error(e)
            if self.lease.expired():
                self.promote()
            return
        self._apply_sync(reply)
        self.lease.renew()
        self._leader_addr = self._upstream.current

    def _apply_sync(self, reply: dict) -> None:
        with self._lock:
            snap = reply.get("snapshot")
            if snap is not None:
                self._load_state(snap)
                if self.journal is not None:
                    self.journal.write_snapshot(snap)
                    self._records_since_snapshot = 0
            for seq, tag, body in reply.get("records", ()):
                if seq <= self.seq:
                    continue  # duplicate tail overlap: already applied
                if self.journal is not None:
                    self.journal.append(seq, tag, body)
                self._apply(tag, body)
                self.seq = seq
            got = reply.get(EPOCH_FIELD)
            if isinstance(got, int) and got > self.epoch:
                self.epoch = got

    def handle_ping(self, body: dict) -> dict:
        return {"meta_id": self.meta_id, "role": self.role,
                "seq": self.seq}

    def handle_sync(self, body: dict) -> dict:
        self._require_leader()
        since = int(body.get("since", 0))
        with self._lock:
            if since > self.seq:
                # the poller is ahead of us (it promoted and wrote its
                # own records while we were deposed): full resync
                return {"snapshot": self._state_snapshot(),
                        "seq": self.seq}
            if since == self.seq:
                return {"records": [], "seq": self.seq}
            if self._tail and self._tail[0][0] <= since + 1:
                records = [[s, t, b] for s, t, b in self._tail if s > since]
                return {"records": records, "seq": self.seq}
            return {"snapshot": self._state_snapshot(), "seq": self.seq}

    def _require_leader(self) -> None:
        if self.role != ROLE_LEADER:
            raise ClusterError(
                f"{self.meta_id} is a standby (epoch {self.epoch})",
                code=ERR_NOT_LEADER, hint=self._leader_addr)

    # -- dispatch ----------------------------------------------------------

    def dispatch(self, msg: ClusterMsg, body: dict) -> dict:
        handlers = {
            ClusterMsg.REGISTER: self.handle_register,
            ClusterMsg.HEARTBEAT: self.handle_heartbeat,
            ClusterMsg.PLAN_PUT: self.handle_plan_put,
            ClusterMsg.COMMIT: self.handle_commit,
            ClusterMsg.LOOKUP: self.handle_lookup,
            ClusterMsg.LIST: self.handle_list,
            ClusterMsg.DELETE: self.handle_delete,
            ClusterMsg.STATE: self.handle_state,
            ClusterMsg.PING: self.handle_ping,
            ClusterMsg.SYNC: self.handle_sync,
        }
        h = handlers.get(msg)
        if h is None:
            raise ClusterError(f"unhandled control message {msg!r}")
        if msg not in (ClusterMsg.PING, ClusterMsg.SYNC,
                       ClusterMsg.STATE):
            self._require_leader()
        out = h(body)
        # every reply carries the sender's epoch: commit acks and
        # heartbeat command batches are fenceable at the receiver
        out.setdefault(EPOCH_FIELD, self.epoch)
        return out

    # -- node control plane ------------------------------------------------

    def handle_register(self, body: dict) -> dict:
        node_id = str(body["node_id"])
        rec = {"node_id": node_id, "host": str(body["host"]),
               "port": int(body["port"])}
        with self._lock:
            self._append(REC_REGISTER, rec)
            self._apply(REC_REGISTER, rec)
            self.detector.beat(node_id)
        return {"heartbeat_timeout": self.heartbeat_timeout,
                "replication": self.replication}

    def handle_heartbeat(self, body: dict) -> dict:
        node_id = str(body["node_id"])
        report = {str(b) for b in body.get("blocks", ())}
        with self._lock:
            node = self.nodes.get(node_id)
            if node is None:
                raise ClusterError(f"unregistered node {node_id!r}",
                                   code=ERR_UNREGISTERED)
            self.detector.beat(node_id)
            self.stats["heartbeats"] += 1
            # full block report: reconcile the location index by diff
            for blk in node.blocks - report:
                holders = self.locations.get(blk)
                if holders is not None:
                    holders.discard(node_id)
                    if not holders:
                        del self.locations[blk]
            for blk in report - node.blocks:
                self.locations.setdefault(blk, set()).add(node_id)
            node.blocks = report
            if body.get("free_bytes") is not None:
                node.free_bytes = int(body["free_bytes"])
            for blk in report:
                self._inflight.pop((blk, node_id), None)
                self.lost_blocks.discard(blk)
            # scrub verdicts: evict the condemned replica from the
            # location index explicitly — the block-report diff above
            # cannot be relied on, because a replica the client committed
            # optimistically may never have appeared in ``node.blocks``
            # (put and condemn within one beat interval). Then command
            # the node to reclaim the bad file; the next tick
            # re-replicates from a surviving good holder
            for blk in body.get("corrupt", ()):
                blk = str(blk)
                self.stats["corrupt_reported"] += 1
                node.blocks.discard(blk)
                holders = self.locations.get(blk)
                if holders is not None:
                    holders.discard(node_id)
                    if not holders:
                        del self.locations[blk]
                self._enqueue(node_id, {"op": CMD_DROP, "block_id": blk})
            self._settle_pending_drops()
            cmds = self._commands.get(node_id, [])
            self._commands[node_id] = []
        return {"commands": cmds}

    def _settle_pending_drops(self) -> None:
        """Rebalance moves drop their source replica only AFTER the
        destination's block report confirms the copy (never reduces
        replication on a failed move); locked by caller."""
        still = []
        for blk, src, dst in self._pending_drops:
            holders = self.locations.get(blk, set())
            if dst in holders and self.detector.is_alive(dst):
                if src in holders:
                    self._enqueue(src, {"op": CMD_DROP, "block_id": blk})
                self._append(REC_MOVE_DONE, {"block_id": blk, "dst": dst})
            elif (blk, dst) in self._inflight:
                still.append((blk, src, dst))
            else:
                # the move expired/failed — abandon the drop entirely
                self._append(REC_MOVE_DONE, {"block_id": blk, "dst": dst})
        self._pending_drops = still

    def _enqueue(self, node_id: str, cmd: dict) -> None:
        self._commands.setdefault(node_id, []).append(cmd)

    # -- failure detection + re-replication --------------------------------

    def tick(self) -> List[str]:
        """One failure-detector sweep + re-replication planning pass.
        Returns the nodes that died this tick. Under-replicated blocks
        (for ANY reason: a dead node, a degraded put, an expired copy
        command) get ``replicate`` commands enqueued on live holders,
        with in-flight suppression so repeated ticks do not spam
        duplicate copies."""
        with self._lock:
            newly_dead = self.detector.sweep()
            alive = self.detector.alive() & set(self.nodes)
            now = self._clock()
            grace = REPLICATION_GRACE_TIMEOUTS * self.heartbeat_timeout
            self._inflight = {k: t for k, t in self._inflight.items()
                              if now - t <= grace and k[1] in alive}
            replicas, lost = placement.scan_replication(
                self.files, self.locations, alive, self.replication)
            self.lost_blocks |= lost
            load = {n: len(self.nodes[n].blocks) for n in alive}
            moves = placement.plan_replication(
                replicas, alive, self.replication, load,
                skip=self._inflight.keys(),
            )
            for mv in moves:
                self._command_copy(mv, now)
                self.stats["re_replications"] += 1
            return newly_dead

    def _command_copy(self, mv: placement.Move, now: float) -> None:
        target = self.nodes[mv.dst]
        self._enqueue(mv.src, {
            "op": CMD_REPLICATE, "block_id": mv.block_id,
            "target": target.as_dict(),
        })
        self._inflight[(mv.block_id, mv.dst)] = now

    def rebalance(self) -> List[placement.Move]:
        """Plan + enqueue moves that even out block counts across live
        nodes; sources are dropped only after the destination confirms
        (see :meth:`_settle_pending_drops`). Returns the planned moves."""
        with self._lock:
            alive = self.detector.alive() & set(self.nodes)
            holdings = {n: set(self.nodes[n].blocks) for n in alive}
            pending_dsts = {(b, d) for b, _s, d in self._pending_drops}
            now = self._clock()
            moves = []
            for mv in placement.plan_rebalance(holdings):
                if ((mv.block_id, mv.dst) in self._inflight
                        or (mv.block_id, mv.dst) in pending_dsts):
                    continue
                self._command_copy(mv, now)
                rec = {"block_id": mv.block_id, "src": mv.src,
                       "dst": mv.dst}
                self._append(REC_MOVE, rec)
                self._apply(REC_MOVE, rec)
                self.stats["rebalance_moves"] += 1
                moves.append(mv)
            return moves

    # -- client control plane ----------------------------------------------

    def handle_plan_put(self, body: dict) -> dict:
        name = str(body["name"])
        size = int(body["size"])
        block_size = int(body["block_size"])
        if block_size <= 0:
            raise ClusterError(f"bad block_size {block_size}")
        exclude = set(body.get("exclude") or ())
        with self._lock:
            alive = sorted(self.detector.alive() & set(self.nodes))
            if exclude:
                # a re-planning client saw these nodes fail mid-put; steer
                # around them, unless that would leave nothing to place on
                pref = [n for n in alive if n not in exclude]
                if pref:
                    alive = pref
            if not alive:
                raise ClusterError("no live data nodes to place on")
            # disk pressure: steer around nodes that advertised too little
            # free space for even one block of this put, unless that would
            # leave nothing to place on (a degraded plan still lets the
            # other replicas land; the full node refuses with disk_full)
            free = {n: self.nodes[n].free_bytes for n in alive}
            roomy = placement.filter_roomy(alive, free,
                                           min(block_size, max(size, 1)))
            if len(roomy) < len(alive):
                self.stats["full_nodes_avoided"] += len(alive) - len(roomy)
                alive = roomy
            rf = min(self.replication, len(alive))
            load = {n: len(self.nodes[n].blocks) for n in alive}
            n_blocks = (size + block_size - 1) // block_size
            plan = placement.plan_put(n_blocks, load, rf)
            blocks = []
            for i, nodes in enumerate(plan):
                off = i * block_size
                blocks.append({
                    "id": new_block_id(), "offset": off,
                    "length": min(block_size, size - off),
                    "nodes": [self.nodes[n].as_dict() for n in nodes],
                })
            self.stats["plans"] += 1
        return {"name": name, "size": size, "block_size": block_size,
                "rf": rf, "blocks": blocks}

    def handle_commit(self, body: dict) -> dict:
        name = str(body["name"])
        blocks = body["blocks"]
        with self._lock:
            for blk in blocks:
                if not blk["nodes"]:
                    raise ClusterError(
                        f"block {blk['id']} of {name!r} has no replicas")
            rec = {
                "name": name, "size": int(body["size"]),
                "block_size": int(body["block_size"]),
                "blocks": [{"id": str(b["id"]), "offset": int(b["offset"]),
                            "length": int(b["length"]),
                            "crc32": int(b["crc32"]),
                            "nodes": [str(n) for n in b["nodes"]]}
                           for b in blocks],
            }
            # write-ahead: the commit is fsynced before the ack — an
            # acknowledged commit survives kill -9
            self._append(REC_COMMIT, rec)
            self._apply(REC_COMMIT, rec)
            self.stats["commits"] += 1
        return {"ok": True, "blocks": len(blocks)}

    def handle_lookup(self, body: dict) -> dict:
        name = str(body["name"])
        with self._lock:
            meta = self.files.get(name)
            if meta is None:
                raise ClusterError(f"unknown file {name!r}")
            alive = self.detector.alive()
            blocks = []
            for blk in meta["blocks"]:
                live = sorted(self.locations.get(blk["id"], set()) & alive)
                blocks.append({
                    **blk,
                    "nodes": [self.nodes[n].as_dict() for n in live
                              if n in self.nodes],
                })
            self.stats["lookups"] += 1
            return {"name": name, "size": meta["size"],
                    "block_size": meta["block_size"], "blocks": blocks}

    def handle_list(self, body: dict) -> dict:
        prefix = str(body.get("prefix", ""))
        with self._lock:
            names = sorted(n for n in self.files if n.startswith(prefix))
        return {"names": names}

    def handle_delete(self, body: dict) -> dict:
        name = str(body["name"])
        with self._lock:
            if name not in self.files:
                raise ClusterError(f"unknown file {name!r}")
            self._append(REC_DELETE, {"name": name})
            self._apply(REC_DELETE, {"name": name})
        return {"ok": True}

    def _reclaim(self, meta: dict) -> None:
        """Enqueue drops for every replica of a dereferenced file's
        blocks; locked by caller."""
        for blk in meta["blocks"]:
            for node_id in self.locations.pop(blk["id"], set()):
                if node_id in self.nodes:
                    self._enqueue(node_id,
                                  {"op": CMD_DROP, "block_id": blk["id"]})
            self.lost_blocks.discard(blk["id"])

    def handle_state(self, body: dict) -> dict:
        with self._lock:
            alive = self.detector.alive()
            return {
                "replication": self.replication,
                "role": self.role,
                "meta_id": self.meta_id,
                "seq": self.seq,
                "nodes": [{**n.as_dict(), "alive": nid in alive,
                           "blocks": len(n.blocks)}
                          for nid, n in sorted(self.nodes.items())],
                "files": len(self.files),
                "under_replicated": sum(
                    1 for c in self._replica_counts() if 0 < c < self.replication),
                "lost": sorted(self.lost_blocks),
            }

    # -- observability (in-process) ----------------------------------------

    def _replica_counts(self) -> List[int]:
        alive = self.detector.alive()
        return [len(self.locations.get(blk["id"], set()) & alive)
                for meta in self.files.values() for blk in meta["blocks"]]

    def replication_of(self, name: str) -> List[int]:
        """Live replica count per block of ``name`` — the block-report
        view tests assert re-replication against."""
        with self._lock:
            meta = self.files.get(name)
            if meta is None:
                raise KeyError(name)
            alive = self.detector.alive()
            return [len(self.locations.get(blk["id"], set()) & alive)
                    for blk in meta["blocks"]]
