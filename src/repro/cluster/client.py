"""ClusterClient: striped, replicated put/get over a fleet of data nodes.

A put asks the MetaNode for a placement plan (``PLAN_PUT``), then writes
every block to each of its ``rf`` planned nodes **in parallel** over
pooled per-node xDFS sessions — one negotiated multi-channel session per
data node, every block a pipelined ``put`` future on it, so the stripe
rides the batched zero-copy datapath unchanged. The client computes a
CRC32 per block and ``COMMIT``\\ s the achieved replica sets: a write
that lost a replica mid-put (node died) still commits as long as every
block landed somewhere, and the MetaNode's re-replication heals it back
to ``rf``.

A get resolves block locations (``LOOKUP``), fans the fetches out across
replicas (block *i* prefers holder ``i mod len(holders)``, spreading
read load), verifies each block's CRC, and **fails over**: a dead node
or a corrupt replica just moves the fetch to the next live holder.

Metanode traffic rides a :class:`~repro.cluster.leader.ControlChannel`:
``meta_address`` may be one ``(host, port)`` or a *list* of metanode
addresses. Transport faults rotate the list with the policy's backoff;
``not_leader`` rejections hop to the hinted leader, so a client created
against the whole metanode group keeps working across a failover.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

from repro.cluster.leader import ControlChannel
from repro.cluster.wire import (
    ClusterError,
    ClusterMsg,
    block_name,
)
from repro.core import spans
from repro.core.api import SessionPool
from repro.core.faults import RetryPolicy
from repro.core.integrity import block_crc
from repro.core.session import BusyError, DEFAULT_BLOCK, DiskFullError

DEFAULT_CLUSTER_BLOCK = 4 << 20


def _crc(view) -> int:
    return block_crc(view)


class ClusterClient:
    """Client-side striping/replication over per-node pooled sessions."""

    def __init__(self, meta_address,
                 block_size: int = DEFAULT_CLUSTER_BLOCK,
                 n_channels: int = 2, engine: str = "mtedp",
                 batch_frames: int = 1,
                 session_block: int = DEFAULT_BLOCK,
                 pool: Optional[SessionPool] = None,
                 policy: Optional[RetryPolicy] = None,
                 connect_timeout: float = 10.0,
                 integrity: bool = True,
                 durability=0):
        self.block_size = block_size
        # one policy drives every deadline/retry decision: metanode dials,
        # metanode requests (including failover rotation), and the bounded
        # put re-plan loop
        self.policy = policy or RetryPolicy(connect_timeout=connect_timeout)
        self._ctrl = ControlChannel(meta_address, policy=self.policy)
        # integrity sessions by default: every block put leaves a CRC
        # manifest sidecar at the data node, which is what the scrubber
        # verifies at rest; ``durability`` is the requested commit policy
        # (the node's own floor still applies)
        self.pool = pool or SessionPool(
            n_channels=n_channels, engine=engine,
            block_size=min(session_block, block_size),
            batch_frames=batch_frames, integrity=integrity,
            durability=durability)
        self._owns_pool = pool is None
        self.stats: Dict[str, int] = {
            "puts": 0, "gets": 0, "blocks_written": 0,
            "replica_failovers": 0, "degraded_blocks": 0, "replans": 0,
            "busy_retries": 0, "disk_full_refusals": 0,
        }

    # -- metanode control --------------------------------------------------

    def _call(self, msg: ClusterMsg, body: dict) -> dict:
        # ClusterError replies pass straight through (a refused request is
        # not a transport fault); dead connections and not_leader redirects
        # fail over along the address list inside the channel
        return self._ctrl.call(msg, body)

    @property
    def meta_address(self):
        """The metanode address currently in use (failover-aware)."""
        return self._ctrl.current

    # -- put ---------------------------------------------------------------

    def put(self, name: str, data: Optional[bytes] = None,
            src: Optional[str] = None) -> dict:
        """Stripe ``data`` (or the contents of file ``src``) across the
        cluster under ``name``. Returns the commit summary."""
        if data is None:
            if src is None:
                raise ValueError("put needs data or src")
            with open(src, "rb") as f:
                data = f.read()
        view = memoryview(data)
        plan = self._call(ClusterMsg.PLAN_PUT, {
            "name": name, "size": len(view), "block_size": self.block_size,
        })
        blocks_plan: List[dict] = list(plan["blocks"])
        achieved: List[List[str]] = [[] for _ in blocks_plan]
        failed_nodes: set = set()

        def write_round(indices: List[int]) -> None:
            # fan out: every (block, replica) is one pipelined put future
            # on that node's pooled session; sessions serialize per node,
            # nodes stream in parallel
            writes = []  # (block index, node dict, future or error)
            for i in indices:
                blk = blocks_plan[i]
                piece = view[blk["offset"]:blk["offset"] + blk["length"]]
                for node in blk["nodes"]:
                    if node["node_id"] in achieved[i]:
                        continue
                    addr = (node["host"], node["port"])
                    try:
                        cli = self.pool.lease(addr)
                        fut = cli.put(None, block_name(blk["id"]), data=piece)
                    except Exception as e:  # noqa: BLE001 - dead node: the
                        # block's other replicas may still land
                        self.pool.invalidate(addr)
                        fut = e
                    writes.append((i, node, fut))
            for i, node, fut in writes:
                if isinstance(fut, Exception):
                    failed_nodes.add(node["node_id"])
                    continue
                try:
                    fut.result()
                    achieved[i].append(node["node_id"])
                    self.stats["blocks_written"] += 1
                except DiskFullError:
                    # typed refusal, not a transport fault: the session
                    # survives, so keep the pooled connection but steer the
                    # re-plan away from the full node
                    failed_nodes.add(node["node_id"])
                    self.stats["disk_full_refusals"] += 1
                except BusyError:
                    # transient admission pushback: the node is healthy, so
                    # do NOT exclude it from the re-plan — the replan loop's
                    # backoff is the retry delay it asked for
                    self.stats["busy_retries"] += 1
                except Exception:  # noqa: BLE001
                    failed_nodes.add(node["node_id"])
                    self.pool.invalidate((node["host"], node["port"]))

        write_round(list(range(len(blocks_plan))))
        pending = [i for i in range(len(blocks_plan)) if not achieved[i]]
        delays = iter(self.policy.delays())
        while pending:
            # every replica of some block failed: back off, then ask the
            # metanode for fresh placements that avoid the nodes we just
            # watched die, and retry only the holeful blocks
            try:
                delay = next(delays)
            except StopIteration:
                raise ClusterError(
                    f"block {pending[0]} of {name!r} failed on every "
                    f"planned node after {self.policy.attempts} rounds"
                    ) from None
            self.policy.sleep(delay)
            replan = self._call(ClusterMsg.PLAN_PUT, {
                "name": name, "size": len(view),
                "block_size": self.block_size,
                "exclude": sorted(failed_nodes),
            })
            self.stats["replans"] += 1
            for i in pending:
                blocks_plan[i] = replan["blocks"][i]
            write_round(pending)
            pending = [i for i in pending if not achieved[i]]
        blocks = []
        for i, blk in enumerate(blocks_plan):
            if len(achieved[i]) < len(blk["nodes"]):
                self.stats["degraded_blocks"] += 1
            piece = view[blk["offset"]:blk["offset"] + blk["length"]]
            blocks.append({
                "id": blk["id"], "offset": blk["offset"],
                "length": blk["length"], "crc32": _crc(piece),
                "nodes": achieved[i],
            })
        out = self._call(ClusterMsg.COMMIT, {
            "name": name, "size": len(view),
            "block_size": plan["block_size"], "blocks": blocks,
        })
        self.stats["puts"] += 1
        return out

    def put_file(self, src: str, name: Optional[str] = None) -> dict:
        return self.put(name or os.path.basename(src), src=src)

    # -- get ---------------------------------------------------------------

    def get(self, name: str) -> bytes:
        """Reassemble ``name`` from block replicas, verifying per-block
        CRCs and failing over dead/corrupt replicas."""
        with spans.span("cluster.lookup"):
            meta = self._call(ClusterMsg.LOOKUP, {"name": name})
        out = bytearray(meta["size"])
        # first pass: one preferred replica per block, fanned out as
        # pipelined futures grouped by session
        fetches = []  # (block, holders after preferred, future or error)
        for i, blk in enumerate(meta["blocks"]):
            holders = blk["nodes"]
            if not holders:
                raise ClusterError(
                    f"block {i} of {name!r} has no live replica")
            order = holders[i % len(holders):] + holders[:i % len(holders)]
            fetches.append((blk, order[1:], self._start_fetch(order[0], blk)))
        retry = []
        for blk, rest, fut in fetches:
            data = self._finish_fetch(blk, fut)
            if data is None:
                retry.append((blk, rest))
            else:
                out[blk["offset"]:blk["offset"] + blk["length"]] = data
        # failover pass: walk the remaining replicas of each failed block
        for blk, rest in retry:
            data = None
            for node in rest:
                self.stats["replica_failovers"] += 1
                data = self._finish_fetch(blk, self._start_fetch(node, blk))
                if data is not None:
                    break
            if data is None:
                raise ClusterError(
                    f"no intact replica of block {blk['id']} ({name!r})")
            out[blk["offset"]:blk["offset"] + blk["length"]] = data
        self.stats["gets"] += 1
        return bytes(out)

    def get_file(self, name: str, dst: str) -> int:
        data = self.get(name)
        with open(dst, "wb") as f:
            f.write(data)
        return len(data)

    def _start_fetch(self, node: dict, blk: dict):
        addr = (node["host"], node["port"])
        try:
            return self.pool.lease(addr).get_bytes(block_name(blk["id"]))
        except Exception as e:  # noqa: BLE001 - dead node
            self.pool.invalidate(addr)
            return e

    def _finish_fetch(self, blk: dict, fut) -> Optional[bytes]:
        """Resolve one block fetch: None on transport failure or CRC
        mismatch (caller fails over to another replica)."""
        if isinstance(fut, Exception):
            return None
        try:
            data = fut.result().data
        except BusyError:
            self.stats["busy_retries"] += 1
            return None  # failover reads the block from another holder
        except Exception:  # noqa: BLE001
            return None
        if len(data) != blk["length"] or _crc(data) != blk["crc32"]:
            return None  # corrupt replica: as dead as a downed node
        return data

    # -- namespace ---------------------------------------------------------

    def delete(self, name: str) -> None:
        self._call(ClusterMsg.DELETE, {"name": name})

    def list(self, prefix: str = "") -> List[str]:
        return self._call(ClusterMsg.LIST, {"prefix": prefix})["names"]

    def state(self) -> dict:
        return self._call(ClusterMsg.STATE, {})

    # -- lifecycle ---------------------------------------------------------

    def close(self) -> None:
        self._ctrl.close()
        if self._owns_pool:
            self.pool.close()

    def __enter__(self) -> "ClusterClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
