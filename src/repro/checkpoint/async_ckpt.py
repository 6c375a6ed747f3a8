"""Asynchronous checkpointing — the paper's disk thread, verbatim.

``AsyncCheckpointer.save`` takes an on-device copy of every device array
(the only synchronous part), starts each copy's transfer to the host, and
returns; training continues, even when the next step donates the arrays
that were saved. A copy thread waits for each leaf's host bytes in turn and
drops the leaf's device copy as soon as they are there, then hands the host
tree to the disk thread through a bounded queue. At most one snapshot is
held on the device: ``save`` waits for the previous snapshot's transfer, not
its commit. ``wait()`` joins all outstanding writes (call before shutdown /
before depending on the checkpoint being on disk).
"""
from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, List

import jax
import numpy as np

from repro.checkpoint import xdfs_ckpt
from repro.core import spans


def _copy(x):
    """A leaf the checkpoint owns: for a device array, a new device buffer
    (the next step may donate ``x``) with its copy to the host started."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    c = jax.device_put(x, x.sharding, may_alias=False)
    c.copy_to_host_async()
    return c


def _to_host(leaves: list):
    """Replace each device copy in ``leaves`` by its host array, in leaf
    order, and free the copy's device memory once its bytes are on the
    host; on a failure, free what is left."""
    try:
        for i, x in enumerate(leaves):
            if isinstance(x, jax.Array):
                with spans.span("ckpt.d2h", x.nbytes):
                    leaves[i] = np.asarray(x)
                x.delete()
    finally:
        for x in leaves:
            if isinstance(x, jax.Array):
                x.delete()


class AsyncCheckpointer:
    def __init__(self, directory: str, keep_last: int = 3, depth: int = 2):
        self.directory = directory
        self.keep_last = keep_last
        self._copies: queue.Queue = queue.Queue()
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._on_device = threading.Semaphore(1)  # one snapshot on the device
        self._futures: List[Future] = []
        self._threads = [threading.Thread(target=f, daemon=True)
                         for f in (self._copy_thread, self._disk_thread)]
        for t in self._threads:
            t.start()

    def _copy_thread(self):
        while True:
            item = self._copies.get()
            if item is None:
                self._q.put(None)
                return
            leaves, treedef, step, fut = item
            try:
                _to_host(leaves)
            except BaseException as e:
                fut.set_exception(e)
                continue
            finally:
                self._on_device.release()
            self._q.put((jax.tree.unflatten(treedef, leaves), step, fut,
                         time.perf_counter()))

    def _disk_thread(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            tree, step, fut, t_put = item
            spans.record("ckpt.queued", t_put, time.perf_counter())
            try:
                fut.set_result(
                    xdfs_ckpt.save(tree, self.directory, step, self.keep_last)
                )
            except BaseException as e:
                fut.set_exception(e)

    def save(self, tree: Any, step: int) -> Future:
        """Non-blocking once the previous snapshot has left the device: copy
        on the device, start the copies to the host, hand them on."""
        self._on_device.acquire()
        try:
            with spans.span("ckpt.snapshot") as sp:
                leaves, treedef = jax.tree.flatten(tree)
                leaves = [_copy(x) for x in leaves]
                sp.nbytes = sum(x.nbytes for x in leaves)
        except BaseException:
            self._on_device.release()
            raise
        fut: Future = Future()
        self._futures.append(fut)
        self._copies.put((leaves, treedef, step, fut))
        return fut

    def wait(self):
        for fut in self._futures:
            fut.result()  # re-raises disk-thread failures
        self._futures.clear()

    def close(self):
        self.wait()
        self._copies.put(None)
        for t in self._threads:
            t.join(timeout=5)
