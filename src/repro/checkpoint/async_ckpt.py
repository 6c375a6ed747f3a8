"""Asynchronous checkpointing — the paper's disk thread, verbatim.

``AsyncCheckpointer.save`` snapshots device arrays to host (the only
synchronous part) and hands the write to a background disk thread through a
bounded queue; training continues while blocks drain to disk. ``wait()``
joins all outstanding writes (call before shutdown / before depending on the
checkpoint being on disk).
"""
from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, List, Optional

import jax
import numpy as np

from repro.checkpoint import xdfs_ckpt
from repro.core import spans


class AsyncCheckpointer:
    def __init__(self, directory: str, keep_last: int = 3, depth: int = 2):
        self.directory = directory
        self.keep_last = keep_last
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._futures: List[Future] = []
        self._thread = threading.Thread(target=self._disk_thread, daemon=True)
        self._thread.start()

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
        """Non-blocking: snapshot to host, enqueue for the disk thread."""
        with spans.span("ckpt.snapshot") as sp:
            host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
            sp.nbytes = sum(x.nbytes for x in jax.tree.leaves(host_tree))
        fut: Future = Future()
        self._futures.append(fut)
        self._q.put((host_tree, step, fut, time.perf_counter()))
        return fut

    def wait(self):
        for fut in self._futures:
            fut.result()  # re-raises disk-thread failures
        self._futures.clear()

    def close(self):
        self.wait()
        self._q.put(None)
        self._thread.join(timeout=5)
