"""Host spans where checkpoint and serving bytes cross a boundary.

``span(name, nbytes)`` times a block of code. It writes the block into a
``jax.profiler`` trace as ``xdfs.<name>`` on the host plane, on the same
clock as the device ops, and appends ``(name, t0, t1, nbytes)`` on
``time.perf_counter()`` to a process-wide ring that ``records()`` reads.
The ring keeps the newest ``MAXLEN`` records; ``dropped()`` counts the
older ones it let go.

JAX's compile events land in the same ring as ``jit.trace``,
``jit.lower``, ``jit.compile`` and ``jit.cache_load``, ending when JAX
reports them, so a slow call shows whether it rebuilt a program.

Recording is always on. The transfer layer runs without JAX too: in a
process that has not imported JAX no profiler trace can be running, so a
span only fills the ring there, and the compile events are listened to
from the first span opened after JAX is imported.
"""
from __future__ import annotations

import collections
import sys
import threading
import time
from typing import List, Tuple

MAXLEN = 1 << 16
PREFIX = "xdfs."

# JAX's monitoring events -> the record names they land under
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jit.cache_load",
}

Record = Tuple[str, float, float, int]

_ring: collections.deque = collections.deque(maxlen=MAXLEN)
_lock = threading.Lock()
_dropped = 0
_jax = None


def record(name: str, t0: float, t1: float, nbytes: int = 0) -> None:
    """Append one record whose times were taken elsewhere (ring only)."""
    global _dropped
    with _lock:
        if len(_ring) == _ring.maxlen:
            _dropped += 1
        _ring.append((name, t0, t1, nbytes))


def records() -> List[Record]:
    """A snapshot of the ring, oldest first."""
    with _lock:
        return list(_ring)


def dropped() -> int:
    """How many records the ring has let go since the process started."""
    return _dropped


def _on_jax_event(event: str, duration: float, **_) -> None:
    name = COMPILE_EVENTS.get(event)
    if name is not None:
        t1 = time.perf_counter()
        record(name, t1 - duration, t1)


def _profiler():
    """``jax.profiler`` once this process has imported JAX, else None."""
    global _jax
    if _jax is None:
        jax = sys.modules.get("jax")
        if jax is None or not hasattr(jax, "monitoring"):
            return None
        with _lock:
            if _jax is None:
                jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
                _jax = jax
    return _jax.profiler


class span:
    """``with span(name, nbytes) as sp:`` times the block as
    ``xdfs.<name>``, with the bytes it moves; the block may set
    ``sp.nbytes`` once it knows them."""

    __slots__ = ("name", "nbytes", "_ann", "_t0")

    def __init__(self, name: str, nbytes: int = 0):
        self.name, self.nbytes = name, nbytes

    def __enter__(self) -> "span":
        prof = _profiler()
        self._ann = prof.TraceAnnotation(PREFIX + self.name) if prof else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        record(self.name, self._t0, t1, self.nbytes)


# where JAX is already loaded, listen to its compile events from here on
_profiler()
