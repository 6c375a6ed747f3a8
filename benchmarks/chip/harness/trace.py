"""The profiler's trace of a run's window, reduced to device busy time,
per-op device time, collective time and idle gaps put down to host spans.

The benchmark wraps the window in a ``bench:window`` annotation and every
call into a layer in ``bench:<span>``; the reduction measures inside the
window only. Device ops are the events of each TPU core plane's ``XLA Ops``
line; a collective's time also counts its transfer on the ``Async XLA Ops``
line. Ops that only hold other ops (``while``, ``conditional``, ``call``)
count towards busy time but not in the per-op list.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from typing import Optional

WINDOW = "bench:window"
SPAN_PREFIX = "bench:"
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv)", re.IGNORECASE)
# ops that only hold other ops: their time is their body's
CONTAINERS = re.compile(r"^(while|conditional|call)\b")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


@dataclasses.dataclass
class Summary:
    window_s: float
    n_devices: int
    busy_s: list            # per device
    op_s: dict              # op name -> device seconds, mean over devices
    collective_s: float     # per device, mean
    collective_exposed_s: float  # per device, mean
    idle_gaps: list         # [(host span, seconds)], longest first
    spans: list             # [(name, start_s, end_s)] host spans in window

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / max(self.n_devices, 1)


def _union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _minus(a, b):
    """Intervals of union ``a`` not covered by union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") or name.startswith("/device:CPU:")


def find_xplane(directory: str) -> Optional[str]:
    found = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    return found[-1] if found else None


def reduce(path: str, top: int = 10) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
        elif _is_device_plane(plane.name):
            ops, coll = [], []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops += [(op_name(e.name), e.start_ns, e.end_ns) for e in line.events]
                elif line.name == ASYNC_LINE:
                    coll += [(op_name(e.name), e.start_ns, e.end_ns) for e in line.events
                             if COLLECTIVE.search(op_name(e.name))]
            if ops:
                devices.append((ops, coll))
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} span in {path}")
    w0, w1 = wins[0]
    spans = [(n[len(SPAN_PREFIX):], s, e) for n, s, e in host
             if n != WINDOW and e > w0 and s < w1]
    busy, op_s = [], defaultdict(float)
    coll, exposed = [], []
    gaps = []
    for di, (ops, async_coll) in enumerate(devices):
        clip = lambda evs: [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                            if e > w0 and s < w1]
        clipped = clip(ops)
        for n, s, e in clipped:
            if not CONTAINERS.search(n):
                op_s[n] += (e - s) / 1e9
        u = _union([(s, e) for _, s, e in clipped])
        busy.append(_length(u) / 1e9)
        cu = _union([(s, e) for n, s, e in clipped + clip(async_coll)
                     if COLLECTIVE.search(n)])
        other = _union([(s, e) for n, s, e in clipped if not COLLECTIVE.search(n)])
        coll.append(_length(cu) / 1e9)
        exposed.append(_length(_minus(cu, other)) / 1e9)
        if di == 0:
            for s, e in _minus([[w0, w1]], u):
                gaps.append((_label(spans, (s + e) / 2), (e - s) / 1e9))
    n = max(len(devices), 1)
    op_mean = {k: v / n for k, v in op_s.items()}
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=(w1 - w0) / 1e9, n_devices=len(devices), busy_s=busy,
        op_s=dict(sorted(op_mean.items(), key=lambda kv: -kv[1])),
        collective_s=sum(coll) / n, collective_exposed_s=sum(exposed) / n,
        idle_gaps=gaps[:top],
        spans=[(nm, (s - w0) / 1e9, (e - w0) / 1e9) for nm, s, e in spans])


def _label(spans, t) -> str:
    """The innermost (shortest) host span that covers time ``t``."""
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "window"


def breakdown(summary: Summary, top: int = 10) -> dict:
    return {"device_ops": [[k, v] for k, v in list(summary.op_s.items())[:top]],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:top]]}
