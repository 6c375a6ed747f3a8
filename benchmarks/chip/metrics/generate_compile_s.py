"""Time JAX spends building programs inside the cold starts' ``generate``
calls (the union of its ``jit.trace``, ``jit.lower``, ``jit.compile`` and
``jit.cache_load`` records within the benchmark's ``generate`` spans), per cold
start, in s."""
from harness.program_spans import window_records
from harness.trace import _length, _union


def read(run):
    recs, n = window_records(run), run.facts.get("cold_starts")
    gens = [(t0, t1) for name, t0, t1 in run.spans if name == "generate"]
    if recs is None or not n or not gens:
        return None
    inside = [(max(t0, g0), min(t1, g1)) for name, t0, t1, _ in recs
              if name.startswith("jit.") for g0, g1 in gens if t0 < g1 and t1 > g0]
    return _length(_union(inside)) / n
