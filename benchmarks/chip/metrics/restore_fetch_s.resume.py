"""Time the restoring thread waits for leaves' bytes (``xdfs.restore.fetch``),
per cycle of the window, in s."""
from harness.program_spans import FETCH, per_event, window_records


def read(run):
    return per_event(window_records(run), FETCH, run.facts.get("cycles"))
