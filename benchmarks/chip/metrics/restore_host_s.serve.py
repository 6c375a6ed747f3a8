"""Time the restoring thread spends checksumming leaves and handing them to
the device (``xdfs.restore.crc``, ``xdfs.restore.place``), per cold start of the
window, in s."""
from harness.program_spans import HOST, per_event, window_records


def read(run):
    return per_event(window_records(run), HOST, run.facts.get("cold_starts"))
