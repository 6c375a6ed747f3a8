"""Time the server's sinks spend in their vectored writes (``xdfs.io.write``)
while the window's saves land, per save, in s."""
from harness.program_spans import per_event, save_records


def read(run):
    return per_event(save_records(run), {"io.write"}, run.facts.get("saves"))
