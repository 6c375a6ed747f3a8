"""Time the disk thread waits on the session for leaves and the manifest to
land (``xdfs.ckpt.send``), per save, in s."""
from harness.program_spans import per_event, save_records


def read(run):
    return per_event(save_records(run), {"ckpt.send"}, run.facts.get("saves"))
