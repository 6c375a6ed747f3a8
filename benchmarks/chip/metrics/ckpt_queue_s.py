"""Mean time a save waits in the queue of ``AsyncCheckpointer`` before the disk
thread takes it (``xdfs.ckpt.queued``), per save, in s."""
from harness.program_spans import per_event, save_records


def read(run):
    return per_event(save_records(run), {"ckpt.queued"}, run.facts.get("saves"))
