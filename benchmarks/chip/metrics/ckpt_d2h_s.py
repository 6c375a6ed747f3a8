"""Time the checkpoint's copy thread waits for leaves' copies to the host
(``xdfs.ckpt.d2h``), per save, in s."""
from harness.program_spans import per_event, save_records


def read(run):
    return per_event(save_records(run), {"ckpt.d2h"}, run.facts.get("saves"))
