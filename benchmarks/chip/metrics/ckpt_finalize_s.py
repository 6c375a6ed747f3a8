"""Time from the last put of a save to its commit: sidecar sweep, atomic
rename and the sweep of old steps (``xdfs.ckpt.finalize``), per save, in s."""
from harness.program_spans import per_event, save_records


def read(run):
    return per_event(save_records(run), {"ckpt.finalize"}, run.facts.get("saves"))
