"""Time the disk thread spends turning leaves into bytes and checksumming
them (``xdfs.ckpt.serialize``, ``xdfs.ckpt.crc``), per save, in s."""
from harness.program_spans import per_event, save_records


def read(run):
    return per_event(save_records(run), {"ckpt.serialize", "ckpt.crc"},
                     run.facts.get("saves"))
