"""Whole step's share of the chips' peak, by the host clock: the FLOPs the
window's steps require, as the cell's reference counts them for its
architecture (``train_flops``, recomputation not counted), over the window's
length, the chips and the bf16 peak of ``device_kind``. In %."""
from harness.device import peaks


def read(run):
    f = run.facts
    if not f.get("steps") or not run.window_s:
        return None
    flops = run.cell.reference().train_flops(f["model"], f["batch"], f["seq"]) * f["steps"]
    peak = peaks(run.devices[0].device_kind)["bf16_flops"]
    return 100 * flops / (run.window_s * len(run.devices) * peak)
