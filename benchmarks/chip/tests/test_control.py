"""The control, the reference computed in int8 and put in the program's
place, comes out as not correct against the configurations' own limits;
the program itself comes out correct. Training at a tiny size, serving at
the cell's own size (about 80 s on a CPU)."""
import pytest

from harness import core
from tiny import tiny_cell


def _rows(name, seeds):
    cell = tiny_cell(name)
    drv = cell.driver()
    import jax
    return list(drv.calibrate(cell, jax.devices()[:cell.chips], seeds, set(seeds)))


def _fails(row, limits) -> list:
    return [k for k, lim in limits.items() if k in row and not row[k] <= lim]


@pytest.mark.parametrize("name", ["smollm-135m.train-ckpt", "smollm-135m.dp4-ring"])
def test_training_control_fails_and_program_passes(name):
    cell = tiny_cell(name)
    limits = cell.config["limits"]
    rows = _rows(name, [3, 2**31 + 11])
    for row in rows:
        if row["side"] == "program":
            assert not _fails(row, limits), row
        else:
            assert _fails(row, limits), row


def test_serving_control_fails_and_program_passes():
    # at the cell's own size: at reduced depth or vocabulary the int8
    # control's gap is too small to tell apart (it read 0.02 at 4 layers)
    cell = core.find_cell("smollm-135m.serve-cold")
    limits = cell.config["limits"]
    import jax
    rows = list(cell.driver().calibrate(cell, jax.devices()[:1], [202, 2**31 + 13],
                                        {202, 2**31 + 13}))
    for row in rows:
        if row["side"] == "program":
            assert not _fails(row, limits), row
        else:
            assert _fails(row, limits), row
