"""Readings that the limits of a cell's checks are set from: the program's
numbers on many seeds, and the control's (the reference in int8 put in the
program's place) on a few, at the cell's own size, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,13 --control-seeds 11,12,13 \
        [--faults half_batch,no_exchange] [--out file.json]

Prints one JSON line per seed and side. Not part of a benchmark run.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))


def _seeds(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="", help="comma-separated, planted in the reference")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax

    from harness import core
    from harness.device import require_chips

    jax.config.update("jax_compilation_cache_dir", core.compile_cache_dir())
    cell = core.find_cell(args.workload)
    devs = require_chips(cell.chips)
    seeds, control = _seeds(args.seeds), _seeds(args.control_seeds)
    rows = list(cell.driver().calibrate(cell, devs, sorted(set(seeds) | set(control)),
                                        set(control),
                                        [f for f in args.faults.split(",") if f]))
    for row in rows:
        print(json.dumps(row), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
