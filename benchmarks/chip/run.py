"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Cells, configurations and metrics are those of ``BENCHMARK.json`` at the
checkout's root; ``harness/core.py`` says where each one's files are. One
process holds the chip(s): it makes weights and inputs from ``--seed`` on
the device, warms up (``setup_s``), measures for ``--seconds``, checks what
the timed path produced against the plain reference, and prints one JSON
object as the last line of standard output. With ``--trace 1`` the window
is traced and the per-layer metrics are printed instead of the end-to-end
ones. Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from harness import core
    from harness.device import NoChip

    jax.config.update("jax_compilation_cache_dir", core.compile_cache_dir())
    # every program goes to the cache, however fast it compiled, so that a
    # cold start in the window loads what set-up compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = core.find_cell(args.workload)
    try:
        line = core.execute(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    core.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
