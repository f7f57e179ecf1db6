"""Run one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Prints the result as the last line of standard output and the numbers
the check compared as the last lines of standard error.  Exits non-zero,
with no result, where JAX finds no chip of the peaks table or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root, bench_dir = harness.bench_paths()
    harness.program_on_path(root)
    cell = harness.Cell.load(root, bench_dir, args.workload)
    trace_dir = root / ".chipbench_trace" / args.workload
    try:
        out = harness.run(cell, args.seed, args.seconds,
                          trace=bool(args.trace), t_start=T_START,
                          cache_dir=str(root / ".jax_cache"),
                          trace_dir=trace_dir)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
