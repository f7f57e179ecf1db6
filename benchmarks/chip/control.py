"""Readings that set a cell's limits on the logit gaps.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \\
        --seeds <n1> <n2> ...

For each seed, in one process: one run of the cell as the benchmark makes
it, with a window of ``--seconds``; the program's gaps (how far the
served tokens lie below the reference's best) on the run's sample; and
each control's gaps on the same sample (of the tokens that the reference
one precision step below the served type puts first), with whether the
cell's limits judge that control not correct.  Prints one JSON line per
seed and a summary: each limit goes above the program's largest reading
and below the controls' smallest.  Not part of a benchmark run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness, reference  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    root, bench_dir = harness.bench_paths()
    harness.program_on_path(root)
    cell = harness.Cell.load(root, bench_dir, args.workload)
    modes = reference.CONTROL[harness.served_dtype(cell.conf)]
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = harness.run(cell, seed, args.seconds, trace=False,
                          t_start=t0, cache_dir=str(root / ".jax_cache"),
                          keep_weights=True)
        row = {"seed": seed, "program": out.notes["gaps"],
               "program_correct": out.result["correct"]}
        for m in modes:
            g = harness.check(cell, out.weights, out.sample, mode=m)
            row[m] = g
            row[m + "_correct"] = harness.judge(g, 0, cell.limits)[0]
        row.update(sampled_tokens=sum(len(s[1]) for s in out.sample),
                   completed=out.notes["completed"],
                   compiles_in_window=out.notes["compiles_in_window"],
                   wall_s=time.perf_counter() - t0)
        print(json.dumps(row), flush=True)
        rows.append(row)
        del out
    summary = {"workload": args.workload, "seeds": len(rows)}
    for k in reference.GAP_NAMES:
        summary[k] = {"program_max": max(r["program"][k] for r in rows)}
        summary[k].update({m + "_min": min(r[m][k] for r in rows)
                           for m in modes})
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
