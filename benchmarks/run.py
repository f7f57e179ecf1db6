"""Benchmark driver: one reproduction per paper table/figure plus perf
benchmarks.  Prints ``name,us_per_call,derived`` CSV rows (stdout) and
writes them to experiments/bench_results.csv.

  PYTHONPATH=src python -m benchmarks.run            # default scale
  REPRO_BENCH_FULL=1 ... python -m benchmarks.run    # full trace suite
  python -m benchmarks.run --only fig8               # subset
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

from benchmarks import (faults, paper_figs, perf, scenarios, serving, shard,
                        tuning)
from repro.launch.jax_cache import use_compile_cache

BENCHES = [
    ("fig7", paper_figs.fig7_fidelity),
    ("fig8", paper_figs.fig8_improvements),
    ("fig9", paper_figs.fig9_mrc),
    ("table1", paper_figs.table1_fig10_flows),
    ("fig11", paper_figs.fig11_dirty),
    ("fig12", paper_figs.fig12_skiplimit),
    ("fig13", paper_figs.fig13_window),
    ("fig14", paper_figs.fig14_nonblock),
    ("fig_scenario_matrix", scenarios.fig_scenario_matrix),
    ("fig_sched_slo", serving.fig_sched_slo),
    ("fig_policy_tuning", scenarios.fig_policy_tuning),
    ("fig_shard", shard.fig_shard_fidelity),
    ("fig_shard_jax", shard.fig_shard_jax_fidelity),
    ("fig_sampled_mrc", tuning.fig_sampled_mrc),
    ("fig_tuner", tuning.fig_tuner_converge),
    ("perf_cpu", perf.perf_cpu_overhead),
    ("perf_obs", perf.perf_obs_overhead),
    ("perf_faults", faults.perf_fault_overhead),
    ("perf_journal", faults.perf_journal_append),
    ("perf_failover", faults.perf_failover_rto),
    ("perf_sched_tick", serving.perf_sched_tick),
    ("perf_sweep_grid", tuning.perf_sweep_grid),
    ("perf_shard_scalability", shard.perf_shard_scalability),
    ("perf_engine", perf.perf_jax_engine),
    ("perf_serving", perf.perf_serving),
    ("perf_train", perf.perf_train_step),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench-name prefixes")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else None
    use_compile_cache()

    out_path = Path(__file__).resolve().parents[1] / "experiments" \
        / "bench_results.csv"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    all_rows = ["name,us_per_call,derived"]
    print(all_rows[0])
    for name, fn in BENCHES:
        if only and not any(name.startswith(o) for o in only):
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            rows = [f"{name}/ERROR,0,{type(e).__name__}:{e}"]
        for r in rows:
            print(r)
            all_rows.append(r)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    out_path.write_text("\n".join(all_rows) + "\n")


if __name__ == "__main__":
    main()
