"""A benchmark tree at a size a CPU test run holds: a copy of this
benchmark with one tiny configuration and two tiny mixes added as
files and entries, the way a later change adds a cell, and any
architecture families added the same way."""

from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Dict, Optional, Tuple

from chipbench import harness

TINY_CONF = {"name": "tiny", "hidden_size": 64, "intermediate_size": 128,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "num_hidden_layers": 2, "vocab_size": 32768,
             "attention_multiplier": 0.25, "torch_dtype": "float32",
             "serving": {"dtype": "float32", "block_size": 16, "max_batch": 4,
                         "max_blocks_per_seq": 16, "token_budget": 512,
                         "hbm_blocks": 48}}
TINY_MIXES = {
    "tiny_chat": {
        "order_seed": 11, "clients": 8, "requests_per_client": 40,
        "fill_turns": 1, "open_after_completions": 4,
        "open_when_slots_full": False,
        "system_prompts": {"count": 2, "tokens": 32, "zipf_s": 1.0},
        "turns": {"dist": "geometric", "mean": 2},
        "think_s": {"dist": "exponential", "mean": 0.2},
        "message": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                    "min": 4, "max": 32},
        "document": {"share": 0.25, "tokens": {"dist": "uniform", "min": 16,
                                               "max": 48}},
        "answer": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2,
                   "max": 12},
        "buckets": [64, 128], "max_tokens": 256},
    "tiny_closed": {
        "order_seed": 12, "clients": 8, "requests_per_client": 40,
        "prompt": {"dist": "uniform", "min": 8, "max": 40},
        "answer": {"dist": "uniform", "min": 16, "max": 48},
        "buckets": [32, 48], "max_tokens": 256},
}
# float32 at this size: the program's greedy tokens match the reference
# exactly (every gap 0 on four seeds) and the control, matrix products in
# bfloat16, reads a widest gap of 0.018 to 0.030 and a mean gap of
# 0.00012 to 0.00046 over 245 to 313 served tokens of tiny_closed (a
# vocabulary this wide puts near-ties among the logits)
TINY_LIMITS = {"max_logit_gap": 0.002, "mean_logit_gap": 0.00002,
               "sample_requests": 8}


def tiny_tree(dest: Path, families: Optional[Dict[str, Tuple[str, Dict]]]
              = None) -> Tuple[Path, Path]:
    """(root, bench_dir) of a copy of the benchmark under ``dest`` with
    cells ``tiny.<mix>`` added for each of ``TINY_MIXES``.  ``families``
    maps a family's name to the source of its file and the keys its
    configuration changes in the tiny one; each is added as
    ``families/<name>.py``, a configuration ``tiny_<name>`` that names
    it, and cells ``tiny_<name>.<mix>``."""
    src_root, src_bench = harness.bench_paths()
    root = Path(dest)
    bench = root / "benchmarks" / "chip"
    shutil.copytree(src_bench, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    spec = json.loads((src_root / "BENCHMARK.json").read_text())
    for mix, params in TINY_MIXES.items():
        (bench / "traffic" / f"{mix}.json").write_text(json.dumps(params))

    def add(conf: Dict) -> None:
        name = conf["name"]
        (bench / "configs" / f"{name}.json").write_text(json.dumps(conf))
        spec["configs"].append({"name": name, "source": "tiny test copy",
                                "file": f"benchmarks/chip/configs/{name}.json",
                                "reduced": [], "why": "CPU tests"})
        for mix in TINY_MIXES:
            cell = f"{name}.{mix}"
            spec["workloads"].append({"name": cell, "config": name,
                                      "traffic": mix, "chips": 1,
                                      "why": "CPU tests"})
            (bench / "cells" / f"{cell}.json").write_text(
                json.dumps(TINY_LIMITS))
            for m in spec["end_to_end"] + spec["per_layer"]:
                if "workloads" in m:
                    m["workloads"].append(cell)

    base = json.loads((bench / "configs" / "granite-3-8b.json").read_text())
    tiny = dict(base, **TINY_CONF)
    add(tiny)
    for fam, (source, keys) in (families or {}).items():
        (bench / "families" / f"{fam}.py").write_text(source)
        add(dict(tiny, **keys, name=f"tiny_{fam}", family=fam))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, bench
