"""CPU rehearsal of ``chip_smoke.py``: its phase functions at the
``reduced()`` size, its checks, and its refusal to report ``ok`` off the
chip."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import traces
from repro.launch import serve

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

SERVE_KW = dict(block_size=8, hbm_blocks=96, max_batch=4, n_requests=6,
                prefix_len=24, suffix_lens=(2, 12), max_new=4, check_steps=3)


def test_phase_serve_reduced():
    """The launcher's path on the reduced granite preset: every request
    completes, prefix blocks hit, paged decode matches the prefill."""
    cfg = serve.model_config("granite-3-8b")
    out = chip_smoke.phase_serve(cfg, logits_rtol=chip_smoke.LOGITS_RTOL,
                                 **SERVE_KW)
    assert out["completions"] == SERVE_KW["n_requests"]
    assert out["tokens"] == SERVE_KW["n_requests"] * SERVE_KW["max_new"]
    assert out["pool_hit_ratio"] > 0
    assert out["pool_blocks"] == SERVE_KW["hbm_blocks"]
    # float32 on the CPU: paged and dense agree far inside the bf16 bound
    assert out["logits_max_abs_diff"] < 1e-4 * out["logits_max_abs_ref"]


def test_phase_serve_check_fails():
    """A tolerance no computation meets makes the logits check raise."""
    cfg = serve.model_config("granite-3-8b")
    with pytest.raises(chip_smoke.SmokeFailure, match="logits differ"):
        chip_smoke.phase_serve(cfg, logits_rtol=-1.0, **SERVE_KW)


def test_phase_sweep_matches_reference():
    meta = traces.derive_metadata(traces.SUITE[0].data())[:20_000]
    out = chip_smoke.phase_sweep(
        meta, size_fracs=chip_smoke.SWEEP_SIZE_FRACS,
        window_fracs=chip_smoke.SWEEP_WINDOW_FRACS,
        lanes=chip_smoke.CHECKED_LANES)
    assert len(out["lanes"]) == 6
    assert [c["lane"] for c in out["checked"]] == list(chip_smoke.CHECKED_LANES)
    assert all(c["hits"] == c["reference_hits"] for c in out["checked"])


def test_entry_point_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
