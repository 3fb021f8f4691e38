"""``chip_smoke.py`` on the CPU: it refuses to run, and its phases work.

The script serves Yi-6B widths on a TPU chip.  Here it must exit non-zero
and name the platform it found instead of falling back to the CPU; its
phases (build, dense and paged engines, decode-step probe, logit check)
run at the reduced Yi config with the Pallas kernels interpreted.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import yi_6b

REPO = Path(__file__).resolve().parents[1]


def _load_script():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_phases_run_at_reduced_size():
    smoke = _load_script()
    lines = []
    report = smoke.run(yi_6b.CONFIG.reduced(), qimpl="interpret", slots=2,
                       max_seq=64, prompt_lens=(5, 12), max_new=4, reps=1,
                       check_batch=1, check_len=8, log=lines.append)
    for name in ("dense", "paged"):
        assert [len(t) for t in report[name]["tokens"].values()] == [4, 4]
        assert report[name]["stats"]["nan_quarantined"] == 0
    assert report["decode_step"]["step_s"] > 0
    for name, _, rel, tol in report["logits"]:
        assert rel <= tol, (name, rel, tol)
    assert any("tokens per request [4, 4]" in line for line in lines)


def test_a_failed_phase_raises():
    smoke = _load_script()
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke._check(False, "boom")


def test_describe_marks_the_cut_depth():
    smoke = _load_script()
    text = smoke.describe(yi_6b.ONE_CHIP, yi_6b.CONFIG)
    assert "n_layers=8 (reduced from 32)" in text
    for width in ("d_model=4096", "n_heads=32", "n_kv_heads=4",
                  "resolved_head_dim=128", "d_ff=11008", "vocab_size=64000",
                  "dtype=bfloat16"):
        assert width in text
