"""Whole runs of a tiny cell on the CPU, driven through the harness with
its look for a chip skipped, and the real command without a chip.

The tiny cell lives in a temporary checkout, added the way a later change
adds one: a configuration, a traffic mix, a cell file, a metric reader and
their entries in ``BENCHMARK.json``.  The engine runs the program's XLA
path (``qimpl: xla``); what these runs check is the harness, the traffic
load and the comparison that decides ``correct``, not speed.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness

REPO = Path(__file__).resolve().parents[2]
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]

TINY = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 500,
        "head_dim": 32}
#: between what the tiny program reads (widest gap 0.033-0.057, mean gap
#: 0.0006-0.0018 on seeds 5-8) and what its control reads (0.19-0.32 and
#: 0.0062-0.011)
TINY_LIMITS = {"max_logit_gap": 0.12, "mean_logit_gap": 0.0035}
NEW_METRIC = '''"""Requests that produced every token they asked for."""


def read(ctx):
    return sum(r.done for r in ctx.recs)
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    cb = root / "chipbench"
    for sub in ("configs", "traffic", "workloads"):
        (cb / sub).mkdir(parents=True)
    shutil.copytree(REPO / "chipbench" / "metrics", cb / "metrics")
    (cb / "metrics" / "tiny.requests_done.py").write_text(NEW_METRIC)
    base = json.loads((REPO / "chipbench/configs/yi-6b.json").read_text())
    for name, qimpl in (("tiny", "xla"), ("tinyi", "interpret")):
        conf = dict(base, name=name, **TINY)
        conf["serving"] = dict(base["serving"], qimpl=qimpl,
                               vocab_rows=512, weight_bits={
                                   "embed": 8, "lm_head": 8, "layers": [8, 4]})
        (cb / "configs" / f"{name}.json").write_text(json.dumps(conf))
    lengths = {"prompt_tokens": {"dist": "uniform", "min": 8, "max": 60},
               "output_tokens": {"dist": "uniform", "min": 8, "max": 24}}
    mixes = {"tiny-open": {"loop": "open", "arrival": {
                 "process": "poisson", "rate_per_s": 8.0}, **lengths},
             "tiny-closed": {"loop": "closed", "clients": 3, "cycle": 8,
                             **lengths}}
    for name, mix in mixes.items():
        (cb / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["workloads"] = []
    engine = {"max_slots": 4, "max_seq": 96, "prefill_pad": 32,
              "batch_admission": False}
    for cell, conf, mix in (("tiny-open", "tiny", "tiny-open"),
                            ("tiny-closed", "tiny", "tiny-closed"),
                            ("tinyi-open", "tinyi", "tiny-open")):
        (cb / "workloads" / f"{cell}.json").write_text(json.dumps({
            "config": conf, "traffic": mix, "engine": engine,
            "check": {"sample": 8, **TINY_LIMITS},
            "trace_seconds": 1, "why": "test"}))
        bench["workloads"].append({"name": cell, "config": conf,
                                   "traffic": mix, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:      # the tiny open cell reads every metric
            m["workloads"].append("tiny-open")
    bench["end_to_end"].append({"name": "tiny.requests_done", "unit": "requests",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["tiny-closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(checkout, cell, seed=7, seconds=2.0, **kw):
    return harness.run(cell, seed, seconds, False, checkout=checkout,
                       need_chip=False, **kw)


def test_discovery_of_added_files(checkout):
    bench = harness.benchmark(checkout)
    cell = harness.find_cell("tiny-closed", bench, checkout / "chipbench")
    assert cell.conf["name"] == "tiny" and cell.mix["loop"] == "closed"
    assert "tiny.requests_done" in [m["name"] for m in cell.end_to_end]
    other = harness.find_cell("tiny-open", bench, checkout / "chipbench")
    assert "tiny.requests_done" not in [m["name"] for m in other.end_to_end]
    assert "client.send_lag_p95_ms" not in [m["name"] for m in cell.per_layer]
    assert "client.send_lag_p95_ms" in [m["name"] for m in other.per_layer]
    read = harness.reader("tiny.requests_done", checkout / "chipbench")
    assert callable(read)


def test_tiny_open_loop_result_line(checkout):
    r = run(checkout, "tiny-open", seed=2**40 + 3)
    assert list(r)[:5] == CONTRACT_KEYS and list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == 16        # 8/s over 2 s, every one due
    assert set(r["metrics"]) == {"ttft_p95_s", "itl_p95_ms",
                                 "output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["metrics"]["itl_p95_ms"]["unit"] == "ms"
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    json.dumps(r)


def test_tiny_interpret_mode_run(checkout):
    """The Pallas kernels the chip runs, interpreted on the CPU."""
    r = run(checkout, "tinyi-open", seconds=1.0)
    assert list(r)[:5] == CONTRACT_KEYS and r["correct"] is True


def test_tiny_closed_loop_reports_added_metric(checkout):
    r = run(checkout, "tiny-closed")
    assert r["correct"] is True
    assert r["metrics"]["tiny.requests_done"]["value"] == r["attempted"] > 3


def test_control_fails_the_limit(checkout):
    """The program passes both limits; its control (the reference with
    matmul inputs rounded to float8, one precision below bfloat16) read on
    the same sampled requests fails them."""
    r = run(checkout, "tiny-open", seed=11, control=True)
    assert r["correct"] is True
    for stat, key in (("max", "served_logit_gap"),
                      ("mean", "served_logit_gap_mean")):
        assert r["gaps"][stat] == r["checks"][key]["value"]
        assert r["control_gaps"][stat] > r["checks"][key]["limit"]
    assert r["control_correct"] is False


def _sample_plus_one(orig):
    def sample(logits, *a, **k):
        return (orig(logits, *a, **k) + 1) % logits.shape[-1]
    return sample


def _keep_cache(orig):
    def step(q, layer, *a, **k):
        o, _ = orig(q, layer, *a, **k)
        return o, layer
    return step


@pytest.mark.parametrize("fault", ["token_altered", "cache_not_updated"])
def test_broken_timed_path_is_not_correct(checkout, monkeypatch, fault):
    from repro.kernels.quant_kv import ops as kv_ops
    from repro.serve import engine as engine_mod
    if fault == "token_altered":
        monkeypatch.setattr(engine_mod, "sample",
                            _sample_plus_one(engine_mod.sample))
    else:
        monkeypatch.setattr(kv_ops, "quant_kv_decode_step_ref",
                            _keep_cache(kv_ops.quant_kv_decode_step_ref))
    r = run(checkout, "tiny-open", seed=5)
    assert r["correct"] is False
    assert r["checks"]["served_logit_gap"]["value"] > \
        r["checks"]["served_logit_gap"]["limit"]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "yi6b-chat",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_tpu_exits_nonzero():
    p = _command(REPO)
    assert p.returncode == 2 and p.stdout == ""
    assert "needs 1 TPU" in p.stderr


def test_command_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_control_rounds_to_float8():
    """The control's matmul inputs are float8 e4m3 under a per-row scale,
    bit for bit what a cast to that format gives."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference
    x = jax.random.normal(jax.random.key(0), (16, 512)) * jnp.exp(
        2 * jax.random.normal(jax.random.key(1), (16, 512)))
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
    cast = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    assert jnp.array_equal(reference._act(x, True), cast)
    assert jnp.array_equal(reference._act(x, False), x)
