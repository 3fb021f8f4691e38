"""Whole runs of a tiny cell on the CPU, driven through the harness with
its look for a chip skipped, and the real command without a chip.

The tiny cell lives in a temporary checkout, added the way a later change
adds one: a configuration, a traffic mix, a cell file, a metric reader,
an architecture module, a reference module and their entries in
``BENCHMARK.json``.  The engine runs the program's XLA path (``qimpl:
xla``); what these runs check is the harness, the traffic load and the
comparison that decides ``correct``, not speed.
"""
import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import harness, system

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
COUNTER_METRIC = '''"""Prompt tokens the engine prefilled over the window
and its drain, by its own counter; each context read is kept."""
CTXS = []


def read(ctx):
    CTXS.append(ctx)
    return ctx.counters["prefill_tokens"]
'''
NEW_ARCH = '''"""A dense decoder under another name: the dense module does
the work, and every call is recorded."""
from pathlib import Path

from .. import system

DENSE = system.architecture("dense_decoder", Path(__file__).parents[1])
CALLS = []


def _dense(conf):
    return dict(conf, architecture="dense_decoder")


def arch(conf):
    CALLS.append("arch")
    return DENSE.arch(_dense(conf))


def pack(conf, seed):
    CALLS.append("pack")
    return DENSE.pack(_dense(conf), seed)


def dims(conf):
    CALLS.append("dims")
    return DENSE.dims(conf)
'''
NEW_REFERENCE = '''"""The dense reference under another name, every call
recorded."""
from . import reference

CALLS = []


class Reference(reference.Reference):
    def __init__(self, conf, seed, **kw):
        CALLS.append("init")
        super().__init__(conf, seed, **kw)

    def gaps(self, requests, control=False):
        CALLS.append("gaps")
        return super().gaps(requests, control=control)
'''
#: read from the tiny configuration at seed ``SEED`` on the CPU by the code
#: before architectures and references were found by name: a digest of
#: every leaf ``system.pack`` gave, the reference's gaps of ``SERVED``
#: after ``PROMPT`` and its control's, and the dims the costs counted
SEED = 2**40 + 3
PACK_DIGEST = (25, "8c3bae47b4d26f4a34b2629bc0e46a955"
                   "b7fd453dbf41c4a5a1b7c0816a3c4fe")
PROMPT = [(7 * i + 3) % 500 for i in range(21)]
SERVED = [11, 499, 0, 250, 37, 123]
REF_GAPS = [2.89536452293396, 3.96569561958313, 2.8853700160980225,
            1.9965916872024536, 2.3897926807403564, 2.355656623840332]
REF_CONTROL_GAPS = [0.0, 0.0, 0.0, 0.0, 0.04233813285827637, 0.0]
DIMS = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 32, "d_ff": 256, "vocab_rows": 512, "layer_bits": (8, 4),
        "head_bits": 8, "embed_bits": 8, "kv_bits": (4, 4), "kv_block": 16,
        "act_bytes": 2}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    cb = root / "chipbench"
    for sub in ("configs", "traffic", "workloads"):
        (cb / sub).mkdir(parents=True)
    shutil.copytree(REPO / "chipbench" / "metrics", cb / "metrics")
    (cb / "metrics" / "tiny.requests_done.py").write_text(NEW_METRIC)
    (cb / "metrics" / "tiny.prefill_tokens.py").write_text(COUNTER_METRIC)
    shutil.copytree(REPO / "chipbench" / "architectures", cb / "architectures",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (cb / "architectures" / "tiny_arch.py").write_text(NEW_ARCH)
    shutil.copy(REPO / "chipbench" / "reference.py", cb)
    (cb / "tiny_ref.py").write_text(NEW_REFERENCE)
    base = json.loads((REPO / "chipbench/configs/yi-6b.json").read_text())
    for name, qimpl, named in (
            ("tiny", "xla", {}), ("tinyi", "interpret", {}),
            ("tinyx", "xla", {"architecture": "tiny_arch",
                              "reference": "tiny_ref"}),
            ("tiny-noarch", "xla", {"architecture": "no_such_arch"}),
            ("tiny-noref", "xla", {"reference": "no_such_ref"})):
        conf = dict(base, name=name, **TINY, **named)
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
                            ("tinyi-open", "tinyi", "tiny-open"),
                            ("tinyx-open", "tinyx", "tiny-open"),
                            ("tiny-counted", "tiny", "tiny-open"),
                            ("tiny-noarch-open", "tiny-noarch", "tiny-open"),
                            ("tiny-noref-open", "tiny-noref", "tiny-open")):
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
    bench["end_to_end"].append({"name": "tiny.prefill_tokens",
                                "unit": "tokens", "better": "higher",
                                "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["tiny-counted"]})
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


def test_dense_pack_unchanged(checkout):
    conf = harness.load("configs", "tiny", checkout / "chipbench")
    tree = system.pack(conf, SEED, checkout / "chipbench")
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in leaves:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert (len(leaves), h.hexdigest()) == PACK_DIGEST


def test_dense_reference_unchanged(checkout):
    conf = harness.load("configs", "tiny", checkout / "chipbench")
    mod = harness.reference(conf, checkout / "chipbench")
    ref = mod.Reference(conf, SEED, max_seq=96, max_out=24)
    (gaps, control), = ref.gaps([(PROMPT, SERVED)], control=True)
    assert gaps.tolist() == REF_GAPS and control.tolist() == REF_CONTROL_GAPS


def test_dense_dims_unchanged(checkout):
    conf = harness.load("configs", "tiny", checkout / "chipbench")
    dims = system.dims(conf, checkout / "chipbench")
    assert dataclasses.asdict(dims) == DIMS


def test_new_architecture_and_reference_by_files_alone(checkout):
    """A configuration that names an architecture module and a reference
    module found only in the checkout runs end to end through both."""
    cb = checkout / "chipbench"
    r = run(checkout, "tinyx-open", seconds=1.0)
    assert r["correct"] is True and r["failed"] == 0
    assert r["checks"]["served_logit_gap_mean"]["value"] is not None
    assert set(system.architecture("tiny_arch", cb).CALLS) == {
        "arch", "pack", "dims"}
    assert harness.reference({"reference": "tiny_ref"}, cb).CALLS == [
        "init", "gaps"]


def test_readers_see_every_engine_counter(checkout):
    """A reader added as a file reads any counter of the engine's registry,
    as its growth between the snapshots after warm-up and after the drain."""
    path = checkout / "chipbench" / "metrics" / "tiny.prefill_tokens.py"
    r = run(checkout, "tiny-counted", seed=2**35 + 9)
    assert r["failed"] == 0
    (ctx,) = system.module(path, "counted").CTXS
    recs = ctx.gen.recs.values()
    # every request of the window was admitted between the snapshots, its
    # prompt but the last token prefilled (the decode step replays that)
    heads = sum(len(rec.req.prompt) - 1 for rec in recs)
    assert r["metrics"]["tiny.prefill_tokens"]["value"] == heads > 0
    assert ctx.counters["completed"] == len(recs) == r["attempted"]
    # decode_steps as before: one decode step a turn that committed tokens
    assert ctx.counters["decode_steps"] == len(
        {t for rec in recs for t in rec.turns})
    assert {"loop_turns", "preemptions", "wall_s"} <= set(ctx.counters)


@pytest.mark.parametrize("cell, missing", [
    ("tiny-noarch-open", "architectures/no_such_arch.py"),
    ("tiny-noref-open", "no_such_ref.py")])
def test_name_with_no_file_fails_before_set_up(checkout, monkeypatch, cell,
                                               missing):
    from chipbench import weights

    def drawn(*_a, **_k):
        raise AssertionError("weights drawn")
    monkeypatch.setattr(weights, "top", drawn)
    monkeypatch.setattr(weights, "layer", drawn)
    with pytest.raises(FileNotFoundError) as e:
        run(checkout, cell)
    assert str(checkout / "chipbench" / missing) in str(e.value)


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
