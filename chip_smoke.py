#!/usr/bin/env python3
"""Bring-up smoke test: serve Yi-6B widths on one TPU chip.

    python chip_smoke.py

Builds Yi-6B at its published widths with the depth cut to 8 of 32 layers
(``configs/yi_6b.ONE_CHIP``) from random weights (seed 0), packs them under
the ``mixed`` policy that ``launch/serve.py --wbits mixed`` uses, and serves
a few requests through ``ServeEngine`` with a 4-bit KV cache and
``qimpl="pallas"``: every kernel compiled for the chip, none interpreted and
none replaced by the XLA reference.  One engine uses the dense cache, a
second the paged pool.  Then one prefill + decode step on the Pallas path is
compared, on logits, with the same step on the XLA reference path.

It prints what it measured, and as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
A failed phase exits non-zero before that line, and so does a run that
finds no TPU: there is no CPU fallback.
"""
from __future__ import annotations

import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import kvcache  # noqa: E402
from repro.configs import yi_6b  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build_serve_model  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.serve.engine import Request, ServeEngine  # noqa: E402

SEED = 0
SLOTS = 4
MAX_SEQ = 512
STATE_BITS = 4
MAX_NEW = 32
PROMPT_LENS = (48, 96, 192, 320)
#: prefill + decode logit check: 2 prompts of this many tokens
CHECK_BATCH, CHECK_LEN = 2, 64
#: Tolerances of the logit check, each on max|path - ref32| / max|ref32|,
#: where ref32 is the XLA reference path with float32 activations at
#: highest matmul precision: the same packed model, computed near exactly.
#:
#: * f32 prefill: the Pallas path with float32 activations (and the XLA ops
#:   between its kernels at highest precision) does the same float32 sums
#:   in another order (K <= 11008 terms a dot); on the CPU it agrees to
#:   ~3e-6.  1e-4 leaves room for that and fails a kernel that computes in
#:   bf16 where f32 was asked: on the chip, prefill attention left at
#:   default precision (bf16 operands) alone put it at 5.7e-3.
#: * f32 decode step: both paths decode from one shared 4-bit cache, and
#:   each requantizes the block its new row lands in.  A level that sits on
#:   a rounding boundary can round either way under the last-bit difference
#:   of the two paths, and one such flip moves the logits by ~1e-3 of their
#:   scale.  1e-2 admits a few flips; a misread lane plane, a dropped block
#:   scale or a wrong mask moves them by their own scale.
#: * bf16 (as served): activations round to bf16 at every layer, which
#:   moves the XLA reference path itself by ~2e-2 (its "floor").  The Pallas
#:   path rounds at the same points and leaves the weights exact, so it may
#:   be at most twice as far from ref32 as the bf16 reference is.
F32_PREFILL_TOL = 1e-4
F32_DECODE_TOL = 1e-2
SERVED_FLOOR_FACTOR = 2.0


class SmokeFailure(RuntimeError):
    """A phase of the smoke run did not do what it must."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def describe(cfg, published) -> str:
    """The configuration built, with every key cut from ``published``
    marked."""
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads",
            "resolved_head_dim", "d_ff", "vocab_size", "rope_theta", "dtype")
    parts = []
    for k in keys:
        v, pub = getattr(cfg, k), getattr(published, k)
        parts.append(f"{k}={v}" + (f" (reduced from {pub})" if v != pub else ""))
    return f"{cfg.name}: " + " ".join(parts)


def requests(cfg, prompt_lens, max_new: int, seed: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(1, cfg.vocab_size, n).tolist(),
                    max_new_tokens=max_new)
            for i, n in enumerate(prompt_lens)]


def serve(cfg, params, reqs, *, paged: bool, qimpl: str, slots: int,
          max_seq: int, seed: int) -> tuple[ServeEngine, dict, float]:
    """One engine over ``reqs``; returns (engine, uid -> tokens, wall s)."""
    eng = ServeEngine(cfg, params, max_slots=slots, max_seq=max_seq,
                      qimpl=qimpl, state_bits=STATE_BITS, paged=paged,
                      seed=seed)
    t0 = time.perf_counter()
    results = eng.run(list(reqs))
    jax.block_until_ready(eng.state)
    return eng, results, time.perf_counter() - t0


def decode_step_probe(cfg, params, *, qimpl: str, slots: int, max_seq: int,
                      reps: int) -> dict:
    """Compile the model's decode step at the engine's geometry ahead of
    time, then time ``reps`` steady calls; each ends in block_until_ready."""
    api = registry.get_api(cfg)
    state = api.init_decode_state(
        cfg, slots, max_seq, jnp.float32,
        state_bits=kvcache.resolve_state_bits(STATE_BITS, cfg))
    tok = jnp.ones((slots, 1), jnp.int32)
    pos = jnp.full((slots,), 8, jnp.int32)
    step = jax.jit(lambda p, s, t, q: api.decode_step(p, cfg, s, t, q,
                                                      qimpl=qimpl))
    t0 = time.perf_counter()
    compiled = step.lower(params, state, tok, pos).compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(params, state, tok, pos))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(params, state, tok, pos))
        times.append(time.perf_counter() - t0)
    return {"compile_s": compile_s, "step_s": statistics.median(times),
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text()}


def _errors(got, ref) -> tuple[float, float]:
    """(max |got - ref|, that over max |ref|)."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    err = float(np.max(np.abs(got - ref)))
    return err, err / float(np.max(np.abs(ref)))


def logit_check(cfg, params, *, qimpl: str, max_seq: int, batch: int,
                length: int, seed: int) -> list[tuple[str, float, float]]:
    """One prefill + one quantized-cache decode step on ``qimpl`` against
    the XLA reference path at highest matmul precision, on logits.

    Every decode step starts from one shared 4-bit cache, built from the
    float32 reference prefill, so the check sees each path's step and not
    how its own prefill rounded into the cache.  Returns ``(name, max abs
    error, max relative error, tolerance)`` rows; the tolerances above
    bound the relative error."""
    api = registry.get_api(cfg)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    bits = kvcache.resolve_state_bits(STATE_BITS, cfg)
    tokens = jnp.asarray(np.random.default_rng(seed + 1).integers(
        1, cfg.vocab_size, (batch, length)), jnp.int32)
    lens = jnp.full((batch,), length, jnp.int32)

    def ref_state(p, toks):
        _, caches = api.prefill(p, cfg32, tokens=toks, qimpl="xla")
        state = api.init_decode_state(cfg32, batch, max_seq, jnp.float32,
                                      state_bits=bits)
        return kvcache.insert_state_rows(state, jnp.arange(batch), caches,
                                         lens)

    def step(p, toks, state, impl, c):
        logits, _ = api.prefill(p, c, tokens=toks, qimpl=impl)
        step_logits, _ = api.decode_step(p, c, state, toks[:, -1:], lens,
                                         qimpl=impl)
        return (logits[:, -1].astype(jnp.float32),
                step_logits[:, -1].astype(jnp.float32))

    step = jax.jit(step, static_argnums=(3, 4))
    with jax.default_matmul_precision("highest"):
        state = jax.jit(ref_state)(params, tokens)
        ref32 = step(params, tokens, state, "xla", cfg32)
        # the XLA ops around the kernels too (prefill attention): at default
        # precision a TPU rounds float32 matmul operands to bf16
        got32 = step(params, tokens, state, qimpl, cfg32)
    outs = [ref32, got32]
    rows = [("f32 prefill", *_errors(got32[0], ref32[0]), F32_PREFILL_TOL),
            ("f32 decode step", *_errors(got32[1], ref32[1]), F32_DECODE_TOL)]
    if cfg.dtype != "float32":
        with jax.default_matmul_precision("highest"):
            ref = step(params, tokens, state, "xla", cfg)
        got = step(params, tokens, state, qimpl, cfg)
        outs += [ref, got]
        both = lambda x: np.concatenate(x, axis=0)
        floor = _errors(both(ref), both(ref32))[1]
        rows.append((f"{cfg.dtype} as served, prefill + decode step (xla "
                     f"{cfg.dtype} floor {floor:.6g})",
                     *_errors(both(got), both(ref32)),
                     SERVED_FLOOR_FACTOR * floor))
    _check(all(np.isfinite(np.asarray(v)).all() for x in outs for v in x),
           "non-finite logits")
    return rows


def run(cfg, *, qimpl: str, slots: int = SLOTS, max_seq: int = MAX_SEQ,
        prompt_lens=PROMPT_LENS, max_new: int = MAX_NEW, reps: int = 10,
        check_batch: int = CHECK_BATCH, check_len: int = CHECK_LEN,
        seed: int = SEED, log=print) -> dict:
    """Every phase of the smoke run on ``cfg``; raises SmokeFailure on the
    first phase that fails and returns what was measured."""
    t0 = time.perf_counter()
    params, policy, _ = build_serve_model(cfg, seed=seed, wbits="mixed")
    jax.block_until_ready(params)
    log(f"build: mixed policy mean_bits={policy.mean_bits():.3f} "
        f"packed {policy.container_bytes()} weight bytes in "
        f"{time.perf_counter() - t0:.1f}s")

    reqs = requests(cfg, prompt_lens, max_new, seed)
    report = {}
    for paged in (False, True):
        name = "paged" if paged else "dense"
        eng, results, wall = serve(cfg, params, reqs, paged=paged,
                                   qimpl=qimpl, slots=slots, max_seq=max_seq,
                                   seed=seed)
        params = eng.params   # fused Q/K/V + gate/up: what the engines serve
        st = eng.stats()
        lens = [len(results.get(r.uid, ())) for r in reqs]
        log(f"{name} engine: {st['completed']}/{len(reqs)} requests, "
            f"tokens per request {lens}, nan_quarantined="
            f"{st['nan_quarantined']}, decode_steps={st['decode_steps']}, "
            f"step_time_median={st['health']['step_time_median_s'] * 1e3:.3f}"
            f"ms, wall={wall:.2f}s (compiles included)")
        _check(st["completed"] == len(reqs) and lens == [max_new] * len(reqs),
               f"{name} engine did not complete every request: {lens}")
        _check(st["nan_quarantined"] == 0,
               f"{name} engine quarantined {st['nan_quarantined']} slots")
        report[name] = {"tokens": results, "stats": st}
        del eng
    same = sum(report["dense"]["tokens"][r.uid] == report["paged"]["tokens"][r.uid]
               for r in reqs)
    log(f"paged tokens equal to dense for {same}/{len(reqs)} requests")

    probe = decode_step_probe(cfg, params, qimpl=qimpl, slots=slots,
                              max_seq=max_seq, reps=reps)
    log(f"decode step ({slots} slots, max_seq {max_seq}): compile "
        f"{probe['compile_s']:.2f}s, steady {probe['step_s'] * 1e3:.3f}ms "
        f"(median of {reps}), tpu_custom_call={probe['tpu_custom_call']}")
    report["decode_step"] = probe

    rows = logit_check(cfg, params, qimpl=qimpl, max_seq=max_seq,
                       batch=check_batch, length=check_len, seed=seed)
    for name, err, rel, tol in rows:
        log(f"logits, {name}: {qimpl} vs xla f32 at highest precision "
            f"max_abs={err:.6g} max_rel={rel:.6g} tolerance={tol:.6g} "
            f"{'ok' if rel <= tol else 'FAIL'}")
    bad = [name for name, _, rel, tol in rows if not rel <= tol]
    _check(not bad, f"logit error above tolerance: {bad}")
    report["logits"] = rows
    return report


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}); not running on it",
              file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device_kind: {dev.device_kind} (x{len(jax.devices())})")
    cfg = yi_6b.ONE_CHIP
    print(f"config {describe(cfg, yi_6b.CONFIG)}")
    print(f"qimpl=pallas state_bits={STATE_BITS} slots={SLOTS} "
          f"max_seq={MAX_SEQ} prompts={list(PROMPT_LENS)} max_new={MAX_NEW}")
    try:
        report = run(cfg, qimpl="pallas")
        _check(report["decode_step"]["tpu_custom_call"],
               "compiled decode step holds no tpu_custom_call: no Pallas "
               "kernel ran")
        peak = dev.memory_stats()["peak_bytes_in_use"]
        print(f"peak bytes in use: {peak} ({peak / 2**30:.3f} GiB)")
        _check(peak < 16 * 10**9, f"peak {peak} bytes is over 16 GB")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
