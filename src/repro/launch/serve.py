"""Serving driver (deliverable b): quantize with a SigmaQuant policy, run
batched requests through the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --requests 16 --wbits mixed

    # deploy a searched PolicyArtifact (launch/search.py): packs exactly the
    # searched per-layer bitwidths, rejecting a mismatched layer registry
    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --policy policy_artifact.json
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_MODULES, get_config
from repro.core.policy import BitPolicy, PolicyArtifact, scheme_policy
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import trace as obs_trace
from repro.models import registry
from repro.quant import apply as qapply
from repro.serve.engine import Request, ServeEngine


def build_serve_model(cfg, *, seed: int = 0, wbits: str = "float",
                      policy_path: str | None = None):
    """Random weights from ``seed``, in the serve layout, packed under a
    policy -> ``(params, policy, artifact)``.

    ``policy_path`` names a searched ``PolicyArtifact`` (its layer registry
    must match the model's); otherwise ``wbits`` is ``float`` (no packing,
    policy None), a uniform bitwidth, ``mixed`` (``core.policy.scheme_policy``)
    or a ``BitPolicy`` JSON file.  The float trees die on return: at
    published widths only the packed tree fits beside the decode state.
    """
    api = registry.get_api(cfg)
    params = api.init(cfg, jax.random.key(seed))
    specs = qapply.layer_specs(params, cfg)
    sp = api.unstack(params, cfg)
    del params
    artifact = None
    if policy_path is not None:
        artifact = PolicyArtifact.load(policy_path)
        artifact.verify_layers(specs)  # refuse a foreign layer registry
        policy = artifact.policy
    elif wbits == "float":
        return sp, None, None
    elif wbits.endswith(".json"):
        with open(wbits) as f:
            policy = BitPolicy.from_json(f.read())
    else:
        policy = scheme_policy(specs, "mixed" if wbits == "mixed"
                               else f"uniform{wbits}")
    return qapply.quantize_for_serve(sp, policy, cfg), policy, artifact


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCH_MODULES), default="gemma-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--wbits", default="float",
                    help="float | 2/4/6/8 | mixed | path/to/policy.json")
    ap.add_argument("--policy", default=None, metavar="ARTIFACT",
                    help="searched PolicyArtifact JSON (launch/search.py); "
                         "overrides --wbits")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="C",
                    help="chunked-prefill continuous batching (DESIGN.md "
                         "§17): prefill prompts in C-token pieces "
                         "interleaved with decode turns")
    ap.add_argument("--token-budget", type=int, default=None, metavar="N",
                    help="per-step token budget shared by decode slots and "
                         "prefill chunks (default: slots + prefill-chunk); "
                         "requires --prefill-chunk")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a Chrome/Perfetto trace of the whole serve "
                         "run (open at https://ui.perfetto.dev) and print "
                         "the per-phase step decomposition")
    args = ap.parse_args(argv)

    if args.trace:
        # enable before the engine builds, so the trace covers the whole run
        obs_trace.enable()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    sp, policy, artifact = build_serve_model(cfg, seed=args.seed,
                                             wbits=args.wbits,
                                             policy_path=args.policy)
    if artifact is not None:
        budget = ("; ".join(f"{it.metric}<={it.limit:g}" for it in artifact.budget.items)
                  if artifact.budget else "none")
        print(f"policy artifact {args.policy}: backend={artifact.backend} "
              f"budget=[{budget}] mean_bits={policy.mean_bits():.2f} "
              f"size={policy.model_size_mib():.2f} MiB")
        if artifact.state_policy is not None:
            print(f"  quantized KV state: mean_bits="
                  f"{artifact.state_policy.mean_bits():.2f} "
                  f"({len(artifact.state_policy.layers)} entries)")
        if artifact.draft_policy is not None:
            print(f"  self-speculative draft: K={artifact.draft_k} "
                  f"mean_bits={artifact.draft_policy.mean_bits():.2f} "
                  f"(DESIGN.md §13)")
    elif policy is not None:
        print(f"quantized: mean_bits={policy.mean_bits():.2f} "
              f"size={policy.model_size_mib():.2f} MiB "
              f"(fp32 {sum(s.n_params for s in policy.layers) * 4 / 2**20:.2f} MiB)")

    rng = np.random.default_rng(args.seed)
    reqs = [Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size, rng.integers(2, 24)).tolist(),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    eng = ServeEngine(cfg, sp, max_slots=args.slots, max_seq=args.max_seq,
                      temperature=args.temperature, seed=args.seed,
                      artifact=artifact, prefill_chunk=args.prefill_chunk,
                      step_token_budget=args.token_budget)
    t0 = time.perf_counter()
    results = eng.run(reqs)
    dt = time.perf_counter() - t0
    new_tokens = sum(len(v) for v in results.values())
    st = eng.stats()
    print(f"{len(results)} requests, {new_tokens} new tokens in {dt:.2f}s "
          f"({new_tokens / dt:.1f} tok/s); decode_steps={st['decode_steps']} "
          f"slot_efficiency={new_tokens / (st['decode_steps'] * args.slots):.2f} "
          f"step_median={st['health']['step_time_median_s'] * 1e3:.1f}ms "
          f"stragglers={st['health']['straggler_flagged']}")
    for uid in sorted(results)[:4]:
        print(f"  req {uid}: {results[uid][:10]}")
    if args.trace:
        doc = obs_trace.get_tracer().save(args.trace)
        obs_trace.validate_chrome_trace(doc)
        obs_trace.disable()
        rep = eng.trace_report()
        print(f"trace: {len(doc['traceEvents'])} events -> {args.trace} "
              f"(open at https://ui.perfetto.dev)")
        print(f"step phases over {rep['steps']} turns "
              f"(attributed {rep['attributed_fraction'] * 100:.1f}%):")
        for name, ph in rep["phases"].items():
            print(f"  {name:<12} {ph['fraction_of_step'] * 100:5.1f}%  "
                  f"mean={ph['mean_us']:8.1f}µs  p99={ph['p99_us']:8.1f}µs")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
