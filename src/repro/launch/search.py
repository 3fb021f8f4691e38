"""Policy-search driver: run the SigmaQuant controller under a hardware
Budget and emit a versioned ``PolicyArtifact`` — the handoff every serving
entry point consumes (launch/serve.py --policy).

    PYTHONPATH=src python -m repro.launch.search --arch gemma-2b --reduced \
        --backend shift_add --limit size_mib=0.5 --out policy.json

    PYTHONPATH=src python -m repro.launch.search --arch gemma-2b --reduced \
        --backend roofline --limit latency_s=3e-6 --limit energy=2e-5 \
        --ckpt /tmp/ckpt --out policy.json

    # joint weight + decode-state budget: the same two-phase controller
    # additionally allocates per-layer K/V cache bitwidths from sigma/KL
    # statistics over calibration decodes (DESIGN.md §11)
    PYTHONPATH=src python -m repro.launch.search --arch gemma-2b --reduced \
        --limit size_mib=0.5 --limit state_bytes=40000 --out policy.json

Any subset of cost metrics may be constrained simultaneously (repeat
``--limit metric=value``); metrics are priced by the chosen CostModel
backend, in that backend's units (DESIGN.md §10).  A ``state_bytes`` limit
runs the state-bitwidth phase after the weight phase and versions the KV
policy in the same artifact.

``--draft`` runs a third phase: the same controller searches a strictly-
cheaper *draft* weight policy maximizing a predicted-acceptance proxy
(one-step argmax agreement vs the deployed packing, smoothed by the logit
divergence) for self-speculative decoding; the v4 artifact records
``draft_policy`` + K and the serve engine auto-enables ``speculate=K``
from it (DESIGN.md §13):

    PYTHONPATH=src python -m repro.launch.search --arch gemma-2b --reduced \
        --limit size_mib=0.5 --draft --speculate-k 3 --out policy.json
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.checkpoint import store as ck
from repro.configs import ARCH_MODULES, get_config
from repro.configs.base import ShapeSpec
from repro.core.controller import ControllerConfig, SigmaQuantController, SigmaQuantResult
from repro.core.policy import COST_METRICS, Budget, PolicyArtifact
from repro.cost import available_cost_models, get_cost_model
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.obs import search as obs_search
from repro.obs import trace as obs_trace
from repro.quant.env import LMQuantEnv


def budget_from_limits(acc_t: float, limits: dict[str, float], *,
                       acc_buffer: float = 0.03, buffer: float = 0.08) -> Budget:
    return Budget.of(acc_t, acc_buffer=acc_buffer, buffer=buffer, **limits)


def search_policy(env: LMQuantEnv, budget: Budget, *,
                  config: ControllerConfig | None = None, log=None,
                  meta: dict | None = None, state_env=None,
                  state_budget: Budget | None = None,
                  state_config: ControllerConfig | None = None,
                  pool: dict | None = None, seed: int | None = None,
                  ) -> tuple[PolicyArtifact, SigmaQuantResult]:
    """Run the two-phase search and package the result as a PolicyArtifact.

    With ``state_env``/``state_budget`` (a ``kvcache.env.KVQuantEnv`` and a
    ``state_bytes`` budget) a second controller pass allocates the decode-
    state bitwidths; the KV policy is versioned in the same artifact.

    ``pool`` requests paged-pool geometry in the artifact (v3): pass
    ``{"block": n}`` and the searched state policy's bitwidths size the
    pool so the whole pool fits the ``state_bytes`` limit — the budget
    bounds *allocated* blocks, so deployment gets exactly the block count
    the budget bought (DESIGN.md §12).
    """
    t0 = time.perf_counter()
    result = SigmaQuantController(env, budget, config, log=log,
                                  phase="weight").run()
    report = dict(env.costs(result.policy))
    meta = dict(meta or {}, success=result.success, abandoned=result.abandoned,
                acc=result.acc, mean_bits=result.policy.mean_bits())
    reports = {"weight": result.search_report}
    limits = {it.metric: it.limit for it in budget.items}
    state_policy = None
    pool_geom = None
    if state_env is not None:
        assert state_budget is not None, "state search needs a state_bytes budget"
        sres = SigmaQuantController(state_env, state_budget,
                                    state_config or config, log=log,
                                    phase="state").run()
        reports["state"] = sres.search_report
        limits.update({it.metric: it.limit for it in state_budget.items})
        state_policy = sres.policy
        report["state_bytes"] = float(state_env.costs(state_policy)["state_bytes"])
        meta.update(state_success=sres.success, state_acc=sres.acc,
                    state_mean_bits=state_policy.mean_bits(),
                    fp_state_bytes=state_env.fp_state_bytes())
        if pool is not None:
            from repro.kvcache import pool_blocks_for_budget, resolve_state_bits

            cfg = state_env.cfg
            block = int(pool["block"])
            limit = next(it.limit for it in state_budget.items
                         if it.metric == "state_bytes")
            pool_geom = {
                "block": block,
                "num_blocks": pool_blocks_for_budget(
                    resolve_state_bits(state_policy, cfg), cfg.n_kv_heads,
                    cfg.resolved_head_dim, block, limit),
            }
    meta["search_wall_s"] = round(time.perf_counter() - t0, 3)
    provenance = obs_search.build_provenance(
        backend=env.cost_model.name, reports=reports, seed=seed, limits=limits,
        config=dataclasses.asdict(config or ControllerConfig()))
    artifact = PolicyArtifact.build(
        result.policy, backend=env.cost_model.name, report=report, budget=budget,
        state_policy=state_policy, pool=pool_geom, provenance=provenance,
        meta=meta)
    return artifact, result


def search_draft_policy(params: dict, cfg, deployed_policy, *, metric: str,
                        calib, cost_model=None, qimpl: str = "auto",
                        draft_frac: float = 0.6, draft_accept: float = 0.6,
                        config: ControllerConfig | None = None, log=None):
    """Search the self-speculation *draft* policy for a deployed policy.

    The controller that allocated the deployed bitwidths runs again over a
    ``spec.env.DraftQuantEnv``: quality is the predicted-acceptance proxy
    (one-step argmax agreement of the draft re-packing vs the deployed
    packing, divergence-smoothed; ``draft_accept`` is the minimum) and the
    budget caps the draft's ``metric`` cost at ``draft_frac`` of the
    deployed policy's — so a successful draft is strictly cheaper under the
    chosen cost metric (DESIGN.md §13).  ``params`` is the train-layout
    float tree.  Returns ``(SigmaQuantResult, DraftQuantEnv,
    deployed_cost)``.
    """
    from repro.core.packing import VALID_BITS
    from repro.spec.env import DraftQuantEnv

    api = registry.get_api(cfg)
    serve_params = api.unstack(params, cfg)
    denv = DraftQuantEnv(params, serve_params, cfg, deployed_policy, calib,
                         cost_model=cost_model, qimpl=qimpl)
    deployed_cost = float(denv.costs(deployed_policy)[metric])
    budget = Budget.of(draft_accept, acc_buffer=0.1, buffer=0.08,
                       **{metric: draft_frac * deployed_cost})
    # the draft's bit ladder sits strictly BELOW the deployed maximum: the
    # controller then *starts* at "deployed minus one level" — the natural
    # draft ansatz — and refines downward with the env's probe ordering;
    # on the size metrics the result is strictly cheaper by construction
    dep_max = max(deployed_policy.bits.values())
    ladder = tuple(b for b in sorted(VALID_BITS) if b < dep_max) \
        or (min(VALID_BITS),)
    cc = config or dataclasses.replace(
        state_controller_config(len(denv.layer_infos())), bit_set=ladder)
    result = SigmaQuantController(denv, budget, cc, log=log,
                                  phase="draft").run()
    return result, denv, deployed_cost


def attach_draft(artifact: PolicyArtifact, draft_policy, draft_k: int, *,
                 slots: int | None = None) -> PolicyArtifact:
    """Return a copy of ``artifact`` carrying a draft policy + K (v4).

    When the artifact also carries paged-pool geometry, the pool grows by
    ``slots * ceil(K / block)`` burst-scratch blocks: a speculative burst
    transiently writes up to K positions past the committed one and the
    engine's admission reservations pre-count that headroom (DESIGN.md
    §13), so a pool sized for the non-speculative demand alone would push
    the same workload into backpressure — or reject a single large request
    outright.  The ``state_bytes`` budget still bounds LIVE tokens; the
    scratch blocks are transient state the deployment must nonetheless
    hold, and the growth is recorded in ``meta``.
    """
    out = dataclasses.replace(artifact, draft_policy=draft_policy,
                              draft_k=int(draft_k),
                              report=dict(artifact.report),
                              meta=dict(artifact.meta))
    if artifact.pool is not None:
        if slots is None:
            raise ValueError("attach_draft on a pooled artifact needs the "
                             "serving slot count (burst-scratch headroom)")
        headroom = slots * -(-int(draft_k) // int(artifact.pool["block"]))
        out.pool = dict(artifact.pool,
                        num_blocks=int(artifact.pool["num_blocks"]) + headroom)
        out.meta["draft_pool_headroom_blocks"] = headroom
    return out


def state_controller_config(n_entries: int) -> ControllerConfig:
    """Controller budgets for the post-training state phase.

    6-bit packs into the same container as 8-bit, so the first shrink wave
    (8 -> 6) cannot reduce ``state_bytes``; patience scales with the entry
    count so the search survives that plateau and reaches the 4/2-bit moves
    that do pay.
    """
    return ControllerConfig(phase2_max_iters=max(16, 4 * n_entries),
                            stagnation_patience=max(8, n_entries),
                            phase1_qat_epochs=0, phase2_qat_epochs=0)


def _parse_limits(pairs: list[str]) -> dict[str, float]:
    limits = {}
    for p in pairs:
        metric, _, value = p.partition("=")
        if metric not in COST_METRICS or not value:
            raise SystemExit(f"--limit wants metric=value with metric in "
                             f"{COST_METRICS}, got {p!r}")
        limits[metric] = float(value)
    return limits


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCH_MODULES), default="gemma-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--backend", choices=available_cost_models(), default="shift_add")
    ap.add_argument("--limit", action="append", default=[],
                    help="metric=value upper bound; repeatable (e.g. size_mib=0.5)")
    ap.add_argument("--loss-slack", type=float, default=0.10,
                    help="quality target: val loss <= float loss + slack")
    ap.add_argument("--pretrain-steps", type=int, default=60)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--decode-batch", type=int, default=1,
                    help="roofline backend: sequences per decode step")
    ap.add_argument("--phase2-iters", type=int, default=10)
    ap.add_argument("--out", default="policy_artifact.json")
    ap.add_argument("--ckpt", default=None,
                    help="also save params + artifact as a checkpoint step here")
    ap.add_argument("--seed", type=int, default=0)
    # decode-state (KV) phase geometry — used when --limit state_bytes=... is given
    ap.add_argument("--slots", type=int, default=4,
                    help="serving slots the state budget prices (engine max_slots)")
    ap.add_argument("--kv-max-seq", type=int, default=64,
                    help="cache length the state budget prices (engine max_seq)")
    ap.add_argument("--kv-calib", type=int, default=4,
                    help="calibration prompts for the state statistics")
    ap.add_argument("--kv-calib-len", type=int, default=16)
    ap.add_argument("--state-tol", type=float, default=0.15,
                    help="tolerated relative logit error of the quantized state")
    ap.add_argument("--paged", action="store_true",
                    help="price/deploy the state as a paged block pool: the "
                         "state_bytes limit bounds ALLOCATED blocks and the "
                         "artifact records pool geometry (DESIGN.md §12)")
    ap.add_argument("--kv-allocated-tokens", type=int, default=None,
                    help="--paged: expected live KV tokens across slots the "
                         "budget prices (default: slots * kv-max-seq, the "
                         "dense worst case)")
    # self-speculation draft phase (DESIGN.md §13) — used with --draft
    ap.add_argument("--draft", action="store_true",
                    help="also search a strictly-cheaper DRAFT weight policy "
                         "maximizing a predicted-acceptance proxy; the "
                         "artifact (v4) records it + K and the engine "
                         "auto-enables speculate=K from it")
    ap.add_argument("--draft-frac", type=float, default=0.6,
                    help="draft budget: fraction of the deployed policy's "
                         "primary-metric cost the draft may spend")
    ap.add_argument("--draft-accept", type=float, default=0.6,
                    help="minimum predicted first-token acceptance (one-step "
                         "argmax agreement of draft vs deployed packing)")
    ap.add_argument("--draft-calib", type=int, default=16,
                    help="calibration prompts for the acceptance proxy")
    ap.add_argument("--speculate-k", type=int, default=3,
                    help="--draft: tokens the draft proposes per verify step "
                         "(recorded in the artifact)")
    # search-side observability (DESIGN.md §18)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a Chrome/Perfetto trace of the whole search "
                         "(controller phases + iterations + env work spans) "
                         "and print the wall-time attribution")
    args = ap.parse_args(argv)
    if not args.limit:
        ap.error("pass at least one --limit metric=value")

    if args.trace:
        obs_trace.enable()
        t_trace0 = time.perf_counter()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = registry.get_api(cfg)
    with obs_search.work_span("model_init", arch=cfg.name):
        params = api.init(cfg, jax.random.key(args.seed))
    shape = ShapeSpec("search", "train", args.seq, args.batch)
    cm_kwargs = {"batch": args.decode_batch} if args.backend == "roofline" else {}
    cost_model = get_cost_model(args.backend, **cm_kwargs)
    env = LMQuantEnv(params, cfg, shape, cost_model=cost_model)

    limits = _parse_limits(args.limit)
    state_limit = limits.pop("state_bytes", None)
    if not limits:
        ap.error("pass at least one weight-side --limit (e.g. size_mib=...) — "
                 "state_bytes only constrains the decode state")

    print(f"pre-training {cfg.name} for {args.pretrain_steps} steps ...")
    env.pretrain(args.pretrain_steps)
    float_loss = env.float_loss()
    budget = budget_from_limits(-(float_loss + args.loss_slack), limits)
    print(f"float val loss {float_loss:.3f}; budget: "
          + ", ".join(f"{it.metric}<={it.limit:g}" for it in budget.items))

    state_env = state_budget = state_cc = pool_req = None
    if state_limit is not None:
        from repro.kvcache.cache import DEFAULT_BLOCK, resolve_block
        from repro.kvcache.env import KVQuantEnv
        from repro.quant import apply as qapply

        # the state phase calibrates on the model AS IT WILL BE SERVED: the
        # weight phase has not run yet, so calibrate on the float weights —
        # weight and state errors are measured independently (the joint
        # artifact still deploys both).
        serve_params = api.unstack(env.params, cfg)
        rng = np.random.default_rng(args.seed)
        calib = rng.integers(1, cfg.vocab_size,
                             (args.kv_calib, args.kv_calib_len))
        allocated = None
        if args.paged:
            if cfg.family not in ("dense", "moe", "vlm"):
                ap.error(f"--paged covers the decoder families; {cfg.family!r} "
                         f"state cannot deploy a block pool (DESIGN.md §12)")
            blk = resolve_block(args.kv_max_seq, DEFAULT_BLOCK)
            allocated = args.kv_allocated_tokens or args.slots * args.kv_max_seq
            allocated = -(-allocated // blk) * blk  # block granularity
            pool_req = {"block": blk}
        state_env = KVQuantEnv(serve_params, cfg, calib, slots=args.slots,
                               max_seq=args.kv_max_seq, cost_model=cost_model,
                               allocated_tokens=allocated)
        state_budget = Budget.of(-args.state_tol, acc_buffer=0.05, buffer=0.08,
                                 state_bytes=state_limit)
        state_cc = state_controller_config(len(state_env.layer_infos()))
        print(f"state budget: state_bytes<={state_limit:g} "
              f"(fp32 cache {state_env.fp_state_bytes():g} B, "
              f"{len(state_env.layer_infos())} KV entries"
              + (f", paged @ {allocated} allocated tokens" if args.paged else "")
              + ")")

    artifact, result = search_policy(
        env, budget, config=ControllerConfig(phase2_max_iters=args.phase2_iters,
                                             phase1_qat_epochs=1, phase2_qat_epochs=1),
        log=print, meta={"arch": cfg.name, "backend": args.backend},
        state_env=state_env, state_budget=state_budget, state_config=state_cc,
        pool=pool_req, seed=args.seed)

    if args.draft:
        metric = budget.primary_metric
        calib = np.random.default_rng(args.seed + 1).integers(
            1, cfg.vocab_size, (args.draft_calib, args.kv_calib_len))
        print(f"draft search: {metric} <= {args.draft_frac:g} x deployed, "
              f"predicted acceptance >= {args.draft_accept:g}")
        dres, denv, dep_cost = search_draft_policy(
            env.params, cfg, artifact.policy, metric=metric, calib=calib,
            cost_model=env.cost_model, draft_frac=args.draft_frac,
            draft_accept=args.draft_accept, log=print)
        draft_cost = float(env.costs(dres.policy)[metric])
        if dres.search_report is not None and artifact.provenance is not None:
            # rebuild the nested mapping instead of mutating it: attach_draft
            # below copies the artifact with dataclasses.replace, which would
            # otherwise share the inner "phases" dict across copies
            artifact.provenance = dict(
                artifact.provenance,
                phases=dict(artifact.provenance.get("phases", {}),
                            draft=obs_search.phase_provenance(dres.search_report)))
        if dres.success and draft_cost < dep_cost:
            # a draft rides the artifact ONLY when strictly cheaper than the
            # deployed policy under the chosen metric — the invariant the
            # engine's speculation win rests on
            artifact = attach_draft(artifact, dres.policy, args.speculate_k,
                                    slots=args.slots)
            artifact.report[f"draft_{metric}"] = draft_cost
            artifact.meta.update(draft_success=True,
                                 draft_agreement=denv.agreement(dres.policy),
                                 draft_divergence=denv.divergence(dres.policy),
                                 draft_mean_bits=dres.policy.mean_bits(),
                                 draft_k=args.speculate_k)
        else:
            artifact.meta.update(draft_success=False)
            print(f"draft search failed ({metric} {draft_cost:g} vs deployed "
                  f"{dep_cost:g}, success={dres.success}); artifact carries "
                  f"no draft policy")

    if args.trace:
        tracer = obs_trace.get_tracer()
        # one root window over the WHOLE run (pretrain + calibration prefills
        # + every controller phase) so the attribution denominator is the
        # full search wall time, not just the controller windows
        tracer.complete("search/main", ts=t_trace0,
                        dur=time.perf_counter() - t_trace0,
                        cat=obs_search.PHASE_CAT, track=obs_search.TRACK)
        srep = obs_search.search_trace_report(tracer.events())
        doc = tracer.save(args.trace, process_name="sigmaquant-search")
        obs_trace.validate_chrome_trace(doc)
        tracer.disable()
        print(f"search trace -> {args.trace}  "
              f"({len(doc['traceEvents'])} events, "
              f"{srep['attributed_fraction']:.1%} of {srep['total_s']:.2f}s "
              f"attributed to env work)")

    artifact.save(args.out)
    print(f"policy artifact -> {args.out}  (success={result.success} "
          f"mean_bits={result.policy.mean_bits():.2f} backend={args.backend})")
    if artifact.state_policy is not None:
        print(f"  state policy: mean_bits={artifact.state_policy.mean_bits():.2f} "
              f"state_bytes={artifact.report['state_bytes']:g} "
              f"(success={artifact.meta.get('state_success')})")
    if artifact.pool is not None:
        print(f"  paged pool: {artifact.pool['num_blocks']} blocks x "
              f"{artifact.pool['block']} positions")
    if artifact.draft_policy is not None:
        print(f"  draft policy: mean_bits={artifact.draft_policy.mean_bits():.2f} "
              f"K={artifact.draft_k} "
              f"predicted_acceptance={artifact.meta['draft_agreement']:.3f}")
    for metric, value in artifact.report.items():
        print(f"  {metric:>16} = {value:g}")

    if args.ckpt:
        ck.save(args.ckpt, args.pretrain_steps, env.params,
                extra={"float_loss": float_loss}, artifact=artifact)
        print(f"checkpoint (+artifact) -> {args.ckpt}")
    return 0 if result.success else 1


if __name__ == "__main__":
    raise SystemExit(main())
