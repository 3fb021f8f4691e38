import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# The dry-run is the ONLY entry point that boots 512 placeholder devices.

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) cell against the production mesh and
extract the roofline inputs from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes --out artifacts/dryrun

Per cell this prints compiled.memory_analysis() (fits-in-HBM proof) and
cost_analysis() (FLOPs/bytes), plus the collective-bytes breakdown parsed
from the optimized HLO, and writes one JSON artifact consumed by
benchmarks/roofline.py and EXPERIMENTS.md.
"""
import argparse
import gzip
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import roofline
from repro.roofline import hlo_cost
from repro.configs import ARCH_MODULES, applicable_shapes, get_config
from repro.configs.base import SHAPES, ArchConfig, ShapeSpec
from repro.core.policy import BitPolicy, scheme_policy
from repro.dist import sharding
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.quant import apply as qapply
from repro.train import optimizer as opt_mod
from repro.train.train_step import TrainConfig, make_train_step


# ---------------------------------------------------------------------------
# lowering builders — one per step kind
# ---------------------------------------------------------------------------


def _abstract_params(cfg: ArchConfig):
    api = registry.get_api(cfg)
    return jax.eval_shape(lambda k: api.init(cfg, k), jax.random.key(0))


def build_train(cfg: ArchConfig, shape: ShapeSpec, mesh, *, qat: bool = True,
                microbatches: int = 8, state_dtype: str = "bfloat16",
                fsdp_pod: bool = True, remat: bool = True):
    api = registry.get_api(cfg)
    params = _abstract_params(cfg)
    tcfg = TrainConfig(
        microbatches=microbatches,
        optimizer=opt_mod.OptimizerConfig(state_dtype=state_dtype))
    opt_state = jax.eval_shape(lambda p: opt_mod.init(tcfg.optimizer, p), params)
    batch = specs_mod.train_batch(cfg, shape, abstract=True)
    if qat:
        policy = BitPolicy.uniform(qapply.layer_specs(params, cfg), 8)
        bits = qapply.bits_for_scan(policy, params, cfg)
    else:
        bits = None

    def loss_fn(p, b, bb):
        return api.loss(p, cfg, b, bits=bb)

    step = make_train_step(cfg, tcfg, loss_fn)

    pspec = sharding.params_specs(params, mesh, cfg, fsdp=True, fsdp_pod=fsdp_pod)
    ospec = opt_mod.state_specs(opt_state, pspec)
    bspec = sharding.batch_specs(batch, mesh)
    bitspec = jax.tree.map(lambda _: P(), bits) if bits is not None else None
    metric_spec = {"grad_norm": P(), "lr": P(), "loss": P()}
    in_sh = sharding.to_named((pspec, ospec, bspec) + ((bitspec,) if bits is not None else ()), mesh)
    out_sh = sharding.to_named((pspec, ospec, metric_spec), mesh)
    jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(0, 1))
    args = (params, opt_state, batch) + ((bits,) if bits is not None else ())
    return jitted, args


def _abstract_serve_params(cfg: ArchConfig, policy: BitPolicy):
    api = registry.get_api(cfg)
    params = _abstract_params(cfg)
    return jax.eval_shape(
        lambda p: qapply.quantize_for_serve(api.unstack(p, cfg), policy, cfg), params)


def build_prefill(cfg: ArchConfig, shape: ShapeSpec, mesh, scheme: str = "mixed",
                  *, sp: bool = False):
    api = registry.get_api(cfg)
    policy = scheme_policy(qapply.layer_specs(_abstract_params(cfg), cfg), scheme)
    sparams = _abstract_serve_params(cfg, policy)
    inputs = specs_mod.prefill_inputs(cfg, shape, abstract=True)

    if sp:  # sequence-parallel variant: replicated weights, seq over model
        from repro.models import decoder

        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

        def prefill_step(p, inp):
            return decoder.prefill_sp(p, cfg, inp["tokens"], mesh=mesh)

        pspec = jax.tree.map(lambda _: P(), sparams)
        ispec = {"tokens": P(batch_axes, ("model",))}
        in_sh = sharding.to_named((pspec, ispec), mesh)
        return jax.jit(prefill_step, in_shardings=in_sh), (sparams, inputs)

    def prefill_step(p, inp):
        return api.prefill(p, cfg, **inp)

    pspec = sharding.params_specs(sparams, mesh, cfg, fsdp=False)
    ispec = sharding.batch_specs(inputs, mesh)
    in_sh = sharding.to_named((pspec, ispec), mesh)
    jitted = jax.jit(prefill_step, in_shardings=in_sh)
    return jitted, (sparams, inputs)


def build_decode(cfg: ArchConfig, shape: ShapeSpec, mesh, scheme: str = "mixed"):
    api = registry.get_api(cfg)
    policy = scheme_policy(qapply.layer_specs(_abstract_params(cfg), cfg), scheme)
    sparams = _abstract_serve_params(cfg, policy)
    inputs = specs_mod.decode_inputs(cfg, shape, abstract=True)

    def serve_step(p, state, token, pos):
        return api.decode_step(p, cfg, state, token, pos)

    pspec = sharding.params_specs(sparams, mesh, cfg, fsdp=False)
    sspec = sharding.decode_state_specs(inputs["state"], mesh)
    tspec = sharding.batch_specs(inputs["token"], mesh)
    in_sh = sharding.to_named((pspec, sspec, tspec, P()), mesh)
    jitted = jax.jit(serve_step, in_shardings=in_sh, donate_argnums=(1,))
    return jitted, (sparams, inputs["state"], inputs["token"], inputs["pos"])


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             scheme: str = "mixed", verbose: bool = True, variant: str = "",
             save_hlo_dir: str | None = None, **overrides) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    if shape.kind == "train":
        jitted, args = build_train(cfg, shape, mesh, **overrides)
    elif shape.kind == "prefill":
        jitted, args = build_prefill(cfg, shape, mesh, scheme, **overrides)
    else:
        jitted, args = build_decode(cfg, shape, mesh, scheme)
    with mesh, sharding.activation_axes(mesh):
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    compile_s = time.time() - t0

    hlo_text = compiled.as_text()
    cost = roofline.cost_summary(compiled)   # XLA's (loop-body-once) numbers
    lac = hlo_cost.analyze(hlo_text)         # loop-aware re-pricing
    n_chips = mesh.size
    terms = roofline.roofline_terms(lac.flops, lac.bytes,
                                    lac.coll_wire_bytes, n_chips)
    mf = roofline.model_flops(cfg, shape, train=(shape.kind == "train"))

    record = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "x".join(map(str, mesh.devices.shape)), "n_chips": n_chips,
        "multi_pod": multi_pod, "scheme": scheme if shape.kind != "train" else "qat8",
        "variant": variant,
        "compile_s": round(compile_s, 1),
        "hlo_flops": terms.flops, "hlo_bytes": terms.hbm_bytes,
        "per_device_flops": lac.flops, "per_device_bytes": lac.bytes,
        "xla_flops_once": cost["flops"], "xla_bytes_once": cost["bytes"],
        "peak_bytes_per_device": cost.get("peak_bytes"),
        "arg_bytes_per_device": cost.get("argument_bytes"),
        "collectives": {k: {"bytes": v, "count": lac.coll_count[k]}
                        for k, v in lac.coll_bytes.items()},
        "coll_wire_bytes": lac.coll_wire_bytes,
        "compute_s": terms.compute_s, "memory_s": terms.memory_s,
        "collective_s": terms.collective_s, "dominant": terms.dominant,
        "bound_s": terms.bound_s,
        "model_flops": mf,
        "useful_flops_ratio": mf / terms.flops if terms.flops else 0.0,
        "roofline_fraction": terms.fraction_of_roofline(mf),
    }
    if verbose:
        mem = compiled.memory_analysis()
        print(f"--- {arch} x {shape_name} on {record['mesh']} "
              f"({'multi-pod' if multi_pod else 'single-pod'})"
              f"{' [' + variant + ']' if variant else ''} ---")
        print(f"  compile {compile_s:.1f}s | memory_analysis: "
              f"args={getattr(mem, 'argument_size_in_bytes', 0)/2**30:.2f}GiB "
              f"temp={getattr(mem, 'temp_size_in_bytes', 0)/2**30:.2f}GiB "
              f"out={getattr(mem, 'output_size_in_bytes', 0)/2**30:.2f}GiB per device")
        print(f"  loop-aware cost: flops={lac.flops:.3e} bytes={lac.bytes:.3e} "
              f"(xla-once: {cost['flops']:.3e} / {cost['bytes']:.3e})")
        print(f"  collectives: { {k: f'{v/2**20:.1f}MiB x{int(lac.coll_count[k])}' for k, v in lac.coll_bytes.items()} }")
        print(f"  roofline: compute={terms.compute_s*1e3:.2f}ms memory={terms.memory_s*1e3:.2f}ms "
              f"collective={terms.collective_s*1e3:.2f}ms -> dominant={terms.dominant}")
        print(f"  model_flops/hlo_flops={record['useful_flops_ratio']:.3f} "
              f"roofline_fraction={record['roofline_fraction']:.3f}")
    if save_hlo_dir:
        os.makedirs(save_hlo_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'pod2' if multi_pod else 'pod1'}"
        tag += f"_{variant}" if variant else ""
        with gzip.open(os.path.join(save_hlo_dir, tag + ".hlo.gz"), "wt") as f:
            f.write(hlo_text)
    return record


def cells(arch_filter=None, shape_filter=None):
    for arch in ARCH_MODULES:
        if arch_filter and arch != arch_filter:
            continue
        cfg = get_config(arch)
        for spec in applicable_shapes(cfg):
            if shape_filter and spec.name != shape_filter:
                continue
            yield arch, spec.name


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCH_MODULES), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--all", action="store_true", help="every applicable cell")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--scheme", default="mixed",
                    choices=["mixed", "uniform2", "uniform4", "uniform6", "uniform8"])
    ap.add_argument("--out", default=None, help="directory for JSON artifacts")
    args = ap.parse_args(argv)

    if not args.all and not args.arch:
        ap.error("pass --arch (and optionally --shape), or --all")
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    todo = list(cells(args.arch, args.shape))
    failures = []
    for arch, shape_name in todo:
        for mp in meshes:
            try:
                rec = run_cell(arch, shape_name, multi_pod=mp, scheme=args.scheme,
                               save_hlo_dir=os.path.join(args.out, "hlo")
                               if args.out else None)
            except Exception:
                traceback.print_exc()
                failures.append((arch, shape_name, mp))
                continue
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                tag = f"{arch}_{shape_name}_{'pod2' if mp else 'pod1'}_{args.scheme}.json"
                with open(os.path.join(args.out, tag), "w") as f:
                    json.dump(rec, f, indent=1)
    print(f"\n{len(todo) * len(meshes) - len(failures)}/{len(todo) * len(meshes)} cells OK")
    for f_ in failures:
        print("FAILED:", f_)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
