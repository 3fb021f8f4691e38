"""A SwiGLU dense decoder (Llama, Yi, Phi-3), as the benchmark draws,
packs and counts it.

An architecture module is found by the configuration's ``architecture``
value: ``architectures/<architecture>.py`` in the checkout's
``chipbench/``.  It exports

- ``arch(conf)``: the program's model description;
- ``pack(conf, seed)``: the program's serve tree of the benchmark's
  weights at ``seed``, checked against the bits the file states;
- ``dims(conf)``: what the cost functions and metric readers get as
  ``ctx.dims``.

It draws its weights from the seed with ``weights`` (which takes the layer
index, so a module may draw a different leaf set per layer), counts with
``costs`` and ``peaks``, and reaches the program only through ``system``.
"""
from __future__ import annotations

from .. import costs, system, weights


def arch(conf: dict):
    """The program's model description of a dense decoder configuration."""
    ArchConfig = system.program()[0]
    if conf["architecture"] != "dense_decoder" or conf["hidden_act"] != "silu":
        raise ValueError(f"{conf['name']}: not a SwiGLU dense decoder")
    return ArchConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["serving"]["vocab_rows"], mlp="swiglu",
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["serving"]["dtype"])


def stated_bits(conf: dict, n_layers: int) -> dict[str, int]:
    """Policy name -> bits, as the configuration file states them."""
    wb = conf["serving"]["weight_bits"]
    out = {"embed": wb["embed"], "lm_head": wb["lm_head"]}
    for i in range(n_layers):
        for m in ("attn.wq", "attn.wk", "attn.wv", "attn.wo",
                  "mlp.w_gate", "mlp.w_up", "mlp.w_down"):
            out[f"layer{i:03d}.{m}"] = wb["layers"][i]
    return out


def pack(conf: dict, seed: int):
    """The program's serve tree of the benchmark's weights at ``seed``,
    packed under the configuration's policy (checked against the bits the
    file states) and with its projections fused as the engine serves them.
    One layer at a time: each is drawn in the served dtype, packed, and
    dropped, so the float copy of the whole model never exists."""
    import jax
    _, scheme_policy, _, qapply, _ = system.program()
    cfg = arch(conf)
    policy = scheme_policy(qapply.layer_specs(weights.stacked_shapes(conf),
                                              cfg),
                           conf["serving"]["weight_policy"])
    want = stated_bits(conf, cfg.n_layers)
    if dict(policy.bits) != want:
        raise ValueError(f"{conf['name']}: the program's "
                         f"{conf['serving']['weight_policy']} policy packs "
                         f"other bits than the configuration states")
    tree = qapply.quantize_for_serve(weights.top(conf, seed), policy, cfg)
    tree["layers"] = []
    for i in range(cfg.n_layers):
        # the serve layout's i-th layer, named as the policy names it
        part = {"layers": [{}] * i + [weights.layer(conf, seed, i)]}
        packed = qapply.fuse_projections(
            qapply.quantize_for_serve(part, policy, cfg))["layers"][i]
        tree["layers"].append(jax.block_until_ready(packed))
        del part
    return tree


def dims(conf: dict) -> costs.Dims:
    """The serving shapes the cost functions count."""
    return costs.Dims.from_config(conf)
