"""The system under test, as the benchmark reaches it.

Everything the benchmark takes from the program passes through here: the
model description it builds from the configuration file, the packing of
the benchmark's weights under the configuration's policy, the serving
engine with the cell's settings, and the engine's spans and counters.
"""
from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"


def _program():
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    from repro.configs.base import ArchConfig
    from repro.core.policy import scheme_policy
    from repro.obs import trace as obs_trace
    from repro.quant import apply as qapply
    from repro.serve import engine
    return ArchConfig, scheme_policy, obs_trace, qapply, engine


def arch(conf: dict):
    """The program's model description of a dense decoder configuration."""
    ArchConfig = _program()[0]
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
    from . import weights
    _, scheme_policy, _, qapply, _ = _program()
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


def engine(conf: dict, params, settings: dict, seed: int):
    """A serving engine with the cell's settings, greedy, 4-bit KV."""
    eng_mod = _program()[4]
    s = conf["serving"]
    if tuple(s["kv_bits"]) != (4, 4) or s["kv_block"] != 16:
        raise ValueError("the engine's state_bits takes one width; this "
                         "configuration asks for another KV layout")
    return eng_mod.ServeEngine(
        arch(conf), params, max_slots=settings["max_slots"],
        max_seq=settings["max_seq"], prefill_pad=settings["prefill_pad"],
        batch_admission=settings["batch_admission"], qimpl=s["qimpl"],
        state_bits=s["kv_bits"][0], kv_block=s["kv_block"], seed=seed % 2**31)


def request(uid: int, prompt: list[int], max_new: int):
    return _program()[4].Request(uid=uid, prompt=prompt, max_new_tokens=max_new)


def tracer():
    """The program's span tracer (``repro.obs.trace``)."""
    return _program()[2]
