"""Quantized KV-cache subsystem (DESIGN.md §11): state cost metrics, the
sigma-driven state allocation, artifact versioning, engine integration, and
the padded-prefill state regression for SSM/hybrid families."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kvcache
from repro.configs import gemma_2b, mamba2_2p7b, zamba2_2p7b
from repro.core import packing
from repro.core.controller import SigmaQuantController
from repro.core.policy import BitPolicy, Budget, LayerInfo, PolicyArtifact
from repro.cost import RooflineCostModel, ShiftAddCostModel
from repro.kvcache import (packed_state_bits, resolve_state_bits,
                           state_bits_by_name, state_layer_infos,
                           verify_state_bits)
from repro.kvcache.env import KVQuantEnv
from repro.launch.search import state_controller_config
from repro.models import registry
from repro.quant import apply as qapply
from repro.serve.engine import ServeEngine


@pytest.fixture(scope="module")
def dense_setup():
    cfg = gemma_2b.CONFIG.reduced()
    api = registry.get_api(cfg)
    params = api.init(cfg, jax.random.key(0))
    return cfg, api, api.unstack(params, cfg)


# ---------------------------------------------------------------------------
# state registry + cost metrics
# ---------------------------------------------------------------------------


class TestStateCosts:
    def test_state_layer_names(self, dense_setup):
        cfg, _, _ = dense_setup
        infos = state_layer_infos(cfg, 4, 64)
        names = [l.name for l in infos]
        assert names == sorted(names)
        assert f"layer000.state.k" in names and f"layer001.state.v" in names
        assert all(l.kind == "state" for l in infos)

    def test_hybrid_state_layer_names(self):
        cfg = zamba2_2p7b.CONFIG.reduced()
        names = [l.name for l in state_layer_infos(cfg, 2, 32)]
        assert all(n.startswith("shared_attn.app") for n in names)

    def test_weight_metrics_exclude_state_layers(self):
        w = LayerInfo("w", (64, 32), macs=2048)
        s = LayerInfo("s.state.k", (2, 32, 2, 16), macs=4096, kind="state")
        joint = BitPolicy.uniform((w, s), 4)
        weights_only = BitPolicy.uniform((w,), 4)
        assert joint.model_size_bytes() == weights_only.model_size_bytes()
        assert joint.container_bytes() == weights_only.container_bytes()
        assert joint.bops() == weights_only.bops()
        # 4-bit packs 2 values/byte along hd=16
        assert joint.state_bytes() == 2 * 32 * 2 * 16 // 2

    @pytest.mark.parametrize("model", [ShiftAddCostModel(), RooflineCostModel()])
    def test_cost_models_price_state_bytes(self, model):
        w = LayerInfo("w", (64, 32), macs=2048)
        s = LayerInfo("s.state.k", (2, 32, 2, 16), macs=4096, kind="state")
        policy = BitPolicy.uniform((w, s), 4)
        costs = model.report(policy).as_costs()
        assert costs["state_bytes"] == policy.state_bytes() > 0
        assert costs["size_bytes"] == policy.model_size_bytes()
        # budgets can name the new metric
        b = Budget.of(0.9, state_bytes=costs["state_bytes"] + 1)
        assert b.res_ok(costs)

    def test_state_bytes_monotone_and_6in8(self):
        s = LayerInfo("s.state.k", (2, 32, 2, 16), macs=1, kind="state")
        by_bits = {b: BitPolicy.uniform((s,), b).state_bytes() for b in (2, 4, 6, 8)}
        assert by_bits[2] < by_bits[4] < by_bits[8]
        assert by_bits[6] == by_bits[8]  # 6-in-8 containers (DESIGN.md §2)


# ---------------------------------------------------------------------------
# artifact versioning
# ---------------------------------------------------------------------------


class TestArtifactStatePolicy:
    def _artifact(self, cfg):
        wl = (LayerInfo("w", (8, 8), macs=64),)
        sp = BitPolicy.from_bits(
            state_layer_infos(cfg, 2, 32),
            {l.name: (4 if l.name.endswith(".k") else 8)
             for l in state_layer_infos(cfg, 2, 32)})
        return PolicyArtifact.build(BitPolicy.uniform(wl, 4), backend="shift_add",
                                    state_policy=sp)

    def test_roundtrip_carries_state_policy(self, dense_setup):
        cfg, _, _ = dense_setup
        art = self._artifact(cfg)
        back = PolicyArtifact.from_json(art.to_json())
        assert back.state_policy.bits == art.state_policy.bits
        assert back.state_registry_hash == art.state_registry_hash != ""
        back.verify_state_layers(state_layer_infos(cfg, 2, 32))
        with pytest.raises(ValueError, match="state-registry hash"):
            back.verify_state_layers(state_layer_infos(cfg, 2, 64))

    def test_v1_artifact_still_loads(self):
        wl = (LayerInfo("w", (8, 8), macs=64),)
        doc = json.loads(PolicyArtifact.build(BitPolicy.uniform(wl, 4)).to_json())
        doc["artifact_version"] = 1
        doc.pop("state_policy")
        doc.pop("state_registry_hash")
        back = PolicyArtifact.from_json(json.dumps(doc))
        assert back.state_policy is None

    def test_state_bits_helpers(self, dense_setup):
        cfg, _, _ = dense_setup
        art = self._artifact(cfg)
        by_name = state_bits_by_name(art.state_policy)
        assert by_name["layer000"] == (4, 8)
        assert resolve_state_bits(art, cfg) == [(4, 8)] * cfg.n_layers
        assert resolve_state_bits(6, cfg) == [(6, 6)] * cfg.n_layers
        with pytest.raises(ValueError, match="no quantizable KV state"):
            resolve_state_bits(6, mamba2_2p7b.CONFIG.reduced())


# ---------------------------------------------------------------------------
# sigma-driven allocation: calibration env + controller
# ---------------------------------------------------------------------------


class TestStateSearch:
    @pytest.fixture(scope="class")
    def kv_env(self, dense_setup):
        cfg, _, sp = dense_setup
        calib = np.random.default_rng(0).integers(1, cfg.vocab_size, (4, 16))
        return KVQuantEnv(sp, cfg, calib, slots=4, max_seq=64, qimpl="xla")

    def test_quality_monotone_in_bits(self, kv_env):
        qual = [kv_env.evaluate(BitPolicy.uniform(kv_env.layer_infos(), b))
                for b in (8, 4, 2)]
        assert qual[0] > qual[1] > qual[2]
        assert qual[0] > -0.05  # 8-bit state is near-exact

    def test_statistics_vectors(self, kv_env):
        sig = kv_env.sigmas()
        sens = kv_env.sensitivities(BitPolicy.uniform(kv_env.layer_infos(), 4))
        n = len(kv_env.layer_infos())
        assert sig.shape == sens.shape == (n,) and (sig > 0).all()

    def test_controller_allocates_heterogeneous_state_bits(self, kv_env):
        ref = kv_env.costs(BitPolicy.uniform(kv_env.layer_infos(), 8))
        budget = Budget.of(-0.25, acc_buffer=0.05, buffer=0.08,
                           state_bytes=0.75 * ref["state_bytes"])
        cc = state_controller_config(len(kv_env.layer_infos()))
        result = SigmaQuantController(kv_env, budget, cc).run()
        bits = set(result.policy.bits.values())
        assert len(bits) >= 2, f"expected heterogeneous state bits, got {bits}"
        got = kv_env.costs(result.policy)["state_bytes"]
        # within the budget buffer, and a real cut vs uniform-8
        assert got <= 0.75 * ref["state_bytes"] * 1.08 + 1e-9
        assert got < ref["state_bytes"]


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------


class TestEngineQuantizedState:
    def test_uniform8_state_serves_and_reports_bits(self, dense_setup):
        cfg, _, sp = dense_setup
        eng = ServeEngine(cfg, sp, max_slots=2, max_seq=64, state_bits=8)
        outs = eng.generate([[5, 6, 7, 8], [1, 2, 9, 4, 7, 3]], max_new_tokens=4)
        assert all(len(o) == 4 for o in outs)
        assert eng.state_bits == {f"layer{i:03d}.state.{s}": 8
                                  for i in range(cfg.n_layers) for s in "kv"}

    def test_8bit_state_matches_fp_tokens_on_tiny_model(self, dense_setup):
        cfg, _, sp = dense_setup
        prompts = [[5, 6, 7, 8], [1, 2, 9, 4, 7, 3]]
        fp = ServeEngine(cfg, sp, max_slots=2, max_seq=64).generate(prompts, 4)
        q8 = ServeEngine(cfg, sp, max_slots=2, max_seq=64,
                         state_bits=8).generate(prompts, 4)
        assert fp == q8

    def test_hybrid_quantized_attn_cache(self):
        cfg = zamba2_2p7b.CONFIG.reduced()
        api = registry.get_api(cfg)
        sp = api.unstack(api.init(cfg, jax.random.key(0)), cfg)
        eng = ServeEngine(cfg, sp, max_slots=2, max_seq=64, state_bits=8)
        outs = eng.generate([[3, 1, 4, 1, 5], [2, 7]], max_new_tokens=4)
        assert all(len(o) == 4 for o in outs)
        assert all(n.startswith("shared_attn.app") for n in eng.state_bits)

    def _state_artifact(self, cfg, params, state_bits_map):
        specs = qapply.layer_specs(params, cfg)
        policy = BitPolicy.uniform(specs, 8)
        sp_infos = state_layer_infos(cfg, 2, 64)
        state_policy = BitPolicy.from_bits(
            sp_infos, {l.name: state_bits_map[l.name.rsplit(".", 1)[-1]]
                       for l in sp_infos})
        return PolicyArtifact.build(policy, backend="shift_add",
                                    state_policy=state_policy)

    def test_artifact_state_policy_builds_and_verifies(self, dense_setup):
        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        art = self._state_artifact(cfg, params, {"k": 4, "v": 8})
        qp = qapply.quantize_for_serve(sp, art, cfg)
        eng = ServeEngine(cfg, qp, max_slots=2, max_seq=64, artifact=art)
        assert eng.state_bits == art.state_policy.bits
        outs = eng.generate([[5, 6, 7], [1, 2]], max_new_tokens=3)
        assert all(len(o) == 3 for o in outs)

    def test_mismatched_state_bits_refused(self, dense_setup):
        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        art = self._state_artifact(cfg, params, {"k": 4, "v": 8})
        qp = qapply.quantize_for_serve(sp, art, cfg)
        with pytest.raises(ValueError, match="disagree with the policy artifact"):
            ServeEngine(cfg, qp, max_slots=2, max_seq=64, artifact=art,
                        state_bits=8)  # explicit uniform-8 != searched (4, 8)

    def test_fp_state_with_state_artifact_refused(self, dense_setup):
        """verify_state_bits is bidirectional: a searched state entry left
        fp must refuse to start (mirrors the weight-side check)."""
        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        art = self._state_artifact(cfg, params, {"k": 4, "v": 8})
        qp = qapply.quantize_for_serve(sp, art, cfg)
        state = registry.get_api(cfg).init_decode_state(cfg, 2, 64, jnp.float32)
        with pytest.raises(ValueError, match="not quantized"):
            verify_state_bits(state, art)
        # and a quantized state against a state-less artifact also fails
        bare = PolicyArtifact.build(art.policy, backend="shift_add")
        qstate = registry.get_api(cfg).init_decode_state(
            cfg, 2, 64, jnp.float32, state_bits=[(4, 4)] * cfg.n_layers)
        with pytest.raises(ValueError, match="no state policy"):
            verify_state_bits(qstate, bare)
        assert packed_state_bits(qstate)["layer000.state.k"] == 4

    def test_foreign_state_surface_refused(self, dense_setup):
        """An artifact searched on a different KV surface (head geometry)
        must refuse to deploy even when the bit values happen to line up;
        a different slots/max_seq geometry alone must NOT refuse."""
        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        art = self._state_artifact(cfg, params, {"k": 4, "v": 8})
        qp = qapply.quantize_for_serve(sp, art, cfg)
        # same surface, different serving geometry: accepted
        eng = ServeEngine(cfg, qp, max_slots=3, max_seq=32, artifact=art)
        assert eng.state_bits == art.state_policy.bits
        # different head geometry: the surface hash catches it
        import dataclasses as dc

        other = dc.replace(cfg, n_kv_heads=cfg.n_kv_heads + 1)
        state = registry.get_api(other).init_decode_state(
            other, 2, 64, jnp.float32,
            state_bits=[(4, 8)] * other.n_layers)
        with pytest.raises(ValueError, match="state-surface mismatch"):
            verify_state_bits(state, art,
                              surface=state_layer_infos(other, 2, 64))

class TestEngineKernelConfigs:
    """v5 deploy path: the engine validates + installs a tuned kernel-config
    table before tracing, and refuses tables tuned for a different cache
    geometry (DESIGN.md §15)."""

    def _entry(self, cfg, *, heads=None, family="decode_step"):
        return {"key": {"family": family, "k_bits": 4, "v_bits": 4,
                        "heads": heads or cfg.n_kv_heads,
                        "head_dim": cfg.resolved_head_dim, "block": 16,
                        "impl": "xla"},
                "config": {"place": "dus", "attend": "reunpack"},
                "micros": 1.0, "candidates": 4}

    def _artifact(self, cfg, params, entries):
        policy = BitPolicy.uniform(qapply.layer_specs(params, cfg), 8)
        state_policy = BitPolicy.uniform(state_layer_infos(cfg, 2, 64), 4)
        return PolicyArtifact.build(policy, backend="shift_add",
                                    state_policy=state_policy,
                                    kernel_configs=entries)

    def test_engine_installs_and_replays_configs(self, dense_setup):
        from repro.kernels import autotune

        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        entry = self._entry(cfg)
        art = self._artifact(cfg, params, [entry])
        qp = qapply.quantize_for_serve(sp, art, cfg)
        prompts = [[5, 6, 7], [1, 2]]
        try:
            eng = ServeEngine(cfg, qp, max_slots=2, max_seq=64, artifact=art)
            key = autotune.KernelKey.from_dict(entry["key"])
            assert autotune.active_configs()[key] == entry["config"]
            with_cfg = eng.generate(prompts, max_new_tokens=3)
        finally:
            autotune.set_active_configs(None)
        # every tuned layout is bitwise-equivalent: tokens match an engine
        # running the dispatcher default
        plain = ServeEngine(cfg, qp, max_slots=2, max_seq=64,
                            state_bits=art.state_policy)
        assert with_cfg == plain.generate(prompts, max_new_tokens=3)

    def test_mismatched_geometry_refused(self, dense_setup):
        from repro.checkpoint.store import ArtifactError
        from repro.kernels import autotune

        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        art = self._artifact(cfg, params,
                             [self._entry(cfg, heads=cfg.n_kv_heads + 1)])
        qp = qapply.quantize_for_serve(sp, art, cfg)
        with pytest.raises(ArtifactError, match="tuned for geometry"):
            ServeEngine(cfg, qp, max_slots=2, max_seq=64, artifact=art)
        assert not autotune.active_configs()  # refused table never installs

    def test_configs_without_quantized_state_refused(self, dense_setup):
        from repro.checkpoint.store import ArtifactError

        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        policy = BitPolicy.uniform(qapply.layer_specs(params, cfg), 8)
        art = PolicyArtifact.build(policy, backend="shift_add",
                                   kernel_configs=[self._entry(cfg)])
        qp = qapply.quantize_for_serve(sp, art, cfg)
        with pytest.raises(ArtifactError, match="float decode state"):
            ServeEngine(cfg, qp, max_slots=2, max_seq=64, artifact=art)

    def test_extra_bit_pair_keys_tolerated(self, dense_setup):
        """Keys for bit pairs the deployed policy doesn't use stay valid —
        a policy edit must not invalidate the whole tuned table."""
        from repro.kernels import autotune

        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        extra = self._entry(cfg)
        extra["key"]["k_bits"] = extra["key"]["v_bits"] = 2
        art = self._artifact(cfg, params, [self._entry(cfg), extra])
        qp = qapply.quantize_for_serve(sp, art, cfg)
        try:
            eng = ServeEngine(cfg, qp, max_slots=2, max_seq=64, artifact=art)
            assert len(autotune.active_configs()) == 2
            assert eng.generate([[5, 6]], max_new_tokens=2)
        finally:
            autotune.set_active_configs(None)


class TestEngineQuantizedStateDonation:
    def test_donation_still_holds_with_quantized_state(self, dense_setup):
        cfg, _, sp = dense_setup
        eng = ServeEngine(cfg, sp, max_slots=2, max_seq=64, state_bits=4)
        tokens = jnp.zeros((2, 1), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        lowered = eng._decode.lower(eng.params, eng.state, tokens, pos,
                                    eng._key, jnp.zeros((2,), jnp.float32),
                                    eng.temperature, eng.top_k, eng.top_p)
        txt = lowered.as_text()
        assert "tf.aliasing_output" in txt or "jax.buffer_donor" in txt


class TestJittedInsert:
    @pytest.mark.parametrize("state_bits", [4, 8, None], ids=["q4", "q8", "fp"])
    def test_jitted_insert_matches_eager(self, dense_setup, state_bits):
        """The engine's donated insert program writes what the eager walker
        writes: levels exactly, scales to float32 rounding, and zero levels
        from the valid length on."""
        cfg, _, sp = dense_setup
        eng = ServeEngine(cfg, sp, max_slots=3, max_seq=64,
                          state_bits=state_bits)
        pad, valid, slot = 16, 11, 2
        toks = jnp.asarray(np.random.default_rng(0).integers(1, 500, (1, pad)),
                           jnp.int32)
        lengths = jnp.asarray([valid], jnp.int32)
        st = eng._prefill(eng.params, toks, lengths)
        ids = jnp.asarray([slot], jnp.int32)
        eager = kvcache.insert_state_rows(eng.state, ids, st, lengths)
        jitted = eng._insert(eng.state, ids, st, lengths)
        for e, j in zip(eager, jitted):
            if state_bits is None:
                for side in "kv":
                    np.testing.assert_array_equal(np.asarray(j[side]),
                                                  np.asarray(e[side]))
                continue
            for side, bits in (("k", j.k_bits), ("v", j.v_bits)):
                np.testing.assert_array_equal(
                    np.asarray(getattr(j, f"{side}_packed")),
                    np.asarray(getattr(e, f"{side}_packed")))
                np.testing.assert_allclose(
                    np.asarray(getattr(j, f"{side}_scale")),
                    np.asarray(getattr(e, f"{side}_scale")), rtol=1e-6)
                lev = np.asarray(packing.unpack(getattr(j, f"{side}_packed"),
                                                bits, j.head_dim))
                assert lev[slot, :, :valid].any()
                assert not lev[slot, :, valid:].any()


# ---------------------------------------------------------------------------
# padded-prefill state regression (SSM/hybrid pad masking)
# ---------------------------------------------------------------------------


class TestPaddedPrefillState:
    """The recurrent decode state must not depend on the pad length."""

    @pytest.mark.parametrize("config", [mamba2_2p7b.CONFIG, zamba2_2p7b.CONFIG],
                             ids=["ssm", "hybrid"])
    def test_padded_state_equals_exact_state(self, config):
        cfg = config.reduced()
        api = registry.get_api(cfg)
        sp = api.unstack(api.init(cfg, jax.random.key(0)), cfg)
        prompt = [3, 1, 4, 1, 5]
        padded = jnp.asarray([prompt + [0] * 11])  # pad 5 -> 16
        _, st_pad = api.prefill(sp, cfg, tokens=padded,
                                lengths=jnp.asarray([len(prompt)]))
        _, st_exact = api.prefill(sp, cfg, tokens=jnp.asarray([prompt]))
        mamba_pad = st_pad if cfg.family == "ssm" else st_pad["mamba"]
        mamba_exact = st_exact if cfg.family == "ssm" else st_exact["mamba"]
        for a, b in zip(mamba_pad, mamba_exact):
            np.testing.assert_allclose(np.asarray(a["ssm"]), np.asarray(b["ssm"]),
                                       rtol=1e-4, atol=2e-4)
            np.testing.assert_allclose(np.asarray(a["conv"]), np.asarray(b["conv"]),
                                       rtol=1e-4, atol=2e-4)

    @pytest.mark.parametrize("config", [mamba2_2p7b.CONFIG, zamba2_2p7b.CONFIG],
                             ids=["ssm", "hybrid"])
    def test_engine_generation_pad_invariant(self, config):
        cfg = config.reduced()
        api = registry.get_api(cfg)
        sp = api.unstack(api.init(cfg, jax.random.key(0)), cfg)
        prompts = [[3, 1, 4, 1, 5, 9, 2], [7, 7]]
        out_a = ServeEngine(cfg, sp, max_slots=2, max_seq=64,
                            prefill_pad=4).generate(prompts, 5)
        out_b = ServeEngine(cfg, sp, max_slots=2, max_seq=64,
                            prefill_pad=16).generate(prompts, 5)
        assert out_a == out_b

    def test_unpadded_lengths_is_noop(self):
        """lengths == full length must reproduce the lengths=None path."""
        cfg = mamba2_2p7b.CONFIG.reduced()
        api = registry.get_api(cfg)
        sp = api.unstack(api.init(cfg, jax.random.key(0)), cfg)
        toks = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]])
        _, st_a = api.prefill(sp, cfg, tokens=toks)
        _, st_b = api.prefill(sp, cfg, tokens=toks, lengths=jnp.asarray([8]))
        for a, b in zip(st_a, st_b):
            np.testing.assert_allclose(np.asarray(a["ssm"]), np.asarray(b["ssm"]),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(a["conv"]), np.asarray(b["conv"]),
                                       rtol=1e-5, atol=1e-5)
