"""Serving engine: continuous batching == single-request reference, quantized
weights path, per-slot positions, sampling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import gemma_2b, mamba2_2p7b
from repro.core.policy import BitPolicy
from repro.models import registry
from repro.quant import apply as qapply
from repro.serve.engine import Request, ServeEngine
from repro.serve.sampling import sample


@pytest.fixture(scope="module")
def dense_setup():
    cfg = gemma_2b.CONFIG.reduced()
    api = registry.get_api(cfg)
    params = api.init(cfg, jax.random.key(0))
    return cfg, api, api.unstack(params, cfg)


def _ref_generate(cfg, api, sp, prompt, n, max_seq=64):
    logits, caches = api.prefill(sp, cfg, tokens=jnp.asarray([prompt]))
    state = api.init_decode_state(cfg, 1, max_seq, jnp.float32)
    state = jax.tree.map(
        lambda c, new: jax.lax.dynamic_update_slice(c, new.astype(c.dtype), (0,) * c.ndim),
        state, caches)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(n - 1):
        lg, state = api.decode_step(sp, cfg, state, jnp.asarray([[out[-1]]]),
                                    jnp.asarray(pos, jnp.int32))
        out.append(int(jnp.argmax(lg[0, -1])))
        pos += 1
    return out


def test_continuous_batching_matches_reference(dense_setup):
    cfg, api, sp = dense_setup
    prompts = [[5, 6, 7, 8], [1, 2, 9, 4, 7, 3], [9] * 11, [2]]
    refs = [_ref_generate(cfg, api, sp, p, 5) for p in prompts]
    eng = ServeEngine(cfg, sp, max_slots=2, max_seq=64, prefill_pad=8)
    outs = eng.generate(prompts, max_new_tokens=5)
    assert outs == refs


def test_slot_reuse_and_stats(dense_setup):
    cfg, api, sp = dense_setup
    eng = ServeEngine(cfg, sp, max_slots=2, max_seq=64)
    outs = eng.generate([[1, 2]] * 5, max_new_tokens=3)
    assert len(outs) == 5 and all(len(o) == 3 for o in outs)
    assert eng.stats()["completed"] == 5
    # identical prompts under greedy decoding produce identical outputs
    assert all(o == outs[0] for o in outs)


def test_eos_stops_generation(dense_setup):
    cfg, api, sp = dense_setup
    ref = _ref_generate(cfg, api, sp, [5, 6, 7, 8], 8)
    eos = ref[2]
    eng = ServeEngine(cfg, sp, max_slots=1, max_seq=64)
    out = eng.run([Request(uid=0, prompt=[5, 6, 7, 8], max_new_tokens=8, eos_id=eos)])
    assert out[0] == ref[:3]


def test_eos_minus_one_never_early_stops(dense_setup):
    """eos_id=-1 (the Request default) means "never stop early": every
    request must run to its full max_new_tokens even though sampled token
    ids span the whole vocab."""
    cfg, api, sp = dense_setup
    eng = ServeEngine(cfg, sp, max_slots=2, max_seq=64, temperature=1.0, seed=7)
    reqs = [Request(uid=i, prompt=[5, 6, 7, i + 1], max_new_tokens=9,
                    eos_id=-1) for i in range(4)]
    out = eng.run(reqs)
    assert all(len(out[i]) == 9 for i in range(4))
    assert all(t >= 0 for toks in out.values() for t in toks)


def test_eos_inside_accepted_burst_stops_that_step(dense_setup):
    """Speculative regression: when an accepted burst contains the eos
    token, the request stops AT the eos — trailing accepted tokens are
    dropped — and its slot (and paged blocks) frees that same step, not
    after finishing out the burst."""
    cfg, api, sp = dense_setup
    for paged in (False, True):
        kw = dict(max_slots=2, max_seq=64)
        if paged:
            kw.update(state_bits=8, paged=True, pool_blocks=16)
        ref = ServeEngine(cfg, sp, **kw).generate([[5, 6, 7, 8]], 8)[0]
        eos = ref[2]  # mid-stream: with speculate=4 it lands inside a burst
        eng = ServeEngine(cfg, sp, speculate=4, draft_policy=4, **kw)
        out = eng.run([Request(uid=0, prompt=[5, 6, 7, 8], max_new_tokens=8,
                               eos_id=eos)])
        assert out[0] == ref[: ref.index(eos) + 1]
        assert eng.stats()["completed"] == 1
        assert all(s.free for s in eng.slots)
        if paged:  # blocks released the step eos was accepted
            assert eng.pool.allocated == 0 and eng.pool.reserved == 0


def test_quantized_weight_path(dense_setup):
    cfg, api, sp = dense_setup
    specs = qapply.layer_specs(api.init(cfg, jax.random.key(0)), cfg)
    policy = BitPolicy.uniform(specs, 8)
    qp = qapply.quantize_for_serve(sp, policy, cfg)
    eng = ServeEngine(cfg, qp, max_slots=2, max_seq=64)
    outs = eng.generate([[5, 6, 7, 8], [1, 2, 3]], max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)
    # 8-bit weights ~ float path agreement on the first token at least
    ref = _ref_generate(cfg, api, sp, [5, 6, 7, 8], 1)
    assert outs[0][0] == ref[0]


def test_batched_admission_matches_sequential(dense_setup):
    """One padded (n_free, pad) prefill call must produce the same tokens as
    admitting the same requests one at a time (attention masks pad exactly)."""
    cfg, api, sp = dense_setup
    prompts = [[5, 6, 7, 8], [1, 2, 9, 4, 7, 3], [9] * 11, [2], [3, 1, 4, 1, 5]]
    batched = ServeEngine(cfg, sp, max_slots=4, max_seq=64, prefill_pad=8,
                          batch_admission=True)
    sequential = ServeEngine(cfg, sp, max_slots=4, max_seq=64, prefill_pad=8,
                             batch_admission=False)
    out_b = batched.generate(prompts, max_new_tokens=6)
    out_s = sequential.generate(prompts, max_new_tokens=6)
    assert out_b == out_s


def test_quantized_fused_matches_unfused(dense_setup):
    """Pack-time Q/K/V + gate/up fusion is exact: same tokens either way."""
    cfg, api, sp = dense_setup
    specs = qapply.layer_specs(api.init(cfg, jax.random.key(0)), cfg)
    qp = qapply.quantize_for_serve(sp, BitPolicy.uniform(specs, 4), cfg)
    prompts = [[5, 6, 7, 8], [1, 2, 3]]
    fused = ServeEngine(cfg, qp, max_slots=2, max_seq=64, fuse_projections=True)
    plain = ServeEngine(cfg, qp, max_slots=2, max_seq=64, fuse_projections=False)
    assert fused.generate(prompts, 5) == plain.generate(prompts, 5)
    # the fused engine really runs on fused leaves
    assert "wqkv" in fused.params["layers"][0]["attn"]
    assert "w_gu" in fused.params["layers"][0]["mlp"]


def test_temperature_mutation_takes_effect(dense_setup):
    """engine.temperature is live config (static jit arg, retraces on
    change), not a value baked in at __init__."""
    cfg, api, sp = dense_setup
    eng = ServeEngine(cfg, sp, max_slots=1, max_seq=64, seed=3)
    greedy = eng.generate([[5, 6, 7]], max_new_tokens=4)
    eng.temperature = 5.0  # near-uniform sampling over 512 tokens
    hot = eng.generate([[5, 6, 7]], max_new_tokens=4)
    assert hot != greedy  # P(collision) ~ (1/512)^4


def test_decode_step_donates_state(dense_setup):
    """The jitted decode step must donate its state buffers (zero-copy KV
    update — no full-cache copy per generated token)."""
    cfg, api, sp = dense_setup
    eng = ServeEngine(cfg, sp, max_slots=2, max_seq=64)
    tokens = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    lowered = eng._decode.lower(eng.params, eng.state, tokens, pos, eng._key,
                                jnp.zeros((2,), jnp.float32),
                                eng.temperature, eng.top_k, eng.top_p)
    txt = lowered.as_text()
    # donation marks the state params as aliased/donated in the lowered HLO
    assert "tf.aliasing_output" in txt or "jax.buffer_donor" in txt


@pytest.mark.parametrize("kw", [dict(state_bits=4),
                                dict(state_bits=4, paged=True, pool_blocks=32)],
                         ids=["dense", "paged"])
def test_insert_donates_state_and_does_not_retrace(dense_setup, kw):
    """Admission's K/V insertion is one donated jitted program per
    (rows, pad) shape: slot ids and prompt lengths never retrace it."""
    cfg, api, sp = dense_setup
    eng = ServeEngine(cfg, sp, max_slots=3, max_seq=64, prefill_pad=16,
                      batch_admission=False, **kw)
    insert = eng._insert_paged if eng.paged else eng._insert
    # heads of 2, 6 and 11 tokens all pad to 16, one per slot
    outs = eng.generate([[5, 6, 7], [1, 2, 9, 4, 7, 3, 8], [9] * 12],
                        max_new_tokens=3)
    assert all(len(o) == 3 for o in outs)
    assert insert._cache_size() == 1
    lengths = jnp.asarray([5], jnp.int32)
    st = eng._prefill(eng.params, jnp.zeros((1, 16), jnp.int32), lengths)
    where = (jnp.ones((1, 1), jnp.int32) if eng.paged
             else jnp.asarray([1], jnp.int32))
    txt = insert.lower(eng.state, where, st, lengths).as_text()
    assert "tf.aliasing_output" in txt or "jax.buffer_donor" in txt


def test_ssm_engine():
    cfg = mamba2_2p7b.CONFIG.reduced()
    api = registry.get_api(cfg)
    params = api.init(cfg, jax.random.key(0))
    sp = api.unstack(params, cfg)
    eng = ServeEngine(cfg, sp, max_slots=2, max_seq=64)
    outs = eng.generate([[3, 1, 4], [1, 5]], max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)


class TestSampling:
    def test_greedy(self):
        logits = jnp.asarray([[0.1, 2.0, 0.3]])
        assert int(sample(logits)[0]) == 1

    def test_temperature_valid_range(self):
        logits = jax.random.normal(jax.random.key(0), (4, 100))
        toks = sample(logits, jax.random.key(1), temperature=1.0)
        assert toks.shape == (4,) and ((toks >= 0) & (toks < 100)).all()

    def test_top_k_restricts_support(self):
        logits = jnp.asarray([[5.0, 4.0, -10.0, -10.0]])
        for s in range(20):
            t = int(sample(logits, jax.random.key(s), temperature=2.0, top_k=2)[0])
            assert t in (0, 1)

    def test_top_p_restricts_support(self):
        # p(0) ~ 0.52: nucleus 0.5 keeps exactly the argmax
        logits = jnp.asarray([[5.0, 4.9, -10.0, -10.0]])
        for s in range(20):
            t = int(sample(logits, jax.random.key(s), temperature=1.0, top_p=0.5)[0])
            assert t == 0
        # a wider nucleus re-admits the runner-up
        seen = {int(sample(logits, jax.random.key(s), temperature=1.0, top_p=0.95)[0])
                for s in range(40)}
        assert seen == {0, 1}

    def test_top_p_composes_with_top_k(self):
        logits = jnp.asarray([[3.0, 2.9, 2.8, -1.0]])
        for s in range(20):
            t = int(sample(logits, jax.random.key(s), temperature=1.0,
                           top_k=2, top_p=0.99)[0])
            assert t in (0, 1)  # top-k already cut token 2 before top-p

    def test_determinism_under_fixed_keys(self):
        logits = jax.random.normal(jax.random.key(0), (3, 64))
        for kwargs in (dict(), dict(temperature=1.0, top_k=8),
                       dict(temperature=0.7, top_p=0.9),
                       dict(temperature=1.3, top_k=16, top_p=0.8)):
            a = sample(logits, jax.random.key(7), **kwargs)
            b = sample(logits, jax.random.key(7), **kwargs)
            assert jnp.array_equal(a, b)

    def test_top_p_one_is_plain_sampling(self):
        logits = jax.random.normal(jax.random.key(1), (2, 32))
        a = sample(logits, jax.random.key(2), temperature=1.0)
        b = sample(logits, jax.random.key(2), temperature=1.0, top_p=1.0)
        assert jnp.array_equal(a, b)

    def test_top_p_zero_is_maximally_restrictive(self):
        """top_p <= 0 degenerates to greedy, never to 'filter disabled'."""
        logits = jax.random.normal(jax.random.key(3), (4, 64))
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for s in range(10):
            toks = sample(logits, jax.random.key(s), temperature=5.0, top_p=0.0)
            assert jnp.array_equal(toks, greedy)


def test_engine_threads_top_p(dense_setup):
    """top_p rides the decode jit as a static arg, like temperature/top_k."""
    cfg, api, sp = dense_setup
    eng = ServeEngine(cfg, sp, max_slots=1, max_seq=64, seed=3,
                      temperature=5.0, top_p=1e-6)
    # a vanishing nucleus degenerates to greedy even at high temperature
    greedy = ServeEngine(cfg, sp, max_slots=1, max_seq=64).generate([[5, 6, 7]], 4)
    assert eng.generate([[5, 6, 7]], max_new_tokens=4) == greedy


class TestPolicyArtifactServing:
    """search -> artifact -> packed deployment: the engine serves exactly the
    searched heterogeneous bitwidths or refuses to start."""

    def _heterogeneous_artifact(self, cfg, params):
        from repro.core.policy import PolicyArtifact

        specs = qapply.layer_specs(params, cfg)
        rng = np.random.default_rng(1)
        policy = BitPolicy.from_bits(
            specs, {s.name: int(rng.choice([2, 4, 6, 8])) for s in specs})
        return PolicyArtifact.build(policy, backend="shift_add"), policy

    def test_packed_leaf_bits_match_artifact(self, dense_setup):
        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        artifact, policy = self._heterogeneous_artifact(cfg, params)
        assert len(set(policy.bits.values())) >= 2  # genuinely heterogeneous
        qp = qapply.quantize_for_serve(sp, artifact, cfg)
        eng = ServeEngine(cfg, qp, max_slots=2, max_seq=64, artifact=artifact)
        # every searched layer packed at exactly its searched bitwidth
        assert eng.packed_bits == policy.bits
        outs = eng.generate([[5, 6, 7], [1, 2]], max_new_tokens=3)
        assert all(len(o) == 3 for o in outs)

    def test_mismatched_packing_refused(self, dense_setup):
        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        artifact, policy = self._heterogeneous_artifact(cfg, params)
        specs = qapply.layer_specs(params, cfg)
        wrong = BitPolicy.uniform(specs, 8)  # packed != searched
        qp = qapply.quantize_for_serve(sp, wrong, cfg)
        if wrong.bits == policy.bits:  # pragma: no cover - rng made them equal
            pytest.skip("rng produced uniform-8 policy")
        with pytest.raises(ValueError, match="disagree with the policy artifact"):
            ServeEngine(cfg, qp, max_slots=2, max_seq=64, artifact=artifact)

    def test_fused_leaves_expand_in_packed_bits(self, dense_setup):
        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        specs = qapply.layer_specs(params, cfg)
        policy = BitPolicy.uniform(specs, 4)  # uniform -> QKV/gate-up fuse
        qp = qapply.quantize_for_serve(sp, policy, cfg)
        fused = qapply.fuse_projections(qp)
        assert qapply.packed_policy_bits(fused) == policy.bits

    def test_unpacked_float_tree_refused(self, dense_setup):
        cfg, api, sp = dense_setup
        params = api.init(cfg, jax.random.key(0))
        artifact, _ = self._heterogeneous_artifact(cfg, params)
        with pytest.raises(ValueError, match="not packed"):
            ServeEngine(cfg, sp, max_slots=2, max_seq=64, artifact=artifact)
