"""Policy <-> pytree glue + activation calibration."""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import deepseek_moe_16b, gemma_2b, whisper_tiny
from repro.core.policy import BitPolicy
from repro.models import registry
from repro.quant import apply as qapply
from repro.quant import calibration


@pytest.fixture(scope="module", params=["gemma", "whisper", "moe"])
def setup(request):
    mod = {"gemma": gemma_2b, "whisper": whisper_tiny, "moe": deepseek_moe_16b}[request.param]
    cfg = mod.CONFIG.reduced()
    api = registry.get_api(cfg)
    params = api.init(cfg, jax.random.key(0))
    return cfg, api, params


class TestLayerSpecs:
    def test_every_spec_resolves_to_a_weight(self, setup):
        cfg, api, params = setup
        specs = qapply.layer_specs(params, cfg)
        assert len(specs) > 0
        for s in specs:
            w = qapply.get_weight(params, s.name)
            assert tuple(w.shape) == s.shape, s.name

    def test_scheme_policies(self, setup):
        """``mixed``: embedding and layer 0 at 8 bits, layer i > 0 at
        MIXED_PATTERN[i % 4], unindexed layers at the pattern's first
        entry; ``uniform<b>``: b everywhere."""
        import re

        from repro.core.policy import MIXED_PATTERN, scheme_policy
        cfg, api, params = setup
        specs = qapply.layer_specs(params, cfg)
        mixed = scheme_policy(specs, "mixed")
        for s in specs:
            m = re.search(r"layer(\d+)", s.name)
            i = int(m.group(1)) if m else None
            want = (8 if s.kind == "embedding" or i == 0
                    else MIXED_PATTERN[(i or 0) % len(MIXED_PATTERN)])
            assert mixed.bits[s.name] == want, s.name
        assert set(scheme_policy(specs, "uniform6").bits.values()) == {6}
        with pytest.raises(ValueError, match="scheme"):
            scheme_policy(specs, "random")

    def test_deterministic_order(self, setup):
        cfg, api, params = setup
        a = [s.name for s in qapply.layer_specs(params, cfg)]
        b = [s.name for s in qapply.layer_specs(params, cfg)]
        assert a == b == sorted(a)


class TestBitsForScan:
    def test_bits_mirror_policy(self, setup):
        cfg, api, params = setup
        specs = qapply.layer_specs(params, cfg)
        rng = np.random.default_rng(0)
        policy = BitPolicy.from_bits(
            specs, {s.name: int(rng.choice([2, 4, 6, 8])) for s in specs})
        bits = qapply.bits_for_scan(policy, params, cfg)
        leaves = dict(qapply._walk(bits))
        assert leaves, "no bit leaves generated"
        # the multiset of bit values in the pytree equals the policy's
        flat = np.concatenate([np.atleast_1d(np.asarray(v)) for v in leaves.values()])
        assert sorted(flat.astype(int).tolist()) == sorted(policy.bits.values())

    def test_loss_runs_with_bit_pytree(self, setup):
        cfg, api, params = setup
        specs = qapply.layer_specs(params, cfg)
        policy = BitPolicy.uniform(specs, 4)
        bits = qapply.bits_for_scan(policy, params, cfg)
        from repro.configs.base import ShapeSpec
        from repro.launch import specs as sm

        batch = sm.train_batch(cfg, ShapeSpec("t", "train", 32, 2), abstract=False,
                               key=jax.random.key(1))
        loss = api.loss(params, cfg, batch, bits=bits)
        assert np.isfinite(float(loss))


class TestQuantizeForServe:
    def test_roundtrip_error_shrinks_with_bits(self, setup):
        cfg, api, params = setup
        specs = qapply.layer_specs(params, cfg)
        sp = api.unstack(params, cfg)
        from repro.quant.tensor import QuantizedTensor

        def find(tree):
            if isinstance(tree, QuantizedTensor):
                yield tree
            elif isinstance(tree, dict):
                for v in tree.values():
                    yield from find(v)
            elif isinstance(tree, list):
                for v in tree:
                    yield from find(v)

        def float_leaves(tree):
            if isinstance(tree, dict):
                for v in tree.values():
                    yield from float_leaves(v)
            elif isinstance(tree, list):
                for v in tree:
                    yield from float_leaves(v)
            else:
                yield tree

        for b in (2, 8):
            qp = qapply.quantize_for_serve(sp, BitPolicy.uniform(specs, b), cfg)
            qts = list(find(qp))
            assert qts and all(q.bits == b for q in qts)
            assert len(qts) == len(specs)  # every policy entry quantized
        # name-addressed roundtrip: dequant error shrinks with bits
        from repro.quant.tensor import quantize_tensor

        name = next(s.name for s in specs
                    if s.name.split(".")[-1] in ("wq", "in_proj", "w_up"))
        w = np.asarray(qapply.get_weight(params, name), np.float32)
        errs = {b: float(np.mean((np.asarray(
            quantize_tensor(jnp.asarray(w), b).dequantize(), np.float32) - w) ** 2))
            for b in (2, 8)}
        assert errs[8] < errs[2] / 10

    def test_dequant_matches_original_at_8bit(self, setup):
        cfg, api, params = setup
        specs = qapply.layer_specs(params, cfg)
        sp = api.unstack(params, cfg)
        qp = qapply.quantize_for_serve(sp, BitPolicy.uniform(specs, 8), cfg)
        from repro.quant.tensor import QuantizedTensor

        def first_pair(orig, quant):
            if isinstance(quant, QuantizedTensor):
                return orig, quant
            if isinstance(quant, dict):
                for k in quant:
                    r = first_pair(orig[k], quant[k])
                    if r:
                        return r
            if isinstance(quant, list):
                for o, q in zip(orig, quant):
                    r = first_pair(o, q)
                    if r:
                        return r
            return None

        o, q = first_pair(sp, qp)
        if o.ndim == 2 and q.shape == tuple(o.shape):
            w = np.asarray(o, np.float32)
            wq = np.asarray(q.dequantize(), np.float32)
            rel = np.abs(wq - w).max() / (np.abs(w).max() + 1e-9)
            assert rel < 0.02  # 8-bit symmetric per-channel


class TestCalibration:
    def test_percentile_clips_outliers(self):
        x = jnp.concatenate([jnp.ones((10000,)), jnp.asarray([1e6])])
        r = calibration.observe(x, 99.9)
        assert float(r.hi) < 1e3

    def test_ranges_merge(self):
        a = calibration.observe(jnp.asarray([-1.0, 2.0] * 600))
        b = calibration.observe(jnp.asarray([-3.0, 0.5] * 600))
        m = a.merge(b)
        assert float(m.lo) <= -2.9 and float(m.hi) >= 1.9

    @hypothesis.given(bits=st.sampled_from([2, 4, 8]), seed=st.integers(0, 50))
    @hypothesis.settings(max_examples=10, deadline=None)
    def test_fake_quant_error_bounded_by_step(self, bits, seed):
        x = jax.random.normal(jax.random.key(seed), (512,))
        r = calibration.calibrate([x])
        y = calibration.fake_quant_act(x, r, bits)
        step = (float(r.hi) - float(r.lo)) / (2 ** bits - 1)
        inside = (np.asarray(x) >= float(r.lo)) & (np.asarray(x) <= float(r.hi))
        err = np.abs(np.asarray(y) - np.asarray(x))[inside]
        assert err.max() <= step / 2 + 1e-6

    def test_more_bits_less_error(self):
        x = jax.random.normal(jax.random.key(7), (4096,))
        r = calibration.calibrate([x])
        errs = [float(jnp.mean((calibration.fake_quant_act(x, r, b) - x) ** 2))
                for b in (2, 4, 8)]
        assert errs[0] > errs[1] > errs[2]
