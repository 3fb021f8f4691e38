"""PolicyArtifact: JSON round-trip, registry-hash rejection, versioning,
checkpoint persistence, and packed-serve consumption."""
import json

import numpy as np
import pytest

from repro.checkpoint import store as ck
from repro.core.policy import (ARTIFACT_VERSION, BitPolicy, Budget, BudgetItem,
                               LayerInfo, PolicyArtifact, Targets,
                               layer_registry_hash)
from repro.cost import ShiftAddCostModel


def layers():
    return (LayerInfo("blk0.w", (64, 32), macs=2048, kind="dense"),
            LayerInfo("blk1.w", (32, 32), macs=1024, kind="dense"),
            LayerInfo("embed", (256, 64), macs=64, kind="embedding"))


def make_artifact():
    policy = BitPolicy.from_bits(layers(), {"blk0.w": 4, "blk1.w": 2, "embed": 8})
    budget = Budget(acc_t=0.9,
                    items=(BudgetItem("size_mib", 0.5, 0.08),
                           BudgetItem("latency_s", 2.0, 0.05, strict=False)))
    report = ShiftAddCostModel().report(policy).as_costs()
    return PolicyArtifact.build(policy, backend="shift_add", report=report,
                                budget=budget, meta={"arch": "toy"})


class TestRoundTrip:
    def test_full_roundtrip(self):
        art = make_artifact()
        back = PolicyArtifact.from_json(art.to_json())
        assert back.policy.bits == art.policy.bits
        assert back.policy.layers == art.policy.layers
        assert back.policy.act_bits == art.policy.act_bits
        assert back.budget == art.budget          # items, buffers, strict flags
        assert back.report == art.report
        assert back.backend == "shift_add"
        assert back.meta["arch"] == "toy"
        assert back.registry_hash == art.registry_hash

    def test_save_load_file(self, tmp_path):
        art = make_artifact()
        path = art.save(str(tmp_path / "pol.json"))
        assert PolicyArtifact.load(path).policy.bits == art.policy.bits

    def test_budgetless_artifact(self):
        art = PolicyArtifact.build(BitPolicy.uniform(layers(), 4))
        assert PolicyArtifact.from_json(art.to_json()).budget is None


class TestDraftPolicyV4:
    def test_roundtrip_with_draft(self):
        art = make_artifact()
        draft = BitPolicy.from_bits(layers(), {"blk0.w": 2, "blk1.w": 2, "embed": 4})
        art4 = PolicyArtifact.build(art.policy, backend=art.backend,
                                    report=art.report, budget=art.budget,
                                    draft_policy=draft, draft_k=3)
        back = PolicyArtifact.from_json(art4.to_json())
        assert back.version == ARTIFACT_VERSION
        assert back.draft_k == 3
        assert back.draft_policy.bits == draft.bits
        assert back.draft_policy.layers == draft.layers

    def test_draft_k_and_policy_go_together(self):
        art = make_artifact()
        with pytest.raises(ValueError, match="go together"):
            PolicyArtifact.build(art.policy, draft_policy=art.policy)  # k=0
        with pytest.raises(ValueError, match="go together"):
            PolicyArtifact.build(art.policy, draft_k=2)  # no policy

    def test_draft_must_share_registry(self):
        art = make_artifact()
        other = (LayerInfo("other.w", (8, 8), macs=1),)
        with pytest.raises(ValueError, match="same weight registry"):
            PolicyArtifact.build(art.policy, draft_k=2,
                                 draft_policy=BitPolicy.uniform(other, 2))

    def test_attach_draft_grows_pooled_artifact(self):
        from repro.launch.search import attach_draft

        art = make_artifact()
        draft = BitPolicy.uniform(layers(), 4)
        plain = attach_draft(art, draft, 3)
        assert plain.draft_k == 3 and plain.pool is None
        assert art.draft_policy is None  # the input artifact is untouched
        pooled = PolicyArtifact.build(art.policy, state_policy=art.policy,
                                      pool={"block": 16, "num_blocks": 10})
        out = attach_draft(pooled, draft, 3, slots=4)
        # burst scratch: slots * ceil(K/block) extra blocks, recorded in meta
        assert out.pool["num_blocks"] == 10 + 4
        assert out.meta["draft_pool_headroom_blocks"] == 4
        assert pooled.pool["num_blocks"] == 10
        with pytest.raises(ValueError, match="slot count"):
            attach_draft(pooled, draft, 3)

    def test_v3_json_loads_without_draft(self):
        """Pre-v4 artifacts (no draft keys at all) load with draft fields
        empty — the draftless forward-compat contract."""
        doc = json.loads(make_artifact().to_json())
        doc["artifact_version"] = 3
        del doc["draft_policy"], doc["draft_k"]
        back = PolicyArtifact.from_json(json.dumps(doc))
        assert back.version == 3
        assert back.draft_policy is None and back.draft_k == 0


class TestVersionLadder:
    """Older artifact layouts load with their missing fields defaulted."""

    @pytest.mark.parametrize("version", [1, 2, 3, 4, 5])
    def test_older_versions_load(self, version):
        """Every pre-v6 layout loads with its missing fields defaulted —
        the full backward-compat ladder in one sweep."""
        doc = json.loads(make_artifact().to_json())
        doc["artifact_version"] = version
        del doc["provenance"]
        if version < 4:
            del doc["draft_policy"], doc["draft_k"]
        if version < 3:
            del doc["pool"]
        if version < 2:
            del doc["state_policy"], doc["state_registry_hash"]
        back = PolicyArtifact.from_json(json.dumps(doc))
        assert back.version == version
        assert back.provenance is None
        assert back.policy.bits == make_artifact().policy.bits

    @pytest.mark.parametrize("version", [5, 6])
    def test_legacy_kernel_configs_field_is_dropped(self, version):
        """A v5/v6 document that still carries the retired decode-step
        layout table (``kernel_configs``) loads; the table is dropped and
        the policy, state policy and provenance are unchanged."""
        art = make_artifact()
        state = BitPolicy.uniform(
            (LayerInfo("blk0.state.k", (64, 16), macs=0, kind="state"),
             LayerInfo("blk0.state.v", (64, 16), macs=0, kind="state")), 4)
        prov = TestProvenanceV6.PROV if version == 6 else None
        full = PolicyArtifact.build(art.policy, backend=art.backend,
                                    state_policy=state, provenance=prov)
        doc = json.loads(full.to_json())
        doc["artifact_version"] = version
        doc["kernel_configs"] = [
            {"key": {"family": "decode_step", "k_bits": 4, "v_bits": 4,
                     "heads": 2, "head_dim": 16, "block": 8, "impl": "xla"},
             "config": {"place": "dus", "attend": "substitute"},
             "micros": 12.3, "candidates": 4}]
        back = PolicyArtifact.from_json(json.dumps(doc))
        assert back.version == version
        assert back.policy.bits == full.policy.bits
        assert back.state_policy.bits == state.bits
        assert back.state_registry_hash == full.state_registry_hash
        assert back.provenance == prov
        assert "kernel_configs" not in json.loads(back.to_json())


class TestProvenanceV6:
    """v6: search provenance (config/limits/seed + per-phase records)."""

    PROV = {"schema": 1, "backend": "shift_add", "seed": 0,
            "limits": {"size_mib": 0.5}, "config": {"phase2_max_iters": 10},
            "phases": {"weight": {"iterations": 7, "digest": "ab12cd34ef56ab78",
                                  "success": True}}}

    def test_roundtrip_carries_provenance(self):
        art = make_artifact()
        art6 = PolicyArtifact.build(art.policy, backend=art.backend,
                                    provenance=self.PROV)
        back = PolicyArtifact.from_json(art6.to_json())
        assert back.version == ARTIFACT_VERSION == 6
        assert back.provenance == self.PROV
        assert make_artifact().provenance is None  # optional on build

    @pytest.mark.parametrize("mutate,field", [
        (lambda p: "not-a-mapping", "provenance"),
        (lambda p: {k: v for k, v in p.items() if k != "phases"},
         "provenance.phases"),
        (lambda p: dict(p, phases=[1, 2]), "provenance.phases"),
        (lambda p: dict(p, phases={"weight": "nope"}),
         "provenance.phases.weight"),
        (lambda p: dict(p, phases={"weight": {"iterations": -1,
                                              "digest": "ab"}}),
         "provenance.phases.weight.iterations"),
        (lambda p: dict(p, phases={"weight": {"iterations": True,
                                              "digest": "ab"}}),
         "provenance.phases.weight.iterations"),
        (lambda p: dict(p, phases={"weight": {"iterations": 3, "digest": ""}}),
         "provenance.phases.weight.digest"),
    ])
    def test_malformed_provenance_names_the_field(self, mutate, field):
        """Build AND load both reject bad provenance, naming the field."""
        art = make_artifact()
        bad = mutate(dict(self.PROV))
        with pytest.raises(ValueError, match=field.replace(".", r"\.")):
            PolicyArtifact.build(art.policy, provenance=bad)
        doc = json.loads(PolicyArtifact.build(
            art.policy, provenance=self.PROV).to_json())
        doc["provenance"] = bad
        with pytest.raises(ValueError, match=field.replace(".", r"\.")):
            PolicyArtifact.from_json(json.dumps(doc))

    def test_checkpoint_store_wraps_into_artifact_error(self, tmp_path):
        """A corrupted checkpointed artifact surfaces as ArtifactError with
        the source AND the offending provenance field in the message."""
        art = PolicyArtifact.build(make_artifact().policy,
                                   provenance=self.PROV)
        ck.save(str(tmp_path), 3, {"w": np.zeros(2, np.float32)}, artifact=art)
        mpath = tmp_path / "step_00000003" / "MANIFEST.json"
        manifest = json.loads(mpath.read_text())
        manifest["extra"][ck.ARTIFACT_KEY]["provenance"]["phases"]["weight"]["digest"] = ""
        mpath.write_text(json.dumps(manifest))
        with pytest.raises(ck.ArtifactError,
                           match=r"provenance\.phases\.weight\.digest"):
            ck.load_policy_artifact(str(tmp_path))


class TestRegistryHash:
    def test_stable_and_order_sensitive(self):
        assert layer_registry_hash(layers()) == layer_registry_hash(layers())
        assert layer_registry_hash(layers()) != layer_registry_hash(tuple(reversed(layers())))

    def test_macs_excluded(self):
        a = (LayerInfo("w", (8, 8), macs=1),)
        b = (LayerInfo("w", (8, 8), macs=999),)
        assert layer_registry_hash(a) == layer_registry_hash(b)

    def test_mismatch_rejected_after_roundtrip(self):
        art = PolicyArtifact.from_json(make_artifact().to_json())
        art.verify_layers(layers())  # same registry accepted
        other = (LayerInfo("blk0.w", (64, 16), macs=2048),) + layers()[1:]
        with pytest.raises(ValueError, match="hash mismatch"):
            art.verify_layers(other)

    def test_unknown_version_rejected(self):
        doc = json.loads(make_artifact().to_json())
        doc["artifact_version"] = ARTIFACT_VERSION + 1
        with pytest.raises(ValueError, match="artifact version"):
            PolicyArtifact.from_json(json.dumps(doc))


class TestCheckpointPersistence:
    def test_artifact_rides_the_manifest(self, tmp_path):
        art = make_artifact()
        tree = {"w": np.ones((4, 4), np.float32)}
        ck.save(str(tmp_path), 7, tree, extra={"note": "x"}, artifact=art)
        back = ck.load_policy_artifact(str(tmp_path))
        assert back is not None and back.policy.bits == art.policy.bits
        assert back.budget == art.budget
        # extras survive alongside, and restore() is undisturbed
        _, extra = ck.restore(str(tmp_path), {"w": np.zeros((4, 4), np.float32)})
        assert extra["note"] == "x"
        step_dir = tmp_path / "step_00000007"
        assert (step_dir / ck.ARTIFACT_FILE).exists()

    def test_no_artifact_returns_none(self, tmp_path):
        ck.save(str(tmp_path), 1, {"w": np.zeros(2, np.float32)})
        assert ck.load_policy_artifact(str(tmp_path)) is None

    def test_async_store_passthrough(self, tmp_path):
        store = ck.CheckpointStore(str(tmp_path))
        store.save_async(3, {"w": np.ones(2, np.float32)}, artifact=make_artifact())
        store.wait()
        assert store.load_policy_artifact().backend == "shift_add"


class TestTargetsBudgetBridge:
    def test_targets_to_budget_equivalence(self):
        t = Targets(acc_t=0.8, res_t=5.0, acc_buffer=0.02, res_buffer=0.1)
        b = t.to_budget()
        assert b.acc_t == t.acc_t and b.acc_buffer == t.acc_buffer
        (item,) = b.items
        assert item.metric == "resource" and item.limit == 5.0 and item.buffer == 0.1
        assert b.res_ok({"resource": 5.4}, buffered=True)
        assert not b.res_ok({"resource": 5.6}, buffered=True)

    def test_budget_of_rejects_unknown_metric(self):
        with pytest.raises(ValueError, match="unknown cost metric"):
            Budget.of(0.9, watts=3.0)
