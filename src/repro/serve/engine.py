"""Batched serving engine with continuous batching over fixed decode slots.

Design (vLLM-style, adapted to JAX's static shapes):

  * A fixed pool of ``max_slots`` decode slots shares one (B, S, ...) decode
    state (KV caches / SSM states).  All compiled shapes are static.
  * **Admission**: every queued request that fits a free slot is admitted in
    ONE batch — the prompts (minus their last tokens) right-pad to the
    group max rounded to ``prefill_pad`` and prefill in a single
    ``(n_free, pad)`` call (a handful of compiled prefill shapes, not one
    dispatch per request).  The rows tree-insert into their slots in one
    jitted program that donates the decode state, compiled once per
    ``(rows, pad)`` shape and shared by whole-prompt and final-chunk
    inserts (slot ids and lengths are traced arrays); the next
    decode step replays the last prompt token at ``pos = len-1`` — that both
    yields the first sampled token *and* overwrites the pad garbage at that
    position.  Pad positions beyond ``pos`` are masked by the per-slot
    ``kv_valid``.
  * **Decode (the fast path, DESIGN.md §2/§8)**: all active slots advance in
    one jitted step with a *vector* of per-slot positions.  The step is
    compiled with ``donate_argnums`` on the state, so the KV caches update
    in place instead of being copied every token ("zero-copy").  Sampling
    runs on-device inside the same jit (PRNG key carried through), so the
    per-step host transfer is one int32 per slot — never the (B, V) logits.
  * **Completion**: a slot frees on EOS/max_tokens and is immediately
    refilled from the queue (continuous batching).

Weights may be float or SigmaQuant-packed ``QuantizedTensor`` leaves
(quant.apply.quantize_for_serve).  Packed Q/K/V and gate/up groups of equal
bitwidth are fused at admission time into single packed buffers
(quant.apply.fuse_projections) so each decode step launches one kernel per
group; decode is memory-bound on HBM weight bytes, which is exactly where
per-layer bitwidth pays (DESIGN.md §2).

The decode state itself may be quantized (DESIGN.md §11): ``state_bits``
(or a ``PolicyArtifact`` carrying a searched state policy) packs the KV
caches as ``kvcache.QuantizedKVLayer`` containers — int lanes + per-block
scales, heterogeneous per-layer K/V bitwidths — and the engine verifies the
built state against the artifact exactly like it verifies the packed
weights.  Admission quantizes the prefill rows into their slots; each
decode step requantizes only the sequence block it writes.

With ``paged=True`` (or a v3 artifact carrying pool geometry) the quantized
caches become block pools with per-slot block tables (DESIGN.md §12):
admission maps blocks on demand — sharing bit-identical shared-prefix
blocks by refcount — decode appends allocate at block boundaries against
admission-time growth reservations, a shared block copies on first write
(copy-on-write), and completion frees every mapped block, so the budgeted
``state_bytes`` pays for *live* tokens instead of ``max_slots * max_seq``.
Requests the pool cannot cover yet wait in the queue (backpressure).

Padded prefill is exact for every family: attention masks pad positions via
the per-slot ``kv_valid``, and SSM/hybrid prefills mask pad tokens out of
the recurrent-state update (``lengths`` threaded through ``api.prefill``),
so the decode state never depends on the pad length.

``speculate=K`` (with a ``draft_policy``, or auto-enabled by a v4 artifact
carrying one) turns each decode round into a self-speculative burst
(DESIGN.md §13): a strictly-cheaper re-packing of the SAME weights
proposes K tokens, the deployed policy verifies all K+1 positions in one
batched weight pass, and the cache rewinds bitwise-exactly to the accepted
prefix — greedy output is token-identical to the non-speculative engine on
fp, quantized and paged caches, at up to K+1 tokens per full weight read.

Every request runs a full lifecycle (DESIGN.md §14, serve/lifecycle.py):
QUEUED -> PREFILL -> DECODE -> DONE | FAILED | CANCELLED | TIMED_OUT, with
per-request deadlines/TTFT budgets, explicit ``cancel(uid)``, and
finalize-exactly-once resource accounting.  Under pool pressure the engine
degrades through a tiered shed ladder (speculation K -> smaller K -> off,
releasing burst-headroom reservations; then priority-gated preemption that
snapshots a victim's progress back into the queue) instead of waiting
indefinitely.  Non-finite logits are detected per slot INSIDE the fused
decode/speculate dispatch and quarantine only the offending request; in
speculate mode a poisoned draft falls back to the verify (non-speculative)
path for that slot before anything is failed.  A ``FailureInjector``
drives the same paths offline and ``debug_invariants=True`` re-checks pool
refcount conservation, reservation accounting, and zero-beyond-write after
every loop turn.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import kvcache
from repro.configs.base import ArchConfig
from repro.core.policy import PolicyArtifact
from repro.models import registry
from repro.obs import calibration as obs_calibration
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.quant import apply as qapply
from repro.runtime.resilience import (FailureInjector, SimulatedFailure,
                                      StepTimer, StragglerMonitor)
from repro.spec import loop as spec_loop
from repro.spec.draft import build_draft_params
from .lifecycle import (LifecycleError, RequestLifecycle, RequestState,
                        ShedPolicy, spec_ladder)
from .sampling import sample
from .scheduler import ChunkScheduler, SchedulerConfig


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 16
    eos_id: int = -1              # -1: never stop early
    priority: int = 0             # higher admits first / preempts lower
    deadline_s: float | None = None      # end-to-end budget from submission
    ttft_budget_s: float | None = None   # first-token budget from submission


@dataclasses.dataclass
class _Slot:
    req: Request | None = None
    pos: int = 0                  # next write position (chunked prefill:
                                  # head tokens prefilled so far)
    generated: list[int] = dataclasses.field(default_factory=list)
    #: monotonic time of the last committed token (inter-token latency)
    last_token_t: float | None = None
    #: mid-chunked-prefill: the slot holds a request whose prompt is still
    #: being prefilled in budgeted chunks (DESIGN.md §17); excluded from the
    #: decode dispatch and (paged) its device table row is masked to -1
    prefilling: bool = False

    @property
    def free(self) -> bool:
        return self.req is None


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


#: integer counters the legacy ``stats()`` view exposes (wall_s rides as a
#: float counter next to these)
_COUNTER_KEYS = ("prefill_tokens", "prefill_chunks", "decode_steps",
                 "loop_turns", "completed",
                 "spec_steps", "spec_proposed", "spec_accepted", "preemptions",
                 "failed", "cancelled", "timed_out", "nan_quarantined",
                 "nan_draft_fallbacks")

#: step-phase span names in serve-loop order (DESIGN.md §16); ``hook`` only
#: appears when a ``step_hook`` is installed, ``prefill_chunk`` only under
#: chunked prefill (DESIGN.md §17)
_PHASE_NAMES = ("hook", "reap", "admission", "prefill_chunk", "prep",
                "dispatch", "device_sync", "commit", "bookkeeping")


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params: dict, *, max_slots: int = 4,
                 max_seq: int = 256, prefill_pad: int = 32, qimpl: str = "auto",
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 seed: int = 0, state_dtype=jnp.float32,
                 batch_admission: bool = True, fuse_projections: bool = True,
                 state_bits=None, kv_block: int | None = None,
                 paged: bool = False, pool_blocks: int | None = None,
                 share_prefix: bool = True,
                 speculate: int | None = None, draft_policy=None,
                 artifact: PolicyArtifact | None = None,
                 shed: ShedPolicy | None = ShedPolicy(),
                 fault_injector: FailureInjector | None = None,
                 debug_invariants: bool = False,
                 prefill_chunk: int | None = None,
                 step_token_budget: int | None = None):
        if cfg.family in ("audio", "encdec"):
            raise NotImplementedError(
                "enc-dec serving goes through registry.prefill/decode_step directly "
                "(cross-attention KV needs the frames input at admission)")
        self.cfg = cfg
        self._injector = fault_injector
        self._debug_invariants = debug_invariants
        # the searched policy this engine claims to serve: refuse to start if
        # the packed leaf bitwidths disagree with the artifact (the end of the
        # search -> artifact -> packed deployment pipeline, DESIGN.md §10)
        self.artifact = artifact
        self.packed_bits = qapply.packed_policy_bits(params)
        if artifact is not None:
            if self._fault("artifact_mismatch", step=0):
                # drive the real verification path with tampered bits so the
                # deploy-time refusal (not a bypassable shim) is what fires
                name = next(iter(self.packed_bits), None)
                bad = dict(self.packed_bits)
                if name is not None:
                    bad[name] = -1
                raise ValueError(
                    f"packed leaf bitwidths disagree with the policy artifact "
                    f"(injected artifact_mismatch fault): {name}={bad.get(name)}")
            qapply.verify_packed_bits(params, artifact)
        # fuse packed Q/K/V + gate/up groups: one kernel launch per group on
        # the decode fast path; exact-output-preserving (no requantization)
        self.params = qapply.fuse_projections(params) if fuse_projections else params
        self.api = registry.get_api(cfg)
        # self-speculative decoding (DESIGN.md §13): a searched low-bit draft
        # re-packing of the SAME weights proposes K tokens per step; explicit
        # speculate/draft_policy win, else a draft-carrying v4 artifact
        # auto-enables speculation at its searched K
        explicit_draft = draft_policy is not None
        if draft_policy is None and artifact is not None \
                and artifact.draft_policy is not None:
            draft_policy = artifact.draft_policy
            if speculate is None:
                speculate = artifact.draft_k
        if explicit_draft and speculate is None:
            # symmetric with the speculate-without-draft error below: a
            # draft that silently never drafts is a misconfiguration
            raise ValueError("draft_policy given without speculate=K "
                             "(pass speculate, or deploy a v4 artifact "
                             "that records K)")
        self.speculate = int(speculate or 0)
        self.draft_params = None
        self.draft_bits: dict[str, int] = {}
        if self.speculate:
            if draft_policy is None:
                raise ValueError("speculate=K needs a draft_policy (or a "
                                 "draft-carrying v4 artifact)")
            if self.api.decode_verify is None:
                raise NotImplementedError(
                    f"family {cfg.family!r} cannot self-speculate: its decode "
                    f"state has no burst-rewindable KV form (DESIGN.md §13)")
            # draft containers derive from the UNFUSED tree so a heterogeneous
            # draft policy never has to split a fused leaf; equal-bit draft
            # groups re-fuse below exactly like the deployed weights
            draft, self.draft_bits = build_draft_params(params, draft_policy, cfg)
            self.draft_params = (qapply.fuse_projections(draft)
                                 if fuse_projections else draft)
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.prefill_pad = prefill_pad
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.batch_admission = batch_admission
        self._key = jax.random.key(seed)
        self.slots = [_Slot() for _ in range(max_slots)]
        # chunked-prefill continuous batching (DESIGN.md §17): prompts admit
        # in the PREFILLING state and prefill in <= prefill_chunk pieces
        # interleaved with decode turns under a per-step token budget
        self.prefill_chunk = prefill_chunk
        if prefill_chunk is not None:
            if step_token_budget is None:
                # tightest legal budget: a full decode house plus one chunk
                step_token_budget = max_slots + prefill_chunk
            self._scheduler = ChunkScheduler(
                SchedulerConfig(prefill_chunk, step_token_budget), max_slots)
        else:
            if step_token_budget is not None:
                raise ValueError("step_token_budget has no meaning without "
                                 "prefill_chunk (chunked prefill disabled)")
            self._scheduler = None
        #: slot -> per-layer fp K/V scratch carried across chunks (decoder
        #: families); quantization into the live cache happens ONCE at the
        #: final insert, so the cache bytes match the unchunked path
        self._scratch: dict[int, Any] = {}
        #: slot -> padded head tokens (1, S_scr) kept for the whole prefill:
        #: the SSM/hybrid prefix-recompute fallback re-prefills them each
        #: chunk (lengths-masked), decoder finals index them for insertion
        self._chunk_head: dict[int, np.ndarray] = {}
        #: streaming front-end: uid -> on_token callback, plus the poll()
        #: ring of (uid, token) committed since the last drain
        self._on_token: dict[int, Any] = {}
        self._token_events: collections.deque = collections.deque(maxlen=65536)
        # quantized decode state (DESIGN.md §11): explicit state_bits wins,
        # else a searched state policy rides in on the artifact
        if state_bits is None and artifact is not None:
            state_bits = artifact.state_policy
        resolved = (kvcache.resolve_state_bits(state_bits, cfg)
                    if state_bits is not None else None)
        # paged block pool (DESIGN.md §12): explicit paged=True, or an
        # artifact carrying v3 pool geometry
        if artifact is not None and artifact.pool is not None:
            paged = True
            pool_blocks = pool_blocks or int(artifact.pool["num_blocks"])
            kv_block = kv_block or int(artifact.pool["block"])
        if paged and resolved is None:
            raise ValueError("paged KV cache requires a quantized state "
                             "(state_bits or an artifact state policy)")
        self.paged = paged
        self.share_prefix = share_prefix
        self.state = self.api.init_decode_state(cfg, max_slots, max_seq,
                                                state_dtype, state_bits=resolved,
                                                block=kv_block, paged=paged,
                                                pool_blocks=pool_blocks)
        if paged:
            blk = self.state[0].block
            if artifact is not None and artifact.pool is not None and (
                    blk != int(artifact.pool["block"])):
                # resolve_block silently shrank the block because it does not
                # divide max_seq — the pool would then cover fewer tokens at
                # different per-block bytes than the budget priced
                raise ValueError(
                    f"artifact pool block {artifact.pool['block']} does not "
                    f"divide max_seq={max_seq}; serve with a max_seq multiple "
                    f"of the searched block length")
            self.pool = kvcache.BlockPool(self.state[0].num_blocks - 1)
            self._kv_blk = blk
            self._host_tables = np.full((max_slots, max_seq // blk), -1, np.int32)
            self._shared_blocks: dict[int, set[int]] = {}
            self._reserved: dict[int, int] = {}
            self._tables_dirty = False
        else:
            self.pool = None
        #: state-entry name -> packed bits (the state analogue of packed_bits)
        self.state_bits = kvcache.packed_state_bits(self.state)
        if artifact is not None:
            # bidirectional: wrong-width caches fail, a searched state entry
            # the engine left fp fails, and a state policy searched on a
            # different KV surface (head geometry / entry set) fails too —
            # slots/max_seq may differ (geometry-independent surface hash)
            surface = (kvcache.state_layer_infos(cfg, max_slots, max_seq)
                       if artifact.state_policy is not None else None)
            kvcache.verify_state_bits(self.state, artifact, surface=surface)
        # autotuned fused decode-step configs (v5, DESIGN.md §15): validate
        # the artifact table against THIS deployment's cache geometry and
        # install it process-wide before any decode program traces, so
        # serving replays the searched layouts instead of re-timing them
        self._install_kernel_configs()
        # observability (DESIGN.md §16): the metrics registry is the source
        # of truth behind the legacy stats() dict; the process-wide tracer
        # adds step-phase, admission and queue-wait spans when (and only
        # when) enabled
        self.metrics = obs_metrics.MetricsRegistry()
        for name in _COUNTER_KEYS:
            self.metrics.counter(name)
        self.metrics.counter("wall_s")
        #: full loop-turn wall time — admission + prefill turns included,
        #: not just decode-dispatch bodies (health medians agree with the
        #: phase spans on totals)
        self.metrics.histogram("step_time_s")
        self.metrics.histogram("ttft_s")
        self.metrics.histogram("itl_s")
        self._tracer = obs_trace.get_tracer()
        self._shed_events: list[dict] = []
        #: uid -> perf_counter start of the request's current wait in the
        #: queue (tracing only)
        self._lc_marks: dict[int, float] = {}
        # graceful degradation (DESIGN.md §14): the live burst K walks the
        # shed ladder under pool pressure; tier index 0 = full service
        self._shed_policy = shed
        self._spec_ladder = spec_ladder(self.speculate)
        self._shed_tier = 0
        self._k_live = self.speculate
        self._straggler = StragglerMonitor()
        self.lifecycles: dict[int, RequestLifecycle] = {}
        self._queue: list[Request] = []
        self._cancel_requested: set[int] = set()
        self._pending_token: dict[int, int] = {}
        #: quantized decode-state layers need the burst snapshot/replay
        #: commit protocol (spec.loop); fp layers rewind for free
        self._quant_state = any(
            isinstance(layer, (kvcache.QuantizedKVLayer, kvcache.PagedKVLayer))
            for layer in (self.state if isinstance(self.state, list) else []))
        self._spec_jits: dict[int, dict] = {}  # burst length K -> jitted fns
        self._qimpl = qimpl

        api, cfg_ = self.api, cfg

        def decode(params, state, tokens, pos, key, inject, temperature,
                   top_k, top_p):
            logits, state = api.decode_step(params, cfg_, state, tokens, pos, qimpl=qimpl)
            # numerical anomaly guard (DESIGN.md §14): detect non-finite
            # logits per slot INSIDE the dispatch — the host sees one (B,)
            # bool, never the (B, V) logits — and sample from a zeroed row
            # so a poisoned slot cannot derail the batch's sampling math.
            # ``inject`` is the chaos harness's per-slot NaN needle (zeros
            # in production; an array arg, so injection never retraces).
            last = logits[:, -1] + inject[:, None]
            bad = ~jnp.isfinite(last).all(axis=-1)
            last = jnp.where(bad[:, None], 0.0, last)
            if temperature > 0.0:  # static arg: greedy never touches the key
                key, sub = jax.random.split(key)
                toks = sample(last, sub, temperature=temperature, top_k=top_k,
                              top_p=top_p)
            else:
                toks = sample(last)
            return toks, state, key, bad

        def prefill(params, tokens, lengths):
            _, st = api.prefill(params, cfg_, tokens=tokens, lengths=lengths,
                                qimpl=qimpl)
            return st

        # donate the decode state: the KV caches / SSM states alias in place
        # instead of being copied every token.  temperature/top_k/top_p ride
        # as static args so mutating engine.temperature between runs retraces
        # instead of silently keeping the init-time value.
        self._decode = jax.jit(decode, donate_argnums=(1,), static_argnums=(6, 7, 8))
        self._prefill = jax.jit(prefill)
        # admission's K/V insertion: ONE donated program per (rows, pad)
        # shape — slot ids, valid lengths and paged row tables ride as
        # traced int32 arrays, so neither a slot nor a prompt length
        # retraces, and the state's buffers update in place.  Dense and
        # paged inserts run under the same compiler, so their blocks
        # quantize bit-identically (DESIGN.md §11/§12/§17).
        def insert(state, ids, st_new, lengths):
            return kvcache.insert_state_rows(state, ids, st_new, lengths)

        def insert_paged(state, row_tables, st_new, lengths):
            return [kvcache.paged.insert_prefill_rows(
                        layer, row_tables, new["k"], new["v"], valid_len=lengths)
                    for layer, new in zip(state, st_new)]

        self._insert = jax.jit(insert, donate_argnums=(0,))
        self._insert_paged = jax.jit(insert_paged, donate_argnums=(0,))
        # chunked prefill: one donated-scratch dispatch per chunk.  The
        # offset rides as a traced scalar so every chunk of a prompt reuses
        # ONE compilation per (scratch_len, chunk) shape pair.
        if api.prefill_chunk is not None:
            def chunk_step(params, scratch, tokens, offset):
                return api.prefill_chunk(params, cfg_, scratch, tokens,
                                         offset, qimpl=qimpl)
            self._chunk_step = jax.jit(chunk_step, donate_argnums=(1,))
        else:
            self._chunk_step = None

    # -- autotuned kernel configs (DESIGN.md §15) --------------------------
    def _install_kernel_configs(self) -> None:
        """Replay a v5 artifact's autotuned fused decode-step configs.

        Every recorded candidate is bitwise-equivalent, so a wrong table can
        only cost speed — but a table tuned for a different cache geometry
        means the artifact does not describe this deployment at all, which
        is refused the same way a bitwidth mismatch is (``ArtifactError``).
        Keys for bit pairs the deployed policy doesn't use are tolerated.
        """
        from repro.checkpoint.store import ArtifactError
        from repro.kernels import autotune

        entries = (self.artifact.kernel_configs
                   if self.artifact is not None else None)
        if not entries:
            return
        qlayers = [l for l in (self.state if isinstance(self.state, list) else [])
                   if isinstance(l, (kvcache.QuantizedKVLayer,
                                     kvcache.PagedKVLayer))]
        if not qlayers:
            raise ArtifactError(
                "policy artifact carries kernel_configs but the engine built "
                "a float decode state (no fused quantized decode step exists "
                "to configure)")
        lyr = qlayers[0]
        try:
            autotune.validate_configs(
                entries, heads=lyr.shape[2], head_dim=lyr.shape[3],
                block=lyr.block,
                bit_pairs={(l.k_bits, l.v_bits) for l in qlayers})
        except ValueError as e:
            raise ArtifactError(
                f"policy artifact kernel_configs do not fit this "
                f"deployment: {e}") from e
        autotune.set_active_configs(entries)

    # -- observability (DESIGN.md §16) ------------------------------------
    def _count(self, name: str, n: int = 1) -> None:
        self.metrics.counter(name).inc(n)

    def _nsteps(self) -> int:
        return int(self.metrics.counter("decode_steps").value)

    def _span(self, name: str, **args):
        """A step-phase span: trace event + ``phase/<name>`` histogram when
        tracing is enabled, the shared no-op singleton otherwise."""
        tr = self._tracer
        if not tr.enabled:
            return obs_trace.NOOP_SPAN
        return tr.span(name, cat="phase", track="engine",
                       hist=self.metrics.histogram("phase/" + name),
                       args=args or None)

    def _admit_span(self, name: str, n: int, pad: int):
        """A span of admission's work (``prefill_dispatch``, ``kv_insert``)
        inside the ``admission`` or ``prefill_chunk`` phase: category
        ``admit``, so phase totals count its time once.  ``n`` requests at
        padded length ``pad``."""
        tr = self._tracer
        if not tr.enabled:
            return obs_trace.NOOP_SPAN
        return tr.span(name, cat="admit", track="engine",
                       args={"n": n, "pad": pad})

    def _observe_transition(self, lc: RequestLifecycle, old: RequestState,
                            new: RequestState, now: float,
                            diagnostic: str) -> None:
        """Lifecycle observer: a ``queued`` span on the request's own trace
        track for each stretch it waits in the queue (from submit, a
        preemption or a rolled-back admission, to its admission or end).
        Keyed off the SAME validated transitions the resource accounting
        uses (serve/lifecycle.py)."""
        t0 = self._lc_marks.pop(lc.uid, None)
        tr = self._tracer
        if not tr.enabled:
            return
        t = tr.now()
        if t0 is not None:
            tr.complete(old.value, ts=t0, dur=t - t0, cat="request",
                        track=f"req/{lc.uid}", args={"uid": lc.uid})
        if new is RequestState.QUEUED:
            self._lc_marks[lc.uid] = t

    # -- fault injection (runtime/resilience.py) ---------------------------
    def _fault(self, site: str, step: int | None = None) -> bool:
        """Consume-once poll of the injector at a serve fault site."""
        if self._injector is None:
            return False
        if step is None:
            step = self._nsteps()
        return self._injector.fires(site, step)

    # -- speculative decode (DESIGN.md §13) -------------------------------
    def _spec_fn(self, k: int):
        """ONE jitted draft-K / verify / accept / commit step for burst K.

        Cached per K: the burst shrinks near ``max_seq`` (K_eff), so at most
        ``speculate`` distinct compilations exist.  The whole round is a
        single dispatch — no host decision exists between its stages, so the
        snapshot, the K draft steps (low-bit containers, appending into the
        shared cache), the restore, the batched K+1 verify pass, the
        accept/reject math, and the bitwise-exact commit replay (spec.loop)
        all fuse into one donated-state call; the only per-step host
        transfer is (acc, out_tokens).
        """
        if k in self._spec_jits:
            return self._spec_jits[k]
        api, cfg_, qimpl = self.api, self.cfg, self._qimpl
        quant = self._quant_state

        def spec_step(params, dparams, state, tokens, pos, key, inject_draft,
                      inject_verify, temperature, top_k, top_p):
            saved = spec_loop.snapshot_state(state, pos, k) if quant else None
            tok, d_toks, d_logits = tokens, [], []
            # per-slot draft anomaly flag (DESIGN.md §14): sticky across the
            # burst; a poisoned slot's draft logits zero out so its (garbage)
            # proposals stay finite, and forcing acc=0 below makes the round
            # degrade to the exact non-speculative verify token for that slot
            draft_bad = jnp.zeros((tokens.shape[0],), bool)
            for j in range(k):
                logits, state = api.decode_step(dparams, cfg_, state, tok,
                                                pos + j, qimpl=qimpl)
                last = logits[:, -1]
                if j == 0:
                    last = last + inject_draft[:, None]
                draft_bad = draft_bad | ~jnp.isfinite(last).all(axis=-1)
                last = jnp.where(draft_bad[:, None], 0.0, last)
                if temperature > 0.0:
                    key, sub = jax.random.split(key)
                    t = sample(last, sub, temperature=temperature, top_k=top_k,
                               top_p=top_p)
                else:
                    t = sample(last)
                d_toks.append(t)
                d_logits.append(last)
                tok = t[:, None]
            d_toks = jnp.stack(d_toks, axis=1)
            d_logits = jnp.stack(d_logits, axis=1)
            if quant:
                state = spec_loop.restore_state(state, saved, pos, k)
            burst = jnp.concatenate([tokens, d_toks], axis=1)   # (B, K+1)
            logits, state, burst_kv = api.decode_verify(params, cfg_, state,
                                                        burst, pos, qimpl=qimpl)
            logits = logits + inject_verify[:, None, None]
            verify_bad = ~jnp.isfinite(logits).all(axis=(1, 2))
            logits = jnp.where(verify_bad[:, None, None], 0.0, logits)
            if temperature > 0.0:
                key, sub = jax.random.split(key)
                acc, out = spec_loop.accept_tokens(
                    logits, d_toks, d_logits, sub, temperature=temperature,
                    top_k=top_k, top_p=top_p)
            else:
                acc, out = spec_loop.accept_tokens(logits, d_toks, d_logits,
                                                   None)
            # poisoned slots accept nothing: with acc=0 the emitted token is
            # the verify pass's position-0 output — byte-for-byte the token
            # the non-speculative engine would have produced (draft fallback)
            acc = jnp.where(draft_bad | verify_bad, 0, acc)
            if quant:
                state = spec_loop.commit_state(state, saved, pos, acc,
                                               burst_kv, k, qimpl=qimpl)
            return acc, out, state, key, draft_bad, verify_bad

        fn = jax.jit(spec_step, donate_argnums=(2,), static_argnums=(8, 9, 10))
        self._spec_jits[k] = fn
        return fn

    def _burst_len(self, active: list[int]) -> int:
        """Burst K for this step: the LIVE K (configured K minus any shed
        tiers), shrunk so no slot's burst can write past ``max_seq - 1``
        (active slots sit at ``pos <= max_seq - 2``, so this is >= 1
        whenever speculation is live)."""
        max_pos = max(self.slots[i].pos for i in active)
        return max(min(self._k_live, self.max_seq - 1 - max_pos), 0)

    def _spec_step(self, active: list[int], tokens_h, pos_h, k: int,
                   inject_draft, inject_verify):
        """One draft-K / verify / accept / commit round -> (emitted tokens
        per active slot (1..K+1 each: accepted draft prefix + bonus),
        per-slot draft/verify non-finite flags)."""
        with self._span("dispatch", k=k):
            acc, out, self.state, self._key, draft_bad, verify_bad = \
                self._spec_fn(k)(
                    self.params, self.draft_params, self.state,
                    jnp.asarray(tokens_h), jnp.asarray(pos_h), self._key,
                    jnp.asarray(inject_draft), jnp.asarray(inject_verify),
                    self.temperature, self.top_k, self.top_p)
        with self._span("device_sync"):
            jax.block_until_ready((acc, out, draft_bad, verify_bad))
        acc_h = np.asarray(acc)      # the step's ONLY host transfer:
        out_h = np.asarray(out)      # (B,) accepts + (B, K+1) tokens + flags
        self._count("spec_steps")
        emitted: dict[int, list[int]] = {}
        for i in active:
            a = int(acc_h[i])
            emitted[i] = [int(t) for t in out_h[i, : a + 1]]
            self._count("spec_proposed", k)
            self._count("spec_accepted", a)
        return emitted, np.asarray(draft_bad), np.asarray(verify_bad)

    # -- state surgery ---------------------------------------------------
    def _insert_rows(self, slot_ids: list[int], st_new: Any,
                     lengths: jax.Array) -> None:
        """Tree-insert rows of a batched prefill state into their slots.

        fp leaves scatter directly (one scatter per leaf, no per-row
        full-cache copies); quantized KV layers quantize the fp prefill
        rows block-wise on the way in — kvcache.insert_state_rows is the
        shared walker (the calibration env admits the same way, eagerly).
        One dispatch of the donated ``_insert`` program: the host returns
        before the device has run it.
        """
        self.state = self._insert(self.state, jnp.asarray(slot_ids, jnp.int32),
                                  st_new, lengths)

    # -- paged block bookkeeping (DESIGN.md §12) --------------------------
    def _push_tables(self) -> None:
        """Mirror the host block tables into every paged layer's device copy.

        Rows of slots still mid-chunked-prefill push as -1: their mapped
        blocks hold no bytes until the final insert, and the lockstep decode
        dispatch must keep appending those slots' (idle) writes into the
        trash block instead of corrupting mapped-but-unwritten blocks.  The
        real row pushes when the prefill completes (``_finish_prefill`` sets
        ``_tables_dirty``).
        """
        if not self._tables_dirty:
            return
        tbl = self._host_tables
        masked = [i for i, s in enumerate(self.slots) if s.prefilling]
        if masked:
            tbl = tbl.copy()
            tbl[masked] = -1
        # one device copy PER layer: the decode step donates the state, and
        # donation rejects the same buffer appearing in two arguments
        self.state = [kvcache.paged.with_table(layer, jnp.asarray(tbl))
                      for layer in self.state]
        self._tables_dirty = False

    def _map_slot_blocks(self, slot_id: int, req: Request) -> bool:
        """Map blocks covering positions ``[0, len(prompt) - 1]`` for a slot
        and RESERVE its decode growth (blocks the appends will cross into,
        plus one copy-on-write split if the write block is shared), so a
        mid-decode allocation can never fail for an admitted request.

        Blocks whose occupied rows are bit-identical to a block some other
        slot already maps (a shared prefix, block-aligned coverage) map the
        SAME physical block with a bumped refcount instead of allocating —
        the first append into such a block copies it first (copy-on-write,
        ``_ensure_append_blocks``).  Returns False (with full rollback) when
        the pool cannot cover prompt + growth, so the caller can requeue the
        request instead of half-admitting it.
        """
        blk = self._kv_blk
        prompt = req.prompt
        length = len(prompt)
        w_new = length - 1                      # head rows written at admission
        tb_first = (length - 1) // blk          # block the replay append hits
        # highest position this request can ever write: at least the replay
        # append at length-1 (even for max_new_tokens <= 0 the decode loop
        # runs one step), at most max_seq - 2 (run()'s stop condition) — plus
        # speculate burst headroom: a draft/verify burst transiently writes
        # up to K positions past the committed one (capped at max_seq - 1),
        # and reserving it here is what keeps a speculative step from ever
        # stranding an admitted request mid-decode (DESIGN.md §13)
        last_pos = min(max(length - 1, length - 2 + req.max_new_tokens),
                       self.max_seq - 2)
        last_pos = min(last_pos + self._k_live, self.max_seq - 1)
        tb_last = last_pos // blk
        donor, common = None, 0
        if self.share_prefix:
            for other, slot in enumerate(self.slots):
                # a prefilling slot cannot donate: its mapped blocks hold no
                # pool bytes until the final scratch insert lands
                if other == slot_id or slot.free or slot.prefilling:
                    continue
                lcp = 0
                for a, b in zip(prompt, slot.req.prompt):
                    if a != b:
                        break
                    lcp += 1
                if lcp > common:
                    donor, common = other, lcp
        plan: list[tuple[int, int | None]] = []  # (logical block, donor bid)
        n_fresh = 0
        for j in range(tb_first + 1):
            end_new = min(w_new, (j + 1) * blk)
            src = None
            if donor is not None and self._host_tables[donor, j] >= 0:
                w_d = self.slots[donor].pos
                # identical occupancy, fully inside the common prefix:
                # the donor's block bytes ARE this slot's block bytes
                if min(w_d, (j + 1) * blk) == end_new and end_new <= common:
                    src = int(self._host_tables[donor, j])
            plan.append((j, src))
            n_fresh += src is None
        # growth: every block past the first write block, plus the CoW copy
        # if the first write block itself is shared
        growth = (tb_last - tb_first) + (plan[tb_first][1] is not None)
        if self.pool.available < n_fresh + growth:
            return False
        row = self._host_tables[slot_id]
        shared: set[int] = set()
        for j, src in plan:
            if src is not None:
                row[j] = self.pool.incref(src)
                shared.add(j)
            else:
                row[j] = self.pool.alloc()
        self.pool.reserve(growth)
        self._reserved[slot_id] = growth
        self._shared_blocks[slot_id] = shared
        self._tables_dirty = True
        return True

    def _map_chunked_blocks(self, slot_id: int, req: Request) -> bool:
        """Reserve a chunked admission's ENTIRE block need upfront; map
        nothing yet.

        Chunked slots take no shared-prefix donors (their bytes land only at
        the final insert, so there is nothing to compare against), so the
        whole span — head blocks plus decode growth plus burst headroom,
        the same ``last_pos`` formula as ``_map_slot_blocks`` — is a plain
        reservation.  Each chunk then maps its fully-filled blocks via
        ``_grow_alloc`` (reservation -> mapped, one ledger), which keeps
        ``_reserved[slot] == _required_growth(slot, k)`` exact at every
        progress point with NO resync — ``check_invariants`` is unchanged.
        Returns False (nothing touched) when the pool cannot cover the span.
        """
        blk = self._kv_blk
        length = len(req.prompt)
        last_pos = min(max(length - 1, length - 2 + req.max_new_tokens),
                       self.max_seq - 2)
        last_pos = min(last_pos + self._k_live, self.max_seq - 1)
        total = last_pos // blk + 1
        if self.pool.available < total:
            return False
        self.pool.reserve(total)
        self._reserved[slot_id] = total
        self._shared_blocks[slot_id] = set()
        return True

    def _grow_alloc(self, slot_id: int) -> int:
        """Allocate one block against the slot's admission-time reservation."""
        n = self._reserved.get(slot_id, 0)
        if n > 0:
            self.pool.unreserve(1)
            self._reserved[slot_id] = n - 1
        return self.pool.alloc()

    def _ensure_append_blocks(self, active: list[int], span: int = 1) -> None:
        """Before a decode step: every block an active slot can write this
        step — positions ``[pos, pos + span - 1]``, span = K_eff + 1 under
        speculation — must be mapped (allocate on demand at block
        boundaries) and exclusively owned (copy-on-write when a shared
        prefix diverges)."""
        cow_src, cow_dst = [], []
        for i in active:
            pos = self.slots[i].pos
            last = min(pos + span - 1, self.max_seq - 1)
            for tb in range(pos // self._kv_blk, last // self._kv_blk + 1):
                bid = int(self._host_tables[i, tb])
                if bid < 0:
                    self._host_tables[i, tb] = self._grow_alloc(i)
                    self._tables_dirty = True
                elif self.pool.refcount(bid) > 1:
                    fresh = self._grow_alloc(i)
                    self.pool.cow_copies += 1
                    self.pool.decref(bid)
                    self._host_tables[i, tb] = fresh
                    cow_src.append(bid)
                    cow_dst.append(fresh)
                    self._tables_dirty = True
        if cow_src:
            self.state = [kvcache.paged.copy_blocks(layer, cow_src, cow_dst)
                          for layer in self.state]
        self._push_tables()

    def _free_slot_blocks(self, slot_id: int) -> None:
        for bid in self._host_tables[slot_id]:
            if bid >= 0:
                self.pool.decref(int(bid))
        self._host_tables[slot_id] = -1
        self.pool.unreserve(self._reserved.pop(slot_id, 0))
        self._shared_blocks.pop(slot_id, None)
        self._tables_dirty = True

    # -- graceful degradation (DESIGN.md §14) -----------------------------
    def _required_growth(self, slot_id: int, k: int) -> int:
        """Blocks an active slot still needs reserved to finish under burst
        headroom ``k``: unmapped logical blocks in its remaining write span,
        plus one copy-on-write split per still-shared mapped block there.
        Mirrors ``_map_slot_blocks``'s admission-time formula evaluated at
        the current write position — ``_reserved[slot] == this`` is the
        reservation-accounting invariant ``check_invariants`` pins."""
        slot = self.slots[slot_id]
        req, blk = slot.req, self._kv_blk
        length = len(req.prompt)
        last_pos = min(max(length - 1, length - 2 + req.max_new_tokens),
                       self.max_seq - 2)
        last_pos = min(last_pos + k, self.max_seq - 1)
        need = 0
        for tb in range(slot.pos // blk, last_pos // blk + 1):
            bid = int(self._host_tables[slot_id, tb])
            if bid < 0 or self.pool.refcount(bid) > 1:
                need += 1
        return need

    def _set_live_k(self, k: int) -> bool:
        """Change the live speculation burst length, resyncing every active
        slot's growth reservation to the new headroom.  Shrinking always
        succeeds (it releases reservations back to the pool — that is the
        shed ladder's whole point); growing back is refused (False) when the
        pool cannot re-secure the larger headroom for ALL active slots, so
        restoring speculation can never strand an admitted request."""
        if k == self._k_live:
            return True
        if self.paged:
            deltas: dict[int, int] = {}
            for i, s in enumerate(self.slots):
                if s.free:
                    continue
                deltas[i] = self._required_growth(i, k) - self._reserved.get(i, 0)
            grow = sum(d for d in deltas.values() if d > 0)
            shrink = -sum(d for d in deltas.values() if d < 0)
            if grow > self.pool.available + shrink:
                return False
            for i, d in sorted(deltas.items(), key=lambda kv: kv[1]):
                if d < 0:                  # releases first: frees headroom
                    self.pool.unreserve(-d)
                elif d > 0:
                    self.pool.reserve(d)
                self._reserved[i] = self._reserved.get(i, 0) + d
        self._k_live = k
        return True

    def _shed_event(self, action: str, **extra) -> None:
        ev = {"action": action, "step": self._nsteps(),
              "tier": self._shed_tier, "k": self._k_live, **extra}
        self._shed_events.append(ev)

    def _maybe_shed(self, waiting: list[Request]) -> bool:
        """ONE degradation action for this loop turn (True if state changed):
        walk the speculation ladder down a tier (releasing draft burst
        headroom reservations), then — ladder exhausted — preempt the
        lowest-priority resident strictly below the best waiting priority.
        Neither applies -> fall back to plain backpressure waiting."""
        pol = self._shed_policy
        if pol is None:
            return False
        if pol.spec_tiers and self._shed_tier < len(self._spec_ladder) - 1:
            if self._set_live_k(self._spec_ladder[self._shed_tier + 1]):
                self._shed_tier += 1
                self._shed_event("spec_shed")
                return True
        return self._preempt_for(waiting)

    def _preempt_for(self, waiting: list[Request]) -> bool:
        """Preempt the lowest-priority resident strictly below the best
        waiting priority (equal priorities never thrash).  Fires from the
        shed ladder under block-pool pressure AND directly under slot
        pressure (all slots busy, a higher-priority request waiting)."""
        pol = self._shed_policy
        if pol is None or not pol.preempt or not waiting:
            return False
        best = max(r.priority for r in waiting)
        victims = [i for i, s in enumerate(self.slots)
                   if not s.free and s.req.priority < best]
        if not victims:
            return False
        # lowest priority first; ties preempt the least-progressed slot
        # (least replayed work)
        victim = min(victims, key=lambda i: (
            self.slots[i].req.priority, len(self.slots[i].generated)))
        self._preempt(victim)
        return True

    def _relax_shed(self) -> None:
        """Pressure-free turn: climb back one ladder tier if the pool can
        re-secure the bigger burst headroom for every active slot."""
        pol = self._shed_policy
        if (pol is None or not pol.restore or self._shed_tier == 0):
            return
        if self._set_live_k(self._spec_ladder[self._shed_tier - 1]):
            self._shed_tier -= 1
            self._shed_event("restore")

    def _preempt(self, slot_id: int) -> None:
        """Snapshot a victim's progress and send it back to QUEUED: its
        prompt + generated tokens become the resumed request's prompt, which
        replays through the normal prefill/shared-prefix path; the remaining
        token budget shrinks by what was already produced, so the resumed
        stream picks up exactly where the victim stopped."""
        s = self.slots[slot_id]
        req = s.req
        lc = self.lifecycles.get(req.uid)
        now = time.monotonic()
        if lc is not None:
            lc.transition(RequestState.QUEUED, now,
                          diagnostic="preempted under pool pressure")
            lc.preemptions += 1
            lc.resume_tokens.extend(s.generated)
            lc.prefill_progress = 0  # a mid-chunk victim restarts its prefill
        self._count("preemptions")
        self._shed_event("preempt", uid=req.uid, at_tokens=len(s.generated))
        resumed = dataclasses.replace(
            req, prompt=req.prompt + s.generated,
            max_new_tokens=req.max_new_tokens - len(s.generated))
        self._release_slot(slot_id)
        self._queue.append(resumed)

    # -- lifecycle bookkeeping (serve/lifecycle.py) -----------------------
    def submit(self, req: Request, on_token=None) -> RequestLifecycle:
        """Enqueue a request (usable mid-``run`` from a step hook).  Creates
        the lifecycle record; admission order is priority-first, FIFO within
        a priority class.

        ``on_token(uid, token)`` — optional streaming callback, fired from
        the commit phase for every token the moment it commits (speculative
        burst tokens fire individually, in order).  Tokens also land in the
        ``poll()`` ring regardless of whether a callback is installed.
        """
        lc = RequestLifecycle(uid=req.uid, priority=req.priority,
                              deadline_s=req.deadline_s,
                              ttft_budget_s=req.ttft_budget_s,
                              enqueued_t=time.monotonic())
        existing = self.lifecycles.get(req.uid)
        if existing is not None and not existing.terminal:
            raise LifecycleError(
                f"request uid {req.uid} is already live ({existing.state.value})")
        lc.observer = self._observe_transition
        tr = self._tracer
        if tr.enabled:
            self._lc_marks[req.uid] = tr.now()
        self.lifecycles[req.uid] = lc
        if on_token is not None:
            self._on_token[req.uid] = on_token
        self._queue.append(req)
        return lc

    def poll(self):
        """Drain committed-but-unread tokens: yields ``(uid, token)`` in
        commit order.  Call between ``run()`` invocations or from a step
        hook mid-run; the ring keeps the most recent 65536 events."""
        while self._token_events:
            yield self._token_events.popleft()

    def cancel(self, uid: int) -> None:
        """Request cancellation; takes effect at the next loop turn (the
        request may still complete first — cancelling a terminal request is
        a no-op, never an error)."""
        self._cancel_requested.add(uid)

    def _release_slot(self, slot_id: int) -> None:
        """Free a slot's compute + paged resources (no lifecycle change)."""
        if self.paged:
            self._free_slot_blocks(slot_id)
        self.slots[slot_id] = _Slot()
        self._pending_token.pop(slot_id, None)
        self._scratch.pop(slot_id, None)
        self._chunk_head.pop(slot_id, None)

    def _finalize(self, slot_id: int | None, req: Request,
                  state: RequestState, results: dict[int, list[int]],
                  diagnostic: str = "") -> None:
        """Move a request to a terminal state and free its resources.

        The lifecycle transition is the free-exactly-once guard: a second
        finalization of the same request raises ``LifecycleError`` before
        any slot/block/reservation is touched twice.
        """
        lc = self.lifecycles.get(req.uid)
        gen = list(self.slots[slot_id].generated) if slot_id is not None else []
        if lc is not None:
            lc.transition(state, time.monotonic(), diagnostic)
            lc.tokens = lc.resume_tokens + gen
            results[req.uid] = lc.tokens
        else:
            results[req.uid] = gen
        if slot_id is not None:
            self._release_slot(slot_id)
        self._on_token.pop(req.uid, None)
        self._count({RequestState.DONE: "completed",
                     RequestState.FAILED: "failed",
                     RequestState.CANCELLED: "cancelled",
                     RequestState.TIMED_OUT: "timed_out"}[state])

    def _reap(self, now: float, results: dict[int, list[int]]) -> None:
        """Apply pending cancellations and deadline/TTFT expiries, queued
        and resident alike, before this turn's admission."""
        for uid in sorted(self._cancel_requested):
            lc = self.lifecycles.get(uid)
            if lc is None or lc.terminal:
                self._cancel_requested.discard(uid)
                continue
            qi = next((j for j, r in enumerate(self._queue) if r.uid == uid),
                      None)
            if qi is not None:
                self._finalize(None, self._queue.pop(qi),
                               RequestState.CANCELLED, results,
                               diagnostic="cancelled while queued")
            else:
                si = next((i for i, s in enumerate(self.slots)
                           if not s.free and s.req.uid == uid), None)
                if si is not None:
                    self._finalize(si, self.slots[si].req,
                                   RequestState.CANCELLED, results,
                                   diagnostic="cancelled mid-decode")
            self._cancel_requested.discard(uid)
        for j in range(len(self._queue) - 1, -1, -1):
            req = self._queue[j]
            lc = self.lifecycles.get(req.uid)
            why = lc.expired(now) if lc is not None else None
            if why is not None:
                self._finalize(None, self._queue.pop(j),
                               RequestState.TIMED_OUT, results,
                               diagnostic=f"{why} budget exceeded while queued")
        for i, s in enumerate(self.slots):
            if s.free:
                continue
            lc = self.lifecycles.get(s.req.uid)
            why = lc.expired(now) if lc is not None else None
            if why is not None:
                self._finalize(i, s.req, RequestState.TIMED_OUT, results,
                               diagnostic=f"{why} budget exceeded mid-decode")

    def _row_tables(self, with_head: list[tuple[int, list[int]]],
                    pad: int) -> np.ndarray:
        """Physical write destinations per (prefill row, logical block).

        -1 skips the write: pad blocks past the row's head rows, and
        shared-prefix blocks whose bytes a donor slot already holds (or
        writes in this very batch — same rows, same quantizer, same bits).
        """
        blk = self._kv_blk
        npb = -(-pad // blk)
        out = np.full((len(with_head), npb), -1, np.int32)
        for r, (slot_id, head) in enumerate(with_head):
            shared = self._shared_blocks.get(slot_id, set())
            for j in range(min(npb, -(-len(head) // blk))):
                if j not in shared:
                    out[r, j] = self._host_tables[slot_id, j]
        return out

    def _insert_rows_paged(self, with_head, st_new, lengths, pad: int) -> None:
        row_tables = jnp.asarray(self._row_tables(with_head, pad))
        self.state = self._insert_paged(self.state, row_tables, st_new, lengths)

    # -- admission ---------------------------------------------------------
    def _admit(self, assignments: list[tuple[int, Request]]) -> list[Request]:
        """Admit requests into free slots; one padded prefill for the batch.

        Returns the requests that could NOT be admitted (paged pool too full
        to cover their prompts) for the caller to requeue.
        """
        with_head: list[tuple[int, list[int]]] = []
        rejected: list[Request] = []
        admitted: list[Request] = []
        now = time.monotonic()
        for slot_id, req in assignments:
            prompt = req.prompt
            assert 1 <= len(prompt) < self.max_seq, (len(prompt), self.max_seq)
            lc = self.lifecycles.get(req.uid)
            if lc is not None:
                lc.transition(RequestState.PREFILL, now)
            slot = self.slots[slot_id]
            slot.req, slot.generated = req, []
            w = len(prompt) - 1
            if self._scheduler is not None and w >= 1:
                # chunked admission (DESIGN.md §17): the slot enters the
                # PREFILLING state with zero progress; the scheduler feeds
                # its head to the model chunk-by-chunk across loop turns,
                # and the request stays in lifecycle PREFILL until the final
                # chunk inserts.  No pending replay token yet — that is what
                # keeps the slot out of the decode dispatch.
                slot.pos = 0
                slot.prefilling = True
                if self.paged and not self._map_chunked_blocks(slot_id, req):
                    self.slots[slot_id] = _Slot()
                    if lc is not None:
                        lc.transition(RequestState.QUEUED, now)
                    rejected.append(req)
                    continue
                pad = min(_round_up(w, self.prefill_pad), self.max_seq)
                head = np.zeros((1, pad), np.int32)
                head[0, :w] = prompt[:-1]
                self._chunk_head[slot_id] = head
                if self.api.init_prefill_scratch is not None:
                    self._scratch[slot_id] = self.api.init_prefill_scratch(
                        self.cfg, pad)
                continue
            slot.pos = w
            if w == 0 and self.api.prefill_chunk is None:
                # length-1 prompts run no prefill; attention caches are
                # causal-masked so stale rows never leak, but SSM/hybrid
                # recurrent state is NOT position-masked — zero the slot's
                # rows so the request decodes from the initial state instead
                # of the previous occupant's leftovers
                self._reset_recurrent_rows(slot_id)
            if self.paged and not self._map_slot_blocks(slot_id, req):
                self.slots[slot_id] = _Slot()
                if lc is not None:   # pool too full: back to the queue
                    lc.transition(RequestState.QUEUED, now)
                rejected.append(req)
                continue
            admitted.append(req)
            self._pending_token[slot_id] = prompt[-1]  # replayed next step
            if len(prompt) > 1:
                with_head.append((slot_id, prompt[:-1]))
        if self.paged:
            self._push_tables()
        if with_head:
            n = len(with_head)
            pad = min(_round_up(max(len(h) for _, h in with_head),
                                self.prefill_pad), self.max_seq)
            with self._admit_span("prefill_dispatch", n, pad):
                toks = np.zeros((n, pad), np.int32)
                for row, (_, head) in enumerate(with_head):
                    toks[row, : len(head)] = head
                lengths = jnp.asarray([len(h) for _, h in with_head],
                                      jnp.int32)
                st = self._prefill(self.params, jnp.asarray(toks), lengths)
            with self._admit_span("kv_insert", n, pad):
                if self.paged:
                    self._insert_rows_paged(with_head, st, lengths, pad)
                else:
                    self._insert_rows([slot_id for slot_id, _ in with_head],
                                      st, lengths)
            self._count("prefill_tokens", sum(len(h) for _, h in with_head))
        now = time.monotonic()
        for req in admitted:
            lc = self.lifecycles.get(req.uid)
            if lc is not None:
                lc.transition(RequestState.DECODE, now)
        return rejected

    def _reset_recurrent_rows(self, slot_id: int) -> None:
        """Zero one slot's rows across every plain-array state leaf.

        Used for length-1 prompt admissions on recurrent families (see
        ``_admit``): quantized KV containers are skipped (attention is
        causal; their stale rows are already masked), every dense leaf with
        a leading slot axis zeroes its row.
        """
        def zero_row(leaf):
            if (isinstance(leaf, jax.Array) and leaf.ndim
                    and leaf.shape[0] == self.max_slots):
                return leaf.at[slot_id].set(jnp.zeros_like(leaf[slot_id]))
            return leaf
        self.state = jax.tree.map(
            zero_row, self.state,
            is_leaf=lambda x: isinstance(x, (kvcache.QuantizedKVLayer,
                                             kvcache.PagedKVLayer)))

    # -- chunked prefill (DESIGN.md §17) ----------------------------------
    def _run_chunks(self, n_decode: int) -> None:
        """Run this turn's budgeted prefill chunks (scheduler-planned).

        Decoder families carry fp K/V scratch across chunks (one donated
        dispatch per chunk, attention offset into the scratch); SSM/hybrid
        fall back to prefix recompute — the whole padded head re-prefills
        with ``lengths=[progress]`` each chunk and only the final (full-
        length) state is kept, trading quadratic total compute for the
        same bounded-stall interleaving.  Either way the live cache/state
        is only written at the final insert, with the SAME insert path and
        valid-length masking as an unchunked admission.
        """
        prefilling = [(i, len(s.req.prompt) - 1 - s.pos)
                      for i, s in enumerate(self.slots)
                      if not s.free and s.prefilling]
        plan = self._scheduler.plan(self._nsteps(), n_decode, prefilling)
        blk = self._kv_blk if self.paged else 0
        for slot_id, n in plan:
            s = self.slots[slot_id]
            req = s.req
            w = len(req.prompt) - 1
            p = s.pos
            with self._span("prefill_chunk", uid=req.uid, offset=p, n=n):
                if self._chunk_step is not None:
                    c = self.prefill_chunk
                    toks = np.zeros((1, c), np.int32)
                    toks[0, :n] = req.prompt[p:p + n]
                    self._scratch[slot_id] = self._chunk_step(
                        self.params, self._scratch[slot_id],
                        jnp.asarray(toks), jnp.asarray(p, jnp.int32))
                    st = self._scratch[slot_id]
                else:
                    # prefix recompute: lengths masks tokens past progress
                    # out of the recurrent-state update, so ONE compiled
                    # shape serves every chunk of this prompt
                    st = self._prefill(self.params,
                                       jnp.asarray(self._chunk_head[slot_id]),
                                       jnp.asarray([p + n], jnp.int32))
                jax.block_until_ready(st)
                s.pos = p + n
                self._count("prefill_tokens", n)
                self._count("prefill_chunks")
                lc = self.lifecycles.get(req.uid)
                if lc is not None:
                    lc.prefill_progress = s.pos
                if self.paged:
                    # map the blocks this chunk fully filled against the
                    # admission-time reservation; the partial block stays
                    # unmapped so the reservation ledger keeps matching
                    # _required_growth exactly (and the zero-beyond-write
                    # probe never reads a mapped-but-unwritten block)
                    for tb in range(p // blk, s.pos // blk):
                        self._host_tables[slot_id, tb] = \
                            self._grow_alloc(slot_id)
                if s.pos >= w:
                    self._finish_prefill(slot_id, st)

    def _finish_prefill(self, slot_id: int, st) -> None:
        """Final chunk landed: insert the carried state into the live cache
        and hand the slot to the decode dispatch (THIS turn — the caller
        recomputes the active set after the chunk phase, and the plan
        already charged this slot's first decode token)."""
        s = self.slots[slot_id]
        prompt = s.req.prompt
        w = len(prompt) - 1
        pad = self._chunk_head[slot_id].shape[1]
        if self.paged:
            blk = self._kv_blk
            for tb in range((w - 1) // blk + 1):
                if self._host_tables[slot_id, tb] < 0:
                    self._host_tables[slot_id, tb] = self._grow_alloc(slot_id)
            self._tables_dirty = True  # real row replaces the -1 mask
        with self._admit_span("kv_insert", 1, pad):
            lengths = jnp.asarray([w], jnp.int32)
            if self.paged:
                self._insert_rows_paged([(slot_id, prompt[:-1])], st,
                                        lengths, pad)
            else:
                self._insert_rows([slot_id], st, lengths)
        s.prefilling = False
        s.pos = w
        self._pending_token[slot_id] = prompt[-1]  # replayed next step
        self._scratch.pop(slot_id, None)
        self._chunk_head.pop(slot_id, None)
        lc = self.lifecycles.get(s.req.uid)
        if lc is not None:
            lc.transition(RequestState.DECODE, time.monotonic())

    # -- main loop -----------------------------------------------------------
    def run(self, requests: list[Request] = (), *,
            step_hook=None) -> dict[int, list[int]]:
        """Continuous-batching loop until every submitted request reaches a
        terminal lifecycle state.  Returns ``{uid: token stream}`` for every
        request that terminated during this call — partial streams for
        FAILED / CANCELLED / TIMED_OUT / never-admitted requests (consult
        ``engine.lifecycles[uid]`` for the terminal state and diagnostic).

        ``step_hook(engine, step)`` fires once per loop turn before
        admission; the chaos harness uses it for mid-run ``submit`` /
        ``cancel`` at deterministic steps.

        With the process-wide tracer enabled (``repro.obs.trace.enable()``)
        every turn additionally records a ``step`` span decomposed into the
        named phases of ``_turn``, admission's ``admit`` spans and each
        request's ``queued`` spans — see
        ``trace_report()`` and DESIGN.md §16.  Tracing never changes the
        dispatch or sampling math, so traced runs are token-identical to
        untraced runs.
        """
        t0 = time.perf_counter()
        for req in requests:
            self.submit(req)
        results: dict[int, list[int]] = {}
        self._pending_token = {}
        tokens_h = np.zeros((self.max_slots, 1), np.int32)
        pos_h = np.zeros((self.max_slots,), np.int32)
        step_hist = self.metrics.histogram("step_time_s")

        while self._queue or self._active():
            tr = self._tracer
            step_idx = self._nsteps()
            step_span = (tr.span("step", cat="step", track="engine",
                                 hist=self.metrics.histogram("traced_step_s"),
                                 args={"step": step_idx})
                         if tr.enabled else obs_trace.NOOP_SPAN)
            with step_span:
                self._count("loop_turns")
                # the turn timer covers the WHOLE turn — admission and
                # prefill work included, not just the decode dispatch — so
                # health medians and the phase spans agree on totals
                with StepTimer() as turn:
                    dispatch_dt = self._turn(results, tokens_h, pos_h,
                                             step_hook)
                step_hist.observe(turn.dt)
                with self._span("bookkeeping"):
                    if dispatch_dt is not None:
                        self._after_dispatch(step_idx, dispatch_dt)
        self.metrics.counter("wall_s").inc(time.perf_counter() - t0)
        return results

    def _active(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if not s.free]

    def _decode_active(self) -> list[int]:
        """Slots the decode dispatch steps this turn: active and NOT still
        mid-chunked-prefill (a prefill finishing this turn decodes from the
        NEXT turn, so the scheduler's per-turn token accounting is exact)."""
        return [i for i, s in enumerate(self.slots)
                if not s.free and not s.prefilling]

    def _turn(self, results: dict[int, list[int]], tokens_h, pos_h,
              step_hook) -> float | None:
        """One serve-loop turn, decomposed into the named step phases
        (DESIGN.md §16): hook -> reap -> admission -> prep -> dispatch ->
        device_sync -> commit.  Returns the dispatch+sync+transfer duration
        (the StragglerMonitor's latency signal), or None if no decode ran."""
        if step_hook is not None:
            with self._span("hook"):
                step_hook(self, self._nsteps())
        # cancellations + deadline/TTFT expiry, queued and resident alike
        with self._span("reap"):
            self._reap(time.monotonic(), results)
        with self._span("admission"):
            # fill free slots: one batched admission per loop turn, highest
            # priority first (stable sort: FIFO within a priority class)
            free = [i for i, s in enumerate(self.slots) if s.free]
            pressure = False
            if free and self._queue:
                self._queue.sort(key=lambda r: -r.priority)
                if self._fault("pool_exhaustion"):
                    # injected pool pressure: refuse the whole admission turn
                    # so the shed ladder reacts exactly as it would to a
                    # genuinely full pool
                    pressure = True
                else:
                    assignments = [(i, self._queue.pop(0))
                                   for i in free[: len(self._queue)]]
                    if self.batch_admission:
                        rejected = self._admit(assignments)
                    else:  # reference path: one padded prefill per request
                        rejected = []
                        for pair in assignments:
                            rejected += self._admit([pair])
                    # paged backpressure: requests the pool could not cover
                    # wait (shedding below) for completions to free blocks
                    self._queue[:0] = rejected
                    pressure = bool(rejected)
                    if rejected and not self._active():
                        # an idle pool that still rejects can never admit:
                        # shedding has nothing left to reclaim
                        raise RuntimeError(
                            f"request needs more KV blocks than the whole pool "
                            f"holds ({self.pool.num_blocks}); raise pool_blocks "
                            f"or the state_bytes budget")
            if pressure:
                # tiered degradation instead of indefinite backpressure:
                # shrink speculation headroom, then priority-gated preemption
                self._maybe_shed(self._queue)
            elif self._queue:
                # slot pressure (every slot busy, nothing rejected): a
                # strictly-higher-priority waiter may still preempt
                self._preempt_for(self._queue)
            else:
                self._relax_shed()
        act = self._decode_active()
        if self._scheduler is not None:
            # budgeted prefill chunks interleave with this turn's decode:
            # decode slots are charged first (they never wait on prefill),
            # chunks fill the remaining per-step token budget.  A slot whose
            # FINAL chunk lands joins this very turn's dispatch (its +1
            # decode charge is part of the chunk's planned cost): the insert
            # and the slot's entry into the lockstep step are atomic, so no
            # idle-slot write can ever land on freshly inserted rows.
            self._run_chunks(len(act))
            act = self._decode_active()
        if not act:
            if self._debug_invariants:
                self.check_invariants()  # pure-prefill turns sweep too
            return None
        if self.paged and self._fault("append_failure"):
            # the slot's paged append bookkeeping died: quarantine that
            # request alone; everyone else decodes this turn as usual
            victim = act[0]
            self._finalize(victim, self.slots[victim].req,
                           RequestState.FAILED, results,
                           diagnostic="paged append bookkeeping failure "
                                      "(injected fault)")
            act = self._decode_active()
            if not act:
                return None
        k_eff = self._burst_len(act) if self._k_live else 0
        with self._span("prep"):
            if self.paged:
                # map/CoW every block an active slot can write this step
                # (the whole K_eff+1 burst span under speculation)
                self._ensure_append_blocks(act, span=k_eff + 1)
            # one lock-step decode over all slots (idle slots step
            # harmlessly; paged idle slots append into the reserved trash
            # block)
            for i in act:
                s = self.slots[i]
                tokens_h[i, 0] = self._pending_token.get(
                    i, s.generated[-1] if s.generated else 0)
                pos_h[i] = s.pos
            # per-slot NaN needles (zeros in production: array args, so the
            # chaos harness injects without retracing the dispatch)
            inject = np.zeros((self.max_slots,), np.float32)
            if self._fault("nan_logit"):
                inject[act[0]] = np.float32("nan")
        step = self._nsteps()
        with StepTimer() as timer:
            if k_eff > 0:
                inj_draft = np.zeros((self.max_slots,), np.float32)
                if self._fault("nan_logit_draft"):
                    inj_draft[act[0]] = np.float32("nan")
                emitted, draft_bad, verify_bad = self._spec_step(
                    act, tokens_h, pos_h, k_eff, inj_draft, inject)
            else:
                with self._span("dispatch"):
                    toks_dev, self.state, self._key, bad_dev = self._decode(
                        self.params, self.state, jnp.asarray(tokens_h),
                        jnp.asarray(pos_h), self._key, jnp.asarray(inject),
                        self.temperature, self.top_k, self.top_p)
                with self._span("device_sync"):
                    jax.block_until_ready((toks_dev, bad_dev))
                toks = np.asarray(toks_dev)  # ONE (B,) int32 host transfer
                verify_bad = np.asarray(bad_dev)
                draft_bad = None
                emitted = {i: [int(toks[i])] for i in act}
        self._count("decode_steps")
        with self._span("commit"):
            self._commit(act, emitted, draft_bad, verify_bad, step, results)
        return timer.dt

    def _commit(self, act: list[int], emitted, draft_bad, verify_bad,
                step: int, results: dict[int, list[int]]) -> None:
        """Apply one dispatch round's tokens: quarantine poisoned slots,
        append accepted tokens (recording TTFT / inter-token gaps), finalize
        completed requests."""
        now = time.monotonic()
        ttft_hist = self.metrics.histogram("ttft_s")
        itl_hist = self.metrics.histogram("itl_s")
        for i in act:
            s = self.slots[i]
            self._pending_token.pop(i, None)
            if verify_bad[i]:
                # numerical quarantine: ONLY the poisoned request fails
                # (sampling already saw zeroed logits, so neighbours'
                # streams are untouched)
                self._count("nan_quarantined")
                self._finalize(i, s.req, RequestState.FAILED, results,
                               diagnostic=f"non-finite logits at decode "
                                          f"step {step}")
                continue
            if draft_bad is not None and draft_bad[i]:
                # poisoned draft, healthy verify: this round already fell
                # back to the non-speculative token for this slot
                self._count("nan_draft_fallbacks")
            lc = self.lifecycles.get(s.req.uid)
            first_of_turn = True
            for tok in emitted[i]:
                if lc is not None and lc.first_token_t is None:
                    lc.first_token_t = now
                    ttft_hist.observe(now - lc.enqueued_t)
                if s.last_token_t is not None:
                    # tokens of one speculative burst land together: only
                    # the first gap of the turn is a real inter-token wait
                    itl_hist.observe((now - s.last_token_t)
                                     if first_of_turn else 0.0)
                s.last_token_t = now
                first_of_turn = False
                s.generated.append(tok)
                s.pos += 1
                # streaming front-end: the commit IS the observable event
                # (TTFT above is the first COMMITTED token, not a prefill
                # chunk landing)
                self._token_events.append((s.req.uid, tok))
                cb = self._on_token.get(s.req.uid)
                if cb is not None:
                    cb(s.req.uid, tok)
                done = (tok == s.req.eos_id
                        or len(s.generated) >= s.req.max_new_tokens
                        or s.pos >= self.max_seq - 1)
                if done:
                    # a burst stops at its first terminal token: the rest
                    # of the accepted prefix is DROPPED, the slot (and
                    # its paged blocks) frees this very step
                    self._finalize(i, s.req, RequestState.DONE, results)
                    break

    def _after_dispatch(self, step: int, dt: float) -> None:
        """Post-dispatch bookkeeping: straggler latency signal -> shed one
        speculation tier (floor K=1: only real pool pressure turns
        speculation fully off), then the chaos harness's invariant sweep."""
        if (self._straggler.observe(step, dt)
                and self._shed_policy is not None
                and self._shed_policy.straggler_sheds_spec
                and self._k_live > 1
                and self._set_live_k(self._spec_ladder[self._shed_tier + 1])):
            self._shed_tier += 1
            self._shed_event("straggler_shed", dt=dt)
        if self._debug_invariants:
            self.check_invariants()

    # -- debug invariants (DESIGN.md §14) ---------------------------------
    def check_invariants(self) -> None:
        """Re-derive the engine's resource-accounting invariants from
        scratch and raise ``AssertionError`` on the first violation.  Runs
        after every loop turn under ``debug_invariants=True`` (the chaos
        harness) — O(slots x blocks) host work plus, for the zero-beyond-
        write probe, one device readback per active slot's write block.

        * refcount conservation: every usable block's pool refcount equals
          the number of host-table rows mapping it; allocated + free
          partitions the pool exactly (no leak, no double-free).
        * reservation accounting: the pool's reserved total is the sum of
          the per-slot ledgers, and each active slot's ledger equals its
          remaining growth requirement at the live burst K (an admitted
          request can always finish).
        * zero-beyond-write: in the block holding an active slot's last
          committed token, every position past the write offset holds zero
          levels — a freed block's previous occupant can never leak into a
          later request (kvcache/paged.py's contract).
        """
        if not self.paged:
            return
        pool = self.pool
        refs = np.zeros(pool.num_blocks + 1, np.int64)
        for i in range(self.max_slots):
            for bid in self._host_tables[i]:
                if bid >= 0:
                    refs[int(bid)] += 1
        for bid in range(1, pool.num_blocks + 1):
            if pool.refcount(bid) != refs[bid]:
                raise AssertionError(
                    f"block {bid}: pool refcount {pool.refcount(bid)} != "
                    f"{refs[bid]} host-table mappings (leak or double-free)")
        mapped = int((refs[1:] > 0).sum())
        if pool.allocated != mapped:
            raise AssertionError(
                f"pool accounts {pool.allocated} allocated blocks but the "
                f"tables map {mapped}")
        if pool.allocated + pool.free_count != pool.num_blocks:
            raise AssertionError(
                f"allocated {pool.allocated} + free {pool.free_count} != "
                f"pool size {pool.num_blocks}")
        ledger = sum(self._reserved.values())
        if pool.reserved != ledger:
            raise AssertionError(
                f"pool reserves {pool.reserved} blocks but per-slot ledgers "
                f"sum to {ledger}")
        blk = self._kv_blk
        for i, s in enumerate(self.slots):
            if s.free:
                if self._reserved.get(i, 0):
                    raise AssertionError(
                        f"free slot {i} still holds a growth reservation "
                        f"({self._reserved[i]} blocks)")
                continue
            need = self._required_growth(i, self._k_live)
            if self._reserved.get(i, 0) != need:
                raise AssertionError(
                    f"slot {i} (uid {s.req.uid}): reserved "
                    f"{self._reserved.get(i, 0)} blocks but needs {need} to "
                    f"finish at K={self._k_live}")
            off = s.pos % blk
            if s.pos == 0 or off == 0:
                continue  # last write filled its block exactly
            bid = int(self._host_tables[i, (s.pos - 1) // blk])
            if bid < 0 or self.pool.refcount(bid) > 1:
                continue  # shared blocks are a donor's bytes, not this slot's
            layer = next((l for l in self.state
                          if isinstance(l, kvcache.PagedKVLayer)), None)
            if layer is None:
                continue
            # one layer's device readback is probe enough per turn
            for side in (layer.k_packed, layer.v_packed):
                tail = np.asarray(side[bid, :, off:, :])
                if tail.any():
                    raise AssertionError(
                        f"slot {i} block {bid}: non-zero levels beyond "
                        f"write offset {off} (stale bytes would leak "
                        f"across free/realloc)")

    # -- stats -------------------------------------------------------------
    def stats(self) -> dict:
        """Counters plus a ``health`` section (latency + degradation state).

        This is a VIEW over ``self.metrics`` (the registry is the source of
        truth — see DESIGN.md §16), shaped exactly like the legacy ad-hoc
        stats dict so existing callers keep working.

        ``step_time_median_s`` is the median FULL loop turn (admission and
        prefill turns included, agreeing with ``wall_s`` and the traced
        phase spans); ``straggler_flagged`` still reflects the
        StragglerMonitor's dispatch-only latency signal; ``shed_tier`` /
        ``speculate_live_k`` show where on the degradation ladder the engine
        currently sits (0 / configured K = full service).
        """
        out = {k: int(self.metrics.counter(k).value) for k in _COUNTER_KEYS}
        out["wall_s"] = self.metrics.counter("wall_s").value
        out["shed_events"] = [dict(e) for e in self._shed_events]
        step_hist = self.metrics.histogram("step_time_s")
        out["health"] = {
            "step_time_median_s": (step_hist.percentile(50)
                                   if step_hist.count else 0.0),
            "dispatch_time_median_s": self._straggler.median(),
            "straggler_flagged": len(self._straggler.flagged),
            "shed_tier": self._shed_tier,
            "speculate_live_k": self._k_live,
            "queue_depth": len(self._queue),
            "active_slots": sum(not s.free for s in self.slots),
            "prefilling_slots": sum(s.prefilling for s in self.slots),
            "pool_available": self.pool.available if self.paged else None,
        }
        if self._scheduler is not None:
            recs = self._scheduler.records
            out["scheduler"] = {
                "prefill_chunk": self.prefill_chunk,
                "step_token_budget": self._scheduler.cfg.step_token_budget,
                "planned_turns": len(recs),
                "chunk_tokens": sum(r.chunk_tokens for r in recs),
                "max_step_tokens": max(
                    (r.decode_tokens + r.chunk_tokens + r.finish_tokens
                     for r in recs), default=0),
            }
        for name in ("ttft_s", "itl_s"):
            hist = self.metrics.histogram(name)
            if hist.count:
                out.setdefault("latency", {})[name] = hist.summary()
        if self.artifact is not None and self.artifact.report:
            cal = obs_calibration.calibration_ratios(self.artifact.report,
                                                     self.measured_costs())
            if cal:
                out["calibration"] = cal
        return out

    def trace_report(self) -> dict:
        """Decompose traced decode-step wall time into the named phases.

        Uses the ``phase/*`` histograms populated while the process-wide
        tracer is enabled (each phase span feeds its histogram on exit) and
        the ``traced_step_s`` parent-span histogram as the denominator.
        ``attributed_fraction`` is the share of total step wall time covered
        by named phases — the acceptance bar is >= 0.90 (the remainder is
        loop glue between spans).
        """
        total_hist = self.metrics.histogram("traced_step_s")
        total = total_hist.sum
        phases = {}
        attributed = 0.0
        for name in _PHASE_NAMES:
            h = self.metrics.get("phase/" + name)
            if h is None or h.count == 0:
                continue
            phases[name] = {
                "total_s": h.sum,
                "count": h.count,
                "mean_us": h.mean * 1e6,
                "p99_us": h.percentile(99) * 1e6,
                "fraction_of_step": (h.sum / total) if total else 0.0,
            }
            attributed += h.sum
        report = {
            "steps": total_hist.count,
            "total_s": total,
            "phases": dict(sorted(phases.items(),
                                  key=lambda kv: -kv[1]["total_s"])),
            "attributed_s": attributed,
            "attributed_fraction": (attributed / total) if total else 0.0,
            "unattributed_fraction": (1.0 - attributed / total) if total else 0.0,
        }
        if total_hist.count == 0:
            report["note"] = ("no traced steps recorded — enable the tracer "
                              "(repro.obs.trace.enable()) before run()")
        return report

    def weight_container_bytes(self) -> int:
        """HBM bytes the packed weights occupy (quantized leaves only)."""
        return sum(leaf.container_bytes() for leaf in jax.tree.leaves(
            self.params, is_leaf=lambda x: hasattr(x, "container_bytes"))
            if hasattr(leaf, "container_bytes"))

    def measured_costs(self) -> dict:
        """Deployment-side measurements of the artifact's predicted metrics.

        The cost-model calibration input (DESIGN.md §18): ``container_bytes``
        from the packed param tree, ``state_bytes`` from the cache
        accountants (only when the state is actually quantized — an fp cache
        measures a different thing than the search priced), ``latency_s``
        as the mean traced compute time per decode step (dispatch +
        device_sync — the part a roofline predicts; loop glue excluded)
        when traced steps exist.
        """
        out = {"container_bytes": float(self.weight_container_bytes())}
        if self._quant_state:
            out["state_bytes"] = float(self.state_container_bytes())
        disp = self.metrics.get("phase/dispatch")
        sync = self.metrics.get("phase/device_sync")
        if disp is not None and disp.count:
            lat = disp.mean + (sync.mean if sync is not None and sync.count
                               else 0.0)
            out["latency_s"] = float(lat)
        return out

    # -- state accounting ----------------------------------------------------
    def state_container_bytes(self) -> int:
        """HBM bytes the decode state occupies (dense containers / whole pool)."""
        total = 0
        for leaf in jax.tree.leaves(
                self.state,
                is_leaf=lambda x: hasattr(x, "container_bytes")):
            if hasattr(leaf, "container_bytes"):
                total += leaf.container_bytes()
            else:
                total += leaf.size * leaf.dtype.itemsize
        return total

    def allocated_state_bytes(self, *, peak: bool = True) -> int:
        """Paged: bytes of live (peak by default) blocks — what the
        ``state_bytes`` budget prices.  Dense: the full container (every
        slot pre-pays ``max_seq``, which is the point of going paged)."""
        if not self.paged:
            return self.state_container_bytes()
        n = self.pool.peak_allocated if peak else self.pool.allocated
        return sum(layer.allocated_bytes(n) for layer in self.state)

    # -- convenience ---------------------------------------------------------
    def generate(self, prompts: list[list[int]], max_new_tokens: int = 16) -> list[list[int]]:
        reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new_tokens)
                for i, p in enumerate(prompts)]
        out = self.run(reqs)
        return [out[i] for i in range(len(prompts))]
