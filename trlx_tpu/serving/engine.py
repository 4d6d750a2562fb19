"""Persistent continuous-batching generation engine.

One :class:`ServingEngine` owns a model trunk, a set of device block pools
(``trunk.init_paged_cache``), a host-side :class:`PagedBlockAllocator`, and an
:class:`InflightScheduler`. The hot loop is two compiled programs:

- **bucketed prefill** — each admission wave runs the trunk's ordinary
  left-padded contiguous prefill at a bucketed ``(batch, prompt_len)`` shape,
  then a jitted scatter packs the resulting K/V rows into the pools through
  each sequence's block table. Buckets keep the compile count O(log) in both
  dimensions.
- **steady-state decode step** — a single fixed-shape jitted step over all
  ``num_slots`` slots: ``TransformerLM.paged_decode`` (paged write + paged
  attention per layer) followed by the shared sampling pipeline. The step
  never recompiles; slot membership changes purely through the block-table /
  context-length inputs.

The step always runs full-batch; idle slots run against the reserved null
block and their outputs are discarded. The scheduler refills a slot the step
after its sequence finishes, which is the whole point: delivered tokens/sec
tracks *live* sequences, not the longest straggler in a padded batch.

Two optional multi-token modes attack the decode bandwidth bound (each round
reads all params + live KV; emitting one token per slot per read is the
ceiling):

- **speculative decoding** (``spec_k > 0``) — a host-side prompt-lookup
  n-gram draft proposes up to K tokens per slot; one fixed-shape verify step
  (``TransformerLM.paged_verify``) scores pending + K drafts at once, samples
  every position on device, and accepts the longest leading run of drafts
  that match what the model would have emitted. Greedy output is
  bit-identical to the non-speculative path (the accept rule only ever keeps
  tokens the plain decode would have produced; rejected-draft KV sits beyond
  ``context_lens`` and is rewritten before it can become valid). ``spec_k=0``
  keeps the original single-token program byte-identical.
- **chunked prefill** (``prefill_chunk > 0``) — prompts longer than the
  chunk run their first chunk through the ordinary bucketed prefill and the
  rest through per-round batch-1 ``paged_verify`` appends interleaved with
  decode rounds, so a long admission no longer stalls every live slot for a
  full-prompt forward. Mid-prefill slots are masked out of the decode batch
  (null table row, len 0) until their last chunk samples the first token.

Sampling runs inside the jitted decode/verify/chunk steps — the only values
that cross back per round are sampled tokens and accept counts, never
logits.

Sampling consumes one rng fold per engine event (prefill wave or decode
step), so sampled streams are reproducible for a fixed seed + submission
order but do not bit-match ``ops/generation.generate`` (which folds per
step over a different batch shape). Greedy decoding matches exactly — the
default-path parity test relies on that.

Thread-safety: ``submit``/``cancel`` may be called from producer threads;
``step``/``run`` must be driven by one thread at a time (the engine guards
this with a lock — rollout producers call through
:class:`trlx_tpu.serving.client.GenerationClient`, which serializes).
"""

import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from trlx_tpu.obs import compile_log
from trlx_tpu.obs.flight import flight
from trlx_tpu.ops.kv_cache import unfold_heads
from trlx_tpu.ops.generation import LENGTH_BUCKETS, left_pad_batch, pad_to_bucket
from trlx_tpu.ops.paged_attention import paged_slots, scatter_paged_rows
from trlx_tpu.ops.sampling import count_accepted_drafts, sample_token
from trlx_tpu.resilience.chaos import chaos
from trlx_tpu.serving.allocator import PagedBlockAllocator
from trlx_tpu.serving.policy import (
    EngineDrainingError,
    EngineWedgedError,
    RequestTooLarge,
    ServingResiliencePolicy,
)
from trlx_tpu.serving.scheduler import InflightScheduler, Request
from trlx_tpu.serving.tenancy import TenantRegistry, select_victim
from trlx_tpu.utils import logging
from trlx_tpu.utils.metrics import gauges, nearest_rank

logger = logging.get_logger(__name__)


def _pow2_at_least(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def _ngram_propose(
    ctx: np.ndarray, k: int, max_order: int, pad_token: int
) -> np.ndarray:
    """Prompt-lookup drafting: propose ``k`` tokens by matching the longest
    suffix n-gram (order ``max_order`` down to 1) earlier in the context and
    continuing from the LATEST such match (recent repetition predicts the
    near future better than distant repetition). Pure host numpy — the draft
    must be cheaper than the verify pass by orders of magnitude or the whole
    scheme loses. No match (or a match flush against the end) pads with
    ``pad_token``: a wrong draft costs nothing beyond the verify FLOPs the
    fixed-shape step was paying anyway.
    """
    out = np.full((k,), pad_token, np.int32)
    L = len(ctx)
    if L < 2:
        return out
    for n in range(min(max_order, L - 1), 0, -1):
        tail = ctx[L - n:]
        # windows of length n starting at j cover ctx[j : j+n]; exclude the
        # suffix itself (j = L - n) so the continuation is a real lookbehind
        n_cand = L - n
        m = np.ones((n_cand,), bool)
        for j in range(n):
            m &= ctx[j : j + n_cand] == tail[j]
        hits = np.nonzero(m)[0]
        if len(hits) == 0:
            continue
        start = int(hits[-1]) + n  # continuation of the latest match
        take = ctx[start : start + k]
        out[: len(take)] = take
        return out
    return out


@dataclass
class ServingStats:
    # true decode-round emission count: every token handed to the scheduler
    # by a decode round — 1/slot plain, 1..K+1/slot speculative (prefill's
    # first sampled token is prefill accounting, as before)
    delivered_tokens: int = 0
    prefill_tokens: int = 0
    decode_steps: int = 0
    prefill_waves: int = 0
    finished_requests: int = 0
    # sum over decode rounds of live-slot count — the denominator for
    # accepted-tokens-per-round (= exactly 1.0 when spec is off)
    decode_slot_rounds: int = 0
    spec_rounds: int = 0
    spec_draft_tokens: int = 0
    spec_accepted_tokens: int = 0
    chunk_appends: int = 0
    # stream-overlapped PPO (docs/serving.md "Stream-overlapped PPO"): the
    # trainer reports each streaming window's decode-busy seconds and the
    # reward/score/learn-stage seconds that genuinely overlapped them
    overlap_decode_s: float = 0.0
    overlap_overlapped_s: float = 0.0
    overlap_windows: int = 0


class ServingEngine:
    def __init__(
        self,
        trunk,
        params,
        *,
        num_slots: int,
        max_seq_len: int,
        block_size: int = 16,
        num_blocks: int = 0,
        eos_token_id: Optional[int] = None,
        pad_token_id: int = 0,
        gen_kwargs: Optional[Dict[str, Any]] = None,
        min_new_tokens: int = 0,
        prefix_caching: bool = True,
        seed: int = 0,
        policy: Optional[ServingResiliencePolicy] = None,
        spec_k: int = 0,
        spec_ngram: int = 3,
        prefill_chunk: int = 0,
        tenants: Optional[TenantRegistry] = None,
        gauge_prefix: str = "serving/",
        replica_id: Optional[int] = None,
        state_sharding=None,
    ):
        """``trunk`` is a built ``TransformerLM`` (its config decides the KV
        dtype via ``kv_cache_quant`` and the kernel via
        ``paged_attention_impl``); ``params`` its parameter subtree.

        ``spec_k`` > 0 enables speculative decoding (K n-gram draft tokens
        verified per round; 0 = the original single-token step, byte-
        identical). ``spec_ngram`` caps the draft-match n-gram order.
        ``prefill_chunk`` > 0 splits admissions longer than the chunk into
        per-round ``paged_verify`` appends interleaved with decode (0 =
        whole-prompt bucketed prefill).

        ``gauge_prefix`` namespaces every gauge this engine writes (and the
        prefix ``close()`` clears). The default keeps the historical global
        ``serving/*`` keys; the fleet router gives each replica
        ``serving/replica/<i>/`` so N live engines stop clobbering each
        other. ``replica_id`` tags typed errors with the raising replica
        (None outside a fleet).

        ``state_sharding`` commits the engine's own device state (KV pools,
        tables, rng) to a sharding. A caller whose params are committed — a
        trainer's are, to its mesh — passes that mesh's replicated sharding:
        what a jitted step returns beside committed params is committed, so
        state that began uncommitted would make every program compile a
        second time for its own outputs. None leaves it uncommitted, which is
        stable beside uncommitted params."""
        c = trunk.config
        if c.stacked:
            raise NotImplementedError("serving engine: per-layer list layout only")
        if c.peft_type in ("prompt", "prefix"):
            raise NotImplementedError("serving engine does not support peft prompt/prefix")
        if c.pos_embedding == "alibi":
            raise NotImplementedError("serving engine does not support alibi")
        self.trunk = trunk
        self.params = params
        self.num_slots = int(num_slots)
        self.block_size = int(block_size)
        self.max_seq_len = int(max_seq_len)
        self.max_blocks_per_seq = -(-self.max_seq_len // self.block_size)
        if num_blocks <= 0:
            # full reservation for every slot, +1 for the reserved null block
            num_blocks = self.num_slots * self.max_blocks_per_seq + 1
        self.num_blocks = int(num_blocks)
        self.eos_token_id = eos_token_id
        self.pad_token_id = int(pad_token_id)
        self.gen_kwargs = dict(gen_kwargs or {})
        self.min_new_tokens = int(min_new_tokens)
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        self.prefill_chunk = int(prefill_chunk)
        self.gauge_prefix = str(gauge_prefix)
        self.replica_id = None if replica_id is None else int(replica_id)
        if self.spec_k < 0 or self.spec_ngram < 1 or self.prefill_chunk < 0:
            raise ValueError(
                f"spec_k={spec_k} must be >= 0, spec_ngram={spec_ngram} >= 1, "
                f"prefill_chunk={prefill_chunk} >= 0"
            )
        # seeded CI regression hook: "accept_all" forces the verify step to
        # claim every draft accepted, which must break the greedy spec/non-spec
        # parity gate (scripts/ci.sh proves the gate bites by requiring the
        # parity test to FAIL under this env)
        seed_reg = os.environ.get("TRLX_SPEC_SEED_REGRESSION", "")
        if seed_reg not in ("", "accept_all"):
            raise ValueError(
                f"TRLX_SPEC_SEED_REGRESSION={seed_reg!r}: only 'accept_all' is defined"
            )
        self._spec_seed_regression = seed_reg

        self.allocator = PagedBlockAllocator(
            self.num_blocks, self.block_size, prefix_caching=prefix_caching
        )
        # fault-tolerance policy (docs/serving.md "Fault tolerance");
        # None keeps every policy pass a no-op, byte-identical to the
        # pre-resilience engine
        self.policy = policy
        # tenancy registry (docs/serving.md "Multi-tenancy and SLO classes");
        # None keeps admission/shedding/preemption tenant-blind, byte-
        # identical to the single-tenant engine
        self.tenants = tenants
        self.scheduler = InflightScheduler(
            self.num_slots, self.allocator, policy=policy, tenants=tenants
        )
        # per-tenant / per-class latency windows for the p99 gauges (bounded:
        # the gauges are operational, not an unbounded history). Written only
        # inside step() under the engine lock; export_gauges snapshots via
        # summary()'s lock.
        self._tenant_latency: Dict[str, deque] = {}
        self._class_latency: Dict[int, deque] = {}
        self.stats = ServingStats()
        self._lock = threading.Lock()
        # graceful shutdown + wedge recovery: drain() flips _draining so
        # submit() rejects; request_abort() unsticks a wedged step loop.
        # Both are Events, not flags: submit() and request_abort() run on
        # client/watchdog threads and must never contend for the engine lock
        # (held for a whole round — or indefinitely by a wedged one)
        self._draining = threading.Event()
        self._abort_evt = threading.Event()
        # generation-island glue (attach_island): round-boundary version
        # swaps + idle-bubble ledger. None keeps step() byte-identical to
        # the single-island engine.
        self._island = None
        self._island_version = -1

        # device state
        self._state_sharding = state_sharding
        self.cache = self._own(trunk.init_paged_cache(
            self.num_blocks, self.block_size, self.max_blocks_per_seq, self.num_slots
        ))
        self._rng = self._own(jax.random.PRNGKey(seed))
        # host mirrors of the table/length leaves; pushed when dirty
        self._tables = np.zeros((self.num_slots, self.max_blocks_per_seq), np.int32)
        self._lens = np.zeros((self.num_slots,), np.int32)
        self._tables_dirty = True
        # the next input token per slot (sampled last round, not yet written)
        self._pending_tok = np.zeros((self.num_slots,), np.int32)
        # slots mid chunked-prefill: masked out of the decode batch (their
        # device table row/len are zeroed) until the final chunk lands
        self._prefilling = np.zeros((self.num_slots,), bool)

        donate = (2,) if jax.default_backend() == "tpu" else ()
        self._decode_step = jax.jit(self._decode_step_impl, donate_argnums=donate)
        self._verify_step = jax.jit(self._verify_step_impl, donate_argnums=donate)
        self._chunk_step = jax.jit(self._chunk_step_impl, donate_argnums=donate)
        self._prefill = jax.jit(self._prefill_impl)
        pack_donate = (0,) if jax.default_backend() == "tpu" else ()
        self._pack = jax.jit(self._pack_impl, donate_argnums=pack_donate)

    def _own(self, tree):
        """``tree`` on the device as engine-owned state lives there: committed
        to ``state_sharding``, or uncommitted on the default device."""
        return jax.device_put(tree, self._state_sharding)

    # -- compiled programs ---------------------------------------------------

    def _sample(self, rng, logits, new_counts):
        rng, sub = jax.random.split(rng)
        if self.eos_token_id is not None and self.min_new_tokens > 0:
            eos_col = jnp.arange(logits.shape[-1]) == self.eos_token_id
            logits = jnp.where(
                (new_counts[:, None] < self.min_new_tokens) & eos_col[None, :],
                -1e9, logits,
            )
        tok = sample_token(sub, logits, **self.gen_kwargs)
        return rng, tok

    def _decode_step_impl(self, params, tok, cache, rng, new_counts):
        logits, _, new_cache = self.trunk.apply(
            {"params": params}, tok[:, None], cache, method=self.trunk.paged_decode
        )
        rng, next_tok = self._sample(rng, logits[:, -1, :], new_counts)
        return next_tok, new_cache, rng

    def _sample_positions(self, rng, logits, counts):
        """Per-position sampling for the verify step: ``logits`` [S, Q, V],
        ``counts`` [S, Q] = each position's generated-token index (drives the
        min_new_tokens eos mask exactly as :meth:`_sample` does per step —
        position j of a verify round IS generated token ``len(generated)+j``
        of the sequential decode it replays)."""
        rng, sub = jax.random.split(rng)
        if self.eos_token_id is not None and self.min_new_tokens > 0:
            eos_col = jnp.arange(logits.shape[-1]) == self.eos_token_id
            logits = jnp.where(
                (counts[..., None] < self.min_new_tokens) & eos_col[None, None, :],
                -1e9, logits,
            )
        tok = sample_token(sub, logits, **self.gen_kwargs)
        return rng, tok

    def _verify_step_impl(self, params, tok, cache, rng, new_counts):
        """Speculative verify round: ``tok`` [S, K+1] = pending token + K
        n-gram drafts per slot. One widened paged forward scores every
        position, per-position sampling and the leading-match accept count
        stay on device, and ``context_lens`` advances by ``accepted + 1`` —
        rejected-draft KV past the new frontier stays invisible to the
        attention mask and is rewritten before it can ever become valid, so
        rollback is free. Only [S, K+1] tokens + [S] counts cross back to the
        host (no logits round-trip)."""
        lens0 = cache["context_lens"]
        logits, _, new_cache = self.trunk.apply(
            {"params": params}, tok, cache, method=self.trunk.paged_verify
        )
        counts = (
            new_counts[:, None]
            + jnp.arange(tok.shape[1], dtype=jnp.int32)[None, :]
        )
        rng, y = self._sample_positions(rng, logits, counts)
        accepted = count_accepted_drafts(y, tok)
        if self._spec_seed_regression == "accept_all":
            accepted = jnp.full_like(accepted, tok.shape[1] - 1)
        new_cache["context_lens"] = lens0 + accepted + 1
        return y, accepted, new_cache, rng

    def _chunk_step_impl(self, params, ids, cache, rng, last_idx, new_counts):
        """One chunked-prefill append: ``ids`` [n, C] (pad-filled on the final
        partial chunk) writes all C positions' KV through the slot's table via
        ``paged_verify`` and samples a next token from the logit at
        ``last_idx`` — only the final chunk's sample is consumed (earlier
        chunks' logits condition on an incomplete prompt). ``context_lens``
        is not advanced on device; the host mirror owns the prefilled
        frontier. Pad positions write garbage KV beyond the prompt, which the
        first decode/verify round overwrites before the mask can expose it."""
        logits, _, new_cache = self.trunk.apply(
            {"params": params}, ids, cache, method=self.trunk.paged_verify
        )
        last = jnp.take_along_axis(logits, last_idx[:, None, None], axis=1)[:, 0, :]
        rng, tok = self._sample(rng, last, new_counts)
        pools = {
            k: v for k, v in new_cache.items()
            if k not in ("block_tables", "context_lens")
        }
        return tok, pools, rng

    def _prefill_impl(self, params, ids, mask, rng, new_counts=None):
        # ``new_counts=None`` (fresh prompts) keeps the compiled graph
        # byte-identical to the pre-resilience engine — the zeros fold into
        # the trace as constants. A wave holding a re-prefilled (preempted or
        # replayed) request passes its generated-so-far counts as a traced
        # array so the min_new_tokens eos mask stays consistent across a
        # re-admission; that compiles a second program, paid only when
        # preemption/replay actually happens.
        B, P = ids.shape
        cache = self.trunk.init_cache(B, P)
        cache = {**cache, "index": 0}  # static prefill-from-zero marker
        positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None).astype(jnp.int32)
        logits, _, _, cache = self.trunk.apply(
            {"params": params}, ids, mask, positions, cache
        )
        if new_counts is None:
            new_counts = jnp.zeros((B,), jnp.int32)
        rng, tok = self._sample(rng, logits[:, -1, :], new_counts)
        return tok, cache, rng

    def _pack_impl(self, pools, cont, rows, lens):
        """Scatter a contiguous left-padded prefill cache into the block
        pools. ``pools``: the pool leaves of ``self.cache`` (per-layer lists);
        ``cont``: the prefill cache (k/v [n,Hkv,P,D], scales [n,Hkv,P,1]);
        ``rows`` [n, MB] block-table rows; ``lens`` [n] prompt lengths.
        Rewriting a shared prefix block stores the identical values it
        already holds (same tokens, same params) — benign by construction."""
        P = cont["k"][0].shape[2]
        n = rows.shape[0]
        s = jnp.arange(P)[None, :]  # source slot in the left-padded cache
        pos = s - (P - lens[:, None])  # logical token position, <0 on padding: dropped
        block, offset = paged_slots(rows, pos, self.num_blocks, self.block_size)

        out = {}
        for key in pools:
            cl = cont[key]
            if key.endswith("_scale"):
                cl = [x[..., 0] for x in cl]  # [n,Hkv,P,1] -> [n,Hkv,P]
            # cont [n, Hkv, P, ...] (kv heads beside the rows where the prefill's cache is folded)
            # -> rows [n, P, Hkv, ...]
            out[key] = [
                scatter_paged_rows(p, block, offset, jnp.moveaxis(unfold_heads(c, c.shape[0] // n), 2, 1))
                for p, c in zip(pools[key], cl)
            ]
        return out

    # -- host loop -----------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        stop_sequences: Sequence[Sequence[int]] = (),
        deadline_s: Optional[float] = None,
        tenant_id: Optional[str] = None,
    ) -> int:
        spec = self.tenants.resolve(tenant_id) if self.tenants is not None else None
        if self._draining.is_set():
            raise EngineDrainingError(
                "engine is draining: new requests are rejected (graceful shutdown)",
                tenant_id=spec.tenant_id if spec else None,
                slo_class=spec.slo_class if spec else None,
                replica_id=self.replica_id,
            )
        if len(prompt) + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new_tokens} exceeds "
                f"engine max_seq_len {self.max_seq_len}"
            )
        # blocks_needed is pure arithmetic on the immutable block size — no
        # allocator state is read, so no lock is needed on this thread
        worst = self.allocator.blocks_needed(len(prompt) + max_new_tokens)  # graftcheck: noqa[CC001]
        if worst > self.num_blocks - 1:
            # would pend forever under worst-case admission (and could still
            # exhaust a lone pool under optimistic admission): reject loudly
            raise RequestTooLarge(
                f"request needs {worst} KV blocks worst-case but the pool "
                f"holds {self.num_blocks - 1}: it can never be admitted",
                tenant_id=spec.tenant_id if spec else None,
                slo_class=spec.slo_class if spec else None,
                replica_id=self.replica_id,
            )
        if spec is not None and spec.kv_block_quota and worst > spec.kv_block_quota:
            # same never-admittable logic against the tenant's own cap — and
            # the guarantee the in-flight quota enforcement leans on: any
            # single admitted sequence always fits its tenant's quota alone
            raise RequestTooLarge(
                f"request needs {worst} KV blocks worst-case but tenant "
                f"{spec.tenant_id!r} is capped at {spec.kv_block_quota}: it "
                f"can never be admitted",
                tenant_id=spec.tenant_id,
                slo_class=spec.slo_class,
                replica_id=self.replica_id,
            )
        return self.scheduler.submit(
            prompt, max_new_tokens, eos_token_id=self.eos_token_id,
            stop_sequences=stop_sequences, deadline_s=deadline_s,
            tenant_id=tenant_id,
        )

    def cancel(self, uid: int) -> bool:
        return self.scheduler.cancel(uid)

    def set_params(self, params) -> None:
        """Swap the parameter snapshot. Cached prefix K/V was computed under
        the old weights, so the prefix cache must flush — sharing across
        versions would silently mix policies."""
        with self._lock:
            self.params = params
            self.allocator.flush_prefix_cache()

    def _free_slot_state(self, slot: int) -> None:
        self._tables[slot] = 0
        self._lens[slot] = 0
        self._pending_tok[slot] = self.pad_token_id
        self._prefilling[slot] = False
        self._tables_dirty = True

    def _admit(self) -> List[Request]:
        """One admission round: reap cancels, place pending requests, run
        bucketed prefills, pack pools, sample each new sequence's first
        token. Returns requests that finished *during admission* (a first
        token can already be eos)."""
        finished: List[Request] = []
        for slot in self.scheduler.reap_cancelled():
            self._free_slot_state(slot)
        # admission-side policy pass: expire + shed pending before placement
        # (terminated requests never held device state, so nothing to free)
        finished.extend(self.scheduler.expire_and_shed_pending())
        placements = self.scheduler.admissions()
        if not placements:
            return finished
        # placed requests hold slots + blocks now; a crash here is the
        # supervisor's replay case (live requests re-queued onto a new engine)
        chaos.fail_if_armed("serving-prefill", f"{len(placements)} placements")
        # group by bucketed prefill length so one wave compiles per bucket
        # pair; prefill covers prompt + generated-so-far (re-admissions).
        # Chunked mode prefills only the FIRST chunk here (through the same
        # compiled wave program) and marks the slot mid-prefill; the rest
        # arrives via _advance_prefill_chunks interleaved with decode rounds.
        by_bucket: Dict[int, List[Tuple[int, Request, List[int]]]] = {}
        for slot, req in placements:
            ids_full = req.prefill_ids
            if 0 < self.prefill_chunk < len(ids_full):
                self._prefilling[slot] = True
                req.prefilled = 0
                first = ids_full[: self.prefill_chunk]
            else:
                first = ids_full
            by_bucket.setdefault(
                pad_to_bucket(len(first), LENGTH_BUCKETS), []
            ).append((slot, req, first))
        for P_b, group in sorted(by_bucket.items()):
            n_b = _pow2_at_least(len(group), self.num_slots)
            ids_list = [np.asarray(first, np.int32) for _, _, first in group]
            ids, mask = left_pad_batch(ids_list, self.pad_token_id, P_b)
            if n_b > len(group):  # pad the wave to its batch bucket
                ids = np.concatenate(
                    [ids, np.full((n_b - len(group), P_b), self.pad_token_id, np.int32)]
                )
                mask = np.concatenate(
                    [mask, np.zeros((n_b - len(group), P_b), mask.dtype)]
                )
                # all-pad rows still need one "valid" token: an all-masked
                # attention row is a softmax over -1e9 everywhere (finite,
                # uniform) but a zero-length cumsum position underflows the
                # learned table on some configs; give them token 0 @ pos 0
                mask[len(group):, -1] = 1
            counts = np.zeros((n_b,), np.int32)
            for i, (_, req, _) in enumerate(group):
                counts[i] = len(req.generated)
            with compile_log.attributed("serving_prefill"):
                tok, cont, self._rng = self._prefill(
                    self.params,  # graftcheck: noqa[TH001] — under step()'s lock
                    jnp.asarray(ids), jnp.asarray(mask), self._rng,
                    jnp.asarray(counts) if counts.any() else None,
                )
            rows = np.zeros((n_b, self.max_blocks_per_seq), np.int32)
            lens = np.zeros((n_b,), np.int32)
            for i, (slot, req, first) in enumerate(group):
                blocks = req.seq_blocks.blocks
                rows[i, : len(blocks)] = blocks
                lens[i] = len(first)
            pools = {
                k: v for k, v in self.cache.items()
                if k not in ("block_tables", "context_lens")
            }
            cont_pools = {k: cont[k] for k in pools}
            with compile_log.attributed("serving_pack_step"):
                packed = self._pack(pools, cont_pools, jnp.asarray(rows), jnp.asarray(lens))
            self.cache.update(packed)
            tok_np = np.asarray(jax.device_get(tok))
            self.stats.prefill_waves += 1
            self.stats.prefill_tokens += int(sum(len(first) for _, _, first in group))
            for i, (slot, req, first) in enumerate(group):
                self._tables[slot] = rows[i]
                self._lens[slot] = len(first)
                self._tables_dirty = True
                if self._prefilling[slot]:
                    # prompt incomplete: the wave's sampled token conditioned
                    # on a truncated prompt and is discarded; the final chunk
                    # samples the real first token
                    req.prefilled = len(first)
                    continue
                self._pending_tok[slot] = tok_np[i]
                done = self.scheduler.on_token(slot, int(tok_np[i]))
                if done is not None:
                    finished.append(done)
                    self._free_slot_state(slot)
        return finished

    def _advance_prefill_chunks(self) -> List[Request]:
        """Advance every mid-prefill slot by one chunk (batch-1
        ``paged_verify`` appends into the shared pools through the slot's own
        table row), interleaved with decode rounds so a long admission stops
        stalling live slots. The final chunk samples the sequence's first
        token on device — after it lands, the slot's state is IDENTICAL to a
        whole-prompt prefill (lens = prompt length, pending = first sampled
        token), which is what keeps chunked output bit-equal to unchunked."""
        finished: List[Request] = []
        if not self._prefilling.any():
            return finished
        C = self.prefill_chunk
        pool_keys = [
            k for k in self.cache if k not in ("block_tables", "context_lens")
        ]
        for slot in np.nonzero(self._prefilling)[0]:
            slot = int(slot)
            req = self.scheduler.slots[slot]
            if req is None:  # freed (cancel/expiry/preempt) mid-prefill
                self._prefilling[slot] = False
                continue
            ids_full = req.prefill_ids
            start = req.prefilled
            chunk = ids_full[start : start + C]
            n_v = len(chunk)
            ids = np.full((1, C), self.pad_token_id, np.int32)
            ids[0, :n_v] = chunk
            row = np.zeros((1, self.max_blocks_per_seq), np.int32)
            blocks = req.seq_blocks.blocks
            row[0, : len(blocks)] = blocks
            cache1 = {key: self.cache[key] for key in pool_keys}
            cache1["block_tables"] = jnp.asarray(row)
            cache1["context_lens"] = jnp.asarray(np.array([start], np.int32))
            with compile_log.attributed("serving_chunk_step"):
                tok, pools, self._rng = self._chunk_step(
                    self.params,  # graftcheck: noqa[TH001] — under step()'s lock
                    jnp.asarray(ids), cache1, self._rng,
                    jnp.asarray(np.array([n_v - 1], np.int32)),
                    jnp.asarray(np.array([len(req.generated)], np.int32)),
                )
            self.cache.update(pools)
            req.prefilled = start + n_v
            self._lens[slot] = req.prefilled
            self.stats.prefill_tokens += n_v
            self.stats.chunk_appends += 1
            if flight.enabled:
                flight.record(
                    req.uid, "prefill_chunk", t=self.scheduler.clock(),
                )
            if req.prefilled >= len(ids_full):
                # prompt complete: unmask the slot into the decode batch
                self._prefilling[slot] = False
                self._tables_dirty = True
                tok_i = int(np.asarray(jax.device_get(tok))[0])
                self._pending_tok[slot] = tok_i
                done = self.scheduler.on_token(slot, tok_i)
                if done is not None:
                    finished.append(done)
                    self._free_slot_state(slot)
        return finished

    def _tenant_shares(self) -> Dict[str, int]:
        """Per-tenant block share for fair-share preemption: a tenant's hard
        quota when it has one, else an equal split of the pool across the
        tenants currently holding blocks. Exceeding the share does not fail
        anything by itself — it just makes the tenant the preferred
        preemption victim under KV pressure."""
        census = self.allocator.owner_census()
        owners = [t for t in census if t is not None]
        fair = (self.num_blocks - 1) // max(1, len(owners))
        return {
            t: (self.tenants.quota(t) or fair) for t in owners
        }

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Preemption victim: the live sequence with the most decode budget
        left (longest-remaining first — it would hold its blocks longest, and
        re-prefilling it re-caches the fewest finished tokens per block
        freed). Never the slot we're trying to grow. With a tenancy registry
        installed, candidates from over-share tenants are preferred before
        the tenant-blind fallback (:func:`~trlx_tpu.serving.tenancy.select_victim`)."""
        candidates = [
            (slot, req)
            for slot, req in enumerate(self.scheduler.slots)
            if req is not None and slot != exclude
        ]
        if self.tenants is not None:
            return select_victim(
                candidates, self.allocator.owner_census(), self._tenant_shares()
            )
        best, best_remaining = None, -1
        for slot, req in candidates:
            if req.remaining_tokens > best_remaining:
                best, best_remaining = slot, req.remaining_tokens
        return best

    def _enforce_quota(self, slot: int, req: Request, need_len: int) -> None:
        """Keep a live sequence's growth inside its tenant's KV-block quota:
        while the extension would push the tenant over, preempt the tenant's
        OWN longest-remaining other sequence (never another tenant's — quota
        pressure is self-inflicted). A lone sequence always fits: submit()
        rejects any request whose worst case exceeds its quota."""
        quota = self.tenants.quota(req.tenant_id)
        if not quota:
            return
        while True:
            grow = self.allocator.blocks_needed(need_len) - len(req.seq_blocks.blocks)
            if grow <= 0:
                return
            if self.allocator.owner_usage(req.tenant_id) + grow <= quota:
                return
            victim, victim_remaining = None, -1
            for s, r in enumerate(self.scheduler.slots):
                if r is None or s == slot or r.tenant_id != req.tenant_id:
                    continue
                if r.remaining_tokens > victim_remaining:
                    victim, victim_remaining = s, r.remaining_tokens
            if victim is None:
                return
            logger.warning(
                f"quota pressure: preempting uid={self.scheduler.slots[victim].uid} "
                f"(slot {victim}, tenant {req.tenant_id!r}) to grow slot {slot}"
            )
            self.scheduler.preempt(victim)
            self._free_slot_state(victim)

    def _ensure_decode_capacity(self) -> None:
        """Optimistic-admission mode: before the decode step, every live slot
        must own a block covering this round's write position. Growth comes
        from ``allocator.extend``; when the pool can't serve it, preempt
        victims (longest-remaining first) until it can. ``serving-alloc``
        chaos reports one extension as failed to drive this path on demand."""
        if self.policy is None or not self.policy.preemption:
            return
        for slot, req in enumerate(self.scheduler.slots):
            if req is None:
                continue
            if self._prefilling[slot]:
                # mid chunked-prefill: admission reserved the whole prefill
                # (+1) up front; no per-round growth until decode starts
                continue
            # lookahead covers every KV position this round can write: the
            # incoming token plus spec_k draft positions, clamped to the hard
            # sequence cap (positions past it are write-dropped and can never
            # be validated — the request finishes at the cap first)
            need_len = min(
                int(self._lens[slot]) + 1 + self.spec_k,
                len(req.prompt) + req.max_new_tokens,
            )
            if self.tenants is not None:
                self._enforce_quota(slot, req, need_len)
            before = len(req.seq_blocks.blocks)
            ok = (not chaos.should_fail("serving-alloc")) and self.allocator.extend(
                req.seq_blocks, need_len
            )
            while not ok:
                victim = self._pick_victim(exclude=slot)
                if victim is not None:
                    logger.warning(
                        f"kv pressure: preempting uid={self.scheduler.slots[victim].uid} "
                        f"(slot {victim}) to grow slot {slot}"
                    )
                    self.scheduler.preempt(victim)
                    self._free_slot_state(victim)
                ok = self.allocator.extend(req.seq_blocks, need_len)
                if not ok and victim is None:
                    # submit() bounds every request's worst case to the pool,
                    # so a lone sequence can always extend; reaching here
                    # means the pool accounting broke — fail to the supervisor
                    raise RuntimeError(
                        f"kv pool cannot cover lone slot {slot} at len {need_len}"
                    )
            if len(req.seq_blocks.blocks) != before:
                self._tables[slot, : len(req.seq_blocks.blocks)] = req.seq_blocks.blocks
                self._tables_dirty = True

    def _push_mirrors(self) -> None:
        """Push the host table/len mirrors to the device when stale. While a
        slot is mid chunked-prefill its true state (partial lens, real table
        row) must stay OFF the decode inputs — the pushed copy masks it to
        the null row / len 0 so the full-batch step treats it as idle — and
        the mirror stays dirty so completion re-pushes the real state."""
        prefill_active = bool(self._prefilling.any())
        if not (self._tables_dirty or prefill_active):
            return
        # push COPIES of the host mirrors: jnp.asarray may zero-copy an
        # aligned numpy buffer on CPU, and the mirrors are mutated in
        # place (``self._lens += ...`` below, slot frees) while the
        # dispatched step may still be reading the aliased device buffer
        # — an intermittent corruption under async dispatch
        tables = np.array(self._tables)
        lens = np.array(self._lens)
        if prefill_active:
            tables[self._prefilling] = 0
            lens[self._prefilling] = 0
        self.cache["block_tables"] = self._own(tables)
        self.cache["context_lens"] = self._own(lens)
        self._tables_dirty = prefill_active

    def _decode_round(self) -> List[Request]:
        finished: List[Request] = []
        for slot, req in self.scheduler.expire_live():
            self._free_slot_state(slot)
            finished.append(req)
        self._ensure_decode_capacity()
        live = [
            s for s, r in enumerate(self.scheduler.slots)
            if r is not None and not self._prefilling[s]
        ]
        if not live:
            return finished
        chaos.fail_if_armed("serving-decode", f"{len(live)} live slots")
        if flight.enabled:
            # journal BEFORE the device step: the request may finish inside
            # it, and the round marks decode participation either way
            t_round = self.scheduler.clock()
            for s in live:
                flight.record(
                    self.scheduler.slots[s].uid, "decode_round", t=t_round
                )
        self._push_mirrors()
        new_counts = np.array(
            [len(r.generated) if r is not None else 0 for r in self.scheduler.slots],
            np.int32,
        )
        if self.spec_k > 0:
            finished.extend(self._spec_round(live, new_counts))
        else:
            with compile_log.attributed("serving_decode_step"):
                next_tok, self.cache, self._rng = self._decode_step(
                    self.params,  # graftcheck: noqa[TH001] — under step()'s lock
                    jnp.asarray(self._pending_tok), self.cache,
                    self._rng, jnp.asarray(new_counts),
                )
            # device lens advanced for every slot; mirror so a no-admission
            # next step needs no host->device sync
            self._lens += 1
            tok_np = np.asarray(jax.device_get(next_tok))
            for slot in live:
                self._pending_tok[slot] = tok_np[slot]
                done = self.scheduler.on_token(slot, int(tok_np[slot]))
                if done is not None:
                    finished.append(done)
                    self._free_slot_state(slot)
            self.stats.delivered_tokens += len(live)
        self.scheduler.note_step()
        self.stats.decode_steps += 1
        self.stats.decode_slot_rounds += len(live)
        return finished

    def _spec_round(self, live: List[int], new_counts: np.ndarray) -> List[Request]:
        """One speculative decode round over the full slot batch: host n-gram
        drafts, one jitted verify step, per-slot accept bookkeeping. Emits
        ``accepted + 1`` tokens per live slot — every one of them provably
        what sequential greedy decode would have produced (the accept rule),
        which is the whole bandwidth play: one weight/KV read, many tokens."""
        finished: List[Request] = []
        K = self.spec_k
        drafts = np.zeros((self.num_slots, K), np.int32)
        for slot in live:
            req = self.scheduler.slots[slot]
            drafts[slot] = _ngram_propose(
                np.asarray(req.prefill_ids, np.int32), K,
                self.spec_ngram, self.pad_token_id,
            )
        tok = np.concatenate([self._pending_tok[:, None], drafts], axis=1)
        with compile_log.attributed("serving_verify_step"):
            y, accepted, self.cache, self._rng = self._verify_step(
                self.params,  # graftcheck: noqa[TH001] — under step()'s lock
                jnp.asarray(tok), self.cache, self._rng, jnp.asarray(new_counts),
            )
        acc_np = np.asarray(jax.device_get(accepted))
        y_np = np.asarray(jax.device_get(y))
        # device advanced EVERY slot's frontier by accepted+1 (idle slots
        # included, off their null garbage); mirror the same arithmetic so
        # host and device lens never diverge
        self._lens += acc_np.astype(np.int32) + 1
        self.stats.spec_rounds += 1
        self.stats.spec_draft_tokens += K * len(live)
        for slot in live:
            a = int(acc_np[slot])
            self.stats.spec_accepted_tokens += a
            if flight.enabled and a > 0:
                flight.record(
                    self.scheduler.slots[slot].uid, "spec_accept",
                    t=self.scheduler.clock(), accepted=a,
                )
            self._pending_tok[slot] = y_np[slot, a]
            done, emitted = self.scheduler.on_tokens(
                slot, [int(t) for t in y_np[slot, : a + 1]]
            )
            self.stats.delivered_tokens += emitted
            if done is not None:
                finished.append(done)
                self._free_slot_state(slot)
        return finished

    def attach_island(self, island) -> None:
        """Run this engine as a generation island
        (:class:`~trlx_tpu.serving.island.GenerationIsland`): every
        :meth:`step` touches the island's round gate, polls its publisher for
        a newly *committed* chunked broadcast — installing it via
        :meth:`set_params`, i.e. exactly one prefix-cache flush per version,
        atomically between rounds — and reports the round's busy interval to
        the island's idle-bubble ledger.

        Called only on a quiescent engine: at wiring time before the first
        step, or by the supervisor's restart on a freshly built successor
        before it adopts replay state — never with a round in flight."""
        self._island = island  # graftcheck: noqa[CC001]
        self._island_version = -1  # graftcheck: noqa[CC001]

    @property
    def serving_version(self) -> int:
        """Broadcast version the engine currently serves (-1 before the
        first island swap, or when no island is attached)."""
        return self._island_version

    def request_abort(self) -> None:
        """Unstick a wedged step loop (called by the watchdog escalation or
        the supervisor's per-round wedge timer, from their own threads).
        Event.set() is internally synchronized — taking the engine lock here
        would deadlock against the wedged step this call exists to abort."""
        self._abort_evt.set()  # graftcheck: noqa[TH001]

    def step(self) -> List[Request]:
        """One engine round: admissions (bucketed prefill) + one decode step.
        Returns requests finished during the round. With an island attached,
        the round boundary is also the atomic weight-swap point: the gate
        touch serializes against an in-flight chunk install, and a committed
        broadcast is installed before (never during) the round."""
        island = self._island
        t_round0 = 0.0
        if island is not None:
            gate = island.round_gate
            gate.acquire()
            gate.release()
            upd = island.poll_swap(self._island_version)
            if upd is not None:
                version, params = upd
                self.set_params(params)  # one prefix-cache flush per version
                self._island_version = version
            t_round0 = time.monotonic()
        with self._lock:
            if chaos.should_fail("serving-wedge"):
                # model a wedged device loop: no heartbeat, no exception, no
                # progress — parked until someone aborts it (watchdog
                # escalation or the supervisor's wedge timer)
                logger.warning("chaos: serving step wedged, waiting for abort")
                # blocking under the engine lock is the POINT: a wedged
                # device call holds the lock exactly like this, and recovery
                # (request_abort) must work without ever taking it
                self._abort_evt.wait()  # graftcheck: noqa[CC005]
                self._abort_evt.clear()
                raise EngineWedgedError("engine step loop wedged and was aborted")
            finished = self._admit()
            finished += self._advance_prefill_chunks()
            finished += self._decode_round()
            for req in finished:
                self.stats.finished_requests += 1
                if req.latency_s is not None:
                    gauges.observe(self.gauge_prefix + "request_latency_s", req.latency_s)
                    if self.tenants is not None:
                        self._tenant_latency.setdefault(
                            req.tenant_id, deque(maxlen=512)
                        ).append(req.latency_s)
                        self._class_latency.setdefault(
                            req.slo_class, deque(maxlen=512)
                        ).append(req.latency_s)
        if island is not None:
            island.note_round(t_round0, time.monotonic())
        return finished

    def begin_drain(self, shed_pending: bool = True) -> None:
        """Enter drain mode: reject new submits. ``shed_pending=False`` is the
        supervisor's mid-drain-restart case — the replay queue holds requests
        that were *live* and must finish, not be shed a second time."""
        self._draining.set()
        if shed_pending:
            self.scheduler.shed_all_pending()

    def drain(self) -> Dict[int, Request]:
        """Graceful shutdown: stop admitting new submits
        (:class:`EngineDrainingError`), shed everything still pending with an
        accountable ``shed`` outcome, and drive rounds until the live slots
        finish. Returns every request that reached a terminal state during
        the drain (preempted sequences re-enter and finish too)."""
        self.begin_drain()
        done: Dict[int, Request] = dict(self.scheduler.pop_finished())
        while self.scheduler.has_work:  # live slots + preemption re-queues
            self.step()
            done.update(self.scheduler.pop_finished())
        return done

    def adopt(self, state: Dict[str, object]) -> None:
        """Install a dead predecessor's exported request state (supervised
        restart): see :meth:`InflightScheduler.adopt_state`."""
        self.scheduler.adopt_state(state)

    def run(self, uids: Optional[Sequence[int]] = None) -> Dict[int, Request]:
        """Drive rounds until the given uids (or all work) complete."""
        want = set(uids) if uids is not None else None
        # collect anything already finished (e.g. cancelled while pending)
        done: Dict[int, Request] = dict(self.scheduler.pop_finished())
        while True:
            if want is not None:
                if want <= set(done):
                    break
                if not self.scheduler.has_work:
                    raise RuntimeError(
                        f"engine drained with requests unaccounted: {want - set(done)}"
                    )
            elif not self.scheduler.has_work:
                break
            self.step()
            done.update(self.scheduler.pop_finished())
            self.export_gauges()
        return done

    # -- observability -------------------------------------------------------

    def note_overlap(self, decode_busy_s: float, overlapped_s: float) -> None:
        """Record one stream-overlap window (trainer-side interval ledger):
        ``decode_busy_s`` seconds of engine stepping, ``overlapped_s`` seconds
        of reward/score/learn-stage work that ran inside those intervals."""
        with self._lock:
            self.stats.overlap_decode_s += float(decode_busy_s)
            self.stats.overlap_overlapped_s += float(overlapped_s)
            self.stats.overlap_windows += 1

    def summary(self) -> Dict[str, float]:
        # stats counters are written by step() under self._lock; snapshot them
        # under the same lock so a gauge read during a concurrent round is
        # consistent (the scheduler/allocator figures take their own locks)
        with self._lock:
            out = {
                "delivered_tokens": float(self.stats.delivered_tokens),
                "decode_steps": float(self.stats.decode_steps),
                "prefill_waves": float(self.stats.prefill_waves),
                "finished_requests": float(self.stats.finished_requests),
                # tokens emitted per live slot per decode round: exactly 1.0
                # with spec off; > 1 measures the speculative multiplier
                # actually delivered (the bandwidth-bound divisor)
                "accepted_tok_per_round": (
                    self.stats.delivered_tokens
                    / max(1, self.stats.decode_slot_rounds)
                ),
                "spec_accept_rate": (
                    self.stats.spec_accepted_tokens
                    / max(1, self.stats.spec_draft_tokens)
                ),
                "spec_rounds": float(self.stats.spec_rounds),
                "chunk_appends": float(self.stats.chunk_appends),
                # scored+learned time overlapped with decode ÷ decode time;
                # can exceed 1.0 when several reward workers hide more than
                # one serial second per decode second (unclamped on purpose)
                "overlap_fraction": (
                    self.stats.overlap_overlapped_s
                    / max(1e-9, self.stats.overlap_decode_s)
                    if self.stats.overlap_windows
                    else 0.0
                ),
                "overlap_decode_s": float(self.stats.overlap_decode_s),
                "overlap_overlapped_s": float(self.stats.overlap_overlapped_s),
                "overlap_windows": float(self.stats.overlap_windows),
            }
        out["mean_slot_occupancy"] = self.scheduler.mean_slot_occupancy
        out["prefix_cache_hit_rate"] = self.allocator.stats.hit_rate
        out["blocks_in_use"] = float(self.allocator.blocks_in_use)
        out["pending_depth"] = float(self.scheduler.pending_depth)
        for key, count in self.scheduler.outcome_counts().items():
            out[key] = float(count)
        return out

    @staticmethod
    def _p99(window: Sequence[float]) -> float:
        """Nearest-rank p99 over a latency window (0.0 when empty)."""
        xs = sorted(window)
        if not xs:
            return 0.0
        return nearest_rank(xs, 0.99)

    def export_gauges(self) -> None:
        s = self.summary()
        gp = self.gauge_prefix
        gauges.set(gp + "slot_occupancy", s["mean_slot_occupancy"])
        gauges.set(gp + "prefix_cache_hit_rate", s["prefix_cache_hit_rate"])
        gauges.set(gp + "blocks_in_use", s["blocks_in_use"])
        gauges.set(gp + "delivered_tokens", s["delivered_tokens"])
        gauges.set(gp + "finished_requests", s["finished_requests"])
        gauges.set(gp + "pending_depth", s["pending_depth"])
        # instantaneous live-slot count (slot_occupancy above is a lifetime
        # mean): the fleet autoscaler's scale-down signal must see idleness
        # NOW, not averaged over the whole busy history
        gauges.set(gp + "live_slots", float(self.scheduler.live_slots))
        gauges.set(gp + "accepted_tok_per_round", s["accepted_tok_per_round"])
        gauges.set(gp + "spec_accept_rate", s["spec_accept_rate"])
        gauges.set(gp + "overlap_fraction", s["overlap_fraction"])
        gauges.set(gp + "shed", s["shed"])
        gauges.set(gp + "expired", s["expired"])
        gauges.set(gp + "preempted", s["preempted"])
        if self.tenants is None:
            return
        # per-tenant / per-SLO-class breakdowns (satellite: <prefix>tenant/*
        # and <prefix>class/* ride the same registry; ServingEngine.close()
        # clears the whole gauge prefix)
        tenant_counts = self.scheduler.tenant_outcome_counts()
        # zero-fill every registered tenant so dashboards see stable keys
        # even before a tenant's first shed/expiry/preemption
        for tid in set(self.tenants.tenant_ids()) | set(tenant_counts):
            counts = tenant_counts.get(tid, {})
            for key in ("shed", "expired", "preempted"):
                gauges.set(f"{gp}tenant/{tid}/{key}", float(counts.get(key, 0)))
        for cls, counts in self.scheduler.class_outcome_counts().items():
            for key in ("shed", "expired", "preempted"):
                gauges.set(f"{gp}class/{cls}/{key}", float(counts.get(key, 0)))
        with self._lock:
            tenant_lat = {t: list(w) for t, w in self._tenant_latency.items()}
            class_lat = {c: list(w) for c, w in self._class_latency.items()}
        for tid, window in tenant_lat.items():
            gauges.set(f"{gp}tenant/{tid}/p99_latency_s", self._p99(window))
        for cls, window in class_lat.items():
            gauges.set(f"{gp}class/{cls}/p99_latency_s", self._p99(window))
        for tid, used in self.allocator.owner_census().items():
            if tid is not None:
                gauges.set(f"{gp}tenant/{tid}/blocks_in_use", float(used))

    def close(self) -> None:
        """Retire this engine's observability surface: clear every gauge
        under this engine's gauge prefix (GaugeRegistry.clear is
        prefix-aware), so a later engine in the same process — or the other
        replicas of a fleet — start from / keep a clean slate. Callers that
        want final values snapshot them BEFORE close — the supervisor
        deliberately does not call this, its tests read gauges after
        shutdown."""
        gauges.clear(prefix=self.gauge_prefix)
