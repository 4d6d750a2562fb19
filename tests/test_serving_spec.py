"""Speculative decoding + chunked prefill tests (docs/serving.md
"Speculative decoding"): greedy bit-parity of the spec path against the
one-shot generate reference across pool layouts and chunk sizes, accept-rate
accounting sanity, scheduler anti-starvation aging under a mixed workload,
KV-pressure preemption replaying accepted draft tokens exactly, and the
TRLX_SPEC_SEED_REGRESSION=accept_all self-test (forced acceptance MUST break
parity — proving the parity harness can actually fail)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.models.presets import PRESETS
from trlx_tpu.models.transformer import TransformerLM
from trlx_tpu.serving import (
    GenerationClient,
    InflightScheduler,
    PagedBlockAllocator,
    ServingEngine,
    ServingResiliencePolicy,
)
from trlx_tpu.serving.engine import _ngram_propose

pytestmark = [pytest.mark.serving, pytest.mark.serving_spec]

TINY = dict(
    vocab_size=37, hidden_size=16, num_layers=2, num_heads=2,
    max_position_embeddings=64, compute_dtype=jnp.float32,
)

PROMPTS = [
    [5, 9, 11], [2, 30, 7, 1, 3, 22, 4, 8, 15, 16, 23, 31],
    [1, 2, 3, 4, 5, 6, 7], [33, 12], [9, 9, 9, 9, 9],
]


@pytest.fixture(scope="module")
def tiny_engine_parts():
    config = PRESETS["gpt2"].replace(**TINY)
    model = TransformerLM(config)
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32)
    )["params"]
    return model, params, config


def _reference_generate(model, params, prompts, max_new, eos=None):
    from trlx_tpu.ops.generation import LENGTH_BUCKETS, generate, left_pad_batch, pad_to_bucket

    P = pad_to_bucket(max(len(p) for p in prompts), LENGTH_BUCKETS)
    ids, mask = left_pad_batch([np.asarray(p, np.int32) for p in prompts], 0, P)

    def step(p, i, m, pos, cache):
        logits, hidden, _, cache = model.apply({"params": p}, i, m, pos, cache)
        return logits, hidden, cache

    out = generate(
        step, params, lambda b, s: model.init_cache(b, s),
        jnp.asarray(ids), jnp.asarray(mask), jax.random.PRNGKey(0),
        max_new_tokens=max_new, do_sample=False,
        eos_token_id=eos, pad_token_id=0,
    )
    return np.asarray(out["sequences"]), np.asarray(out["response_mask"]), P


def _spec_engine(parts, *, quant=False, spec_k=0, spec_ngram=3, prefill_chunk=0,
                 num_slots=3, num_blocks=0, policy=None, max_seq_len=32):
    _, params, config = parts
    trunk = TransformerLM(config.replace(kv_cache_quant=quant))
    return ServingEngine(
        trunk, params, num_slots=num_slots, max_seq_len=max_seq_len,
        block_size=4, num_blocks=num_blocks, eos_token_id=None, pad_token_id=0,
        gen_kwargs=dict(do_sample=False), seed=0, policy=policy,
        spec_k=spec_k, spec_ngram=spec_ngram, prefill_chunk=prefill_chunk,
    )


# ------------------------------------------------------------------ drafting


def test_ngram_propose_prefers_longest_suffix_match():
    ctx = np.array([7, 8, 9, 5, 6, 7, 8, 9], np.int32)
    # suffix [7,8,9] matched at position 0 (order 3) -> continuation 5, 6, ...
    got = _ngram_propose(ctx, 4, max_order=3, pad_token=0)
    np.testing.assert_array_equal(got, [5, 6, 7, 8])


def test_ngram_propose_pads_when_nothing_matches():
    ctx = np.array([1, 2, 3, 4], np.int32)  # no repeated n-gram of any order
    got = _ngram_propose(ctx, 3, max_order=3, pad_token=0)
    np.testing.assert_array_equal(got, [0, 0, 0])


# -------------------------------------------------------------- greedy parity


@pytest.mark.parametrize(
    "spec_k,prefill_chunk",
    [(4, 0), (0, 4), (3, 5)],
    ids=["spec_k4", "chunk4", "spec_k3+chunk5"],
)
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
def test_spec_greedy_parity_with_generate(tiny_engine_parts, quant, spec_k,
                                          prefill_chunk):
    """The acceptance-rule theorem as a test: speculative decode (and chunked
    prefill, alone and combined) must produce byte-identical sequences and
    response masks to the one-shot generate path under greedy decoding —
    every accepted draft is provably what sequential decode would have
    emitted."""
    model, params, config = tiny_engine_parts
    eng = _spec_engine(
        tiny_engine_parts, quant=quant, spec_k=spec_k, prefill_chunk=prefill_chunk,
    )
    client = GenerationClient(eng)
    seqs, mask, P = client.generate_batch(
        [np.asarray(p, np.int32) for p in PROMPTS], 6
    )
    ref_seqs, ref_mask, ref_P = _reference_generate(model, params, PROMPTS, 6)
    assert P == ref_P
    np.testing.assert_array_equal(seqs, ref_seqs)
    np.testing.assert_array_equal(mask, ref_mask)
    summary = eng.summary()
    if spec_k > 0:
        assert summary["spec_rounds"] > 0
        assert summary["accepted_tok_per_round"] >= 1.0
    if prefill_chunk > 0:
        assert summary["chunk_appends"] > 0  # a 12-token prompt chunks
    assert eng.allocator.blocks_in_use == 0
    eng.allocator.check_invariants()


def test_spec_eos_parity_stops_inside_an_accept_run(tiny_engine_parts):
    """An eos validated mid-accept-run must finish the request THERE: tokens
    past it in the same verify round are never emitted (exactly what
    step-at-a-time decode does)."""
    model, params, config = tiny_engine_parts
    prompts = [[5, 9, 11, 2], [7, 1, 3]]
    ref_seqs, _, _ = _reference_generate(model, params, prompts, 8)
    eos = int(ref_seqs[0, -8:][1])  # fires mid-generation
    ref_seqs, ref_mask, P = _reference_generate(model, params, prompts, 8, eos=eos)
    _, params, config = tiny_engine_parts
    eng = ServingEngine(
        TransformerLM(config), params, num_slots=2, max_seq_len=32, block_size=4,
        eos_token_id=eos, pad_token_id=0, gen_kwargs=dict(do_sample=False),
        seed=0, spec_k=4,
    )
    seqs, mask, P2 = GenerationClient(eng).generate_batch(
        [np.asarray(p, np.int32) for p in prompts], 8
    )
    assert P2 == P
    np.testing.assert_array_equal(seqs, ref_seqs)
    np.testing.assert_array_equal(mask, ref_mask)
    eng.allocator.check_invariants()


def test_spec_off_keeps_baseline_accounting():
    """spec_k=0 keeps the exact one-token-per-round accounting (the summary
    values the pre-spec engine reported)."""
    config = PRESETS["gpt2"].replace(**TINY)
    model = TransformerLM(config)
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32)
    )["params"]
    eng = ServingEngine(
        model, params, num_slots=2, max_seq_len=32, block_size=4,
        eos_token_id=None, pad_token_id=0, gen_kwargs=dict(do_sample=False), seed=0,
    )
    uids = [eng.submit(p, 4) for p in ([3, 1, 4], [1, 5, 9, 2])]
    eng.run(uids)
    summary = eng.summary()
    assert summary["accepted_tok_per_round"] == 1.0
    assert summary["spec_accept_rate"] == 0.0
    assert summary["spec_rounds"] == 0.0
    assert summary["chunk_appends"] == 0.0


def test_spec_accounting_is_consistent(tiny_engine_parts):
    eng = _spec_engine(tiny_engine_parts, spec_k=3)
    uids = [eng.submit(p, 6) for p in PROMPTS]
    eng.run(uids)
    s = eng.stats
    assert s.spec_rounds > 0 and s.spec_draft_tokens > 0
    assert 0 <= s.spec_accepted_tokens <= s.spec_draft_tokens
    summary = eng.summary()
    assert 0.0 <= summary["spec_accept_rate"] <= 1.0
    # every live slot emits at least its sampled token each round; delivered
    # never exceeds (K+1) per slot-round
    assert 1.0 <= summary["accepted_tok_per_round"] <= 4.0
    from trlx_tpu.utils.metrics import gauges

    eng.export_gauges()
    snap = gauges.snapshot()
    assert snap["serving/accepted_tok_per_round"] == pytest.approx(
        summary["accepted_tok_per_round"]
    )
    assert snap["serving/spec_accept_rate"] == pytest.approx(
        summary["spec_accept_rate"]
    )
    gauges.clear(prefix="serving/")


def test_engine_rejects_bad_spec_knobs(tiny_engine_parts):
    with pytest.raises(ValueError, match="spec_k"):
        _spec_engine(tiny_engine_parts, spec_k=-1)
    with pytest.raises(ValueError, match="spec_ngram"):
        _spec_engine(tiny_engine_parts, spec_k=2, spec_ngram=0)
    with pytest.raises(ValueError, match="prefill_chunk"):
        _spec_engine(tiny_engine_parts, prefill_chunk=-2)


# ------------------------------------------------------------ anti-starvation


def test_scheduler_ages_long_prompts_past_short_stream():
    """Mixed workload: a sustained stream of short prompts must not starve a
    long one — after `age_priority_after` passed-over rounds the aging bonus
    outranks any fresh short arrival."""
    a = PagedBlockAllocator(num_blocks=64, block_size=4, prefix_caching=False)
    s = InflightScheduler(
        num_slots=1, allocator=a, age_priority_after=2, age_priority_bonus=64
    )
    u_long = s.submit(list(range(20)), 2)
    placed_uids = []
    for round_i in range(12):
        s.submit([round_i], 2)  # fresh short prompt every round
        placements = s.admissions()
        for slot, req in placements:
            placed_uids.append(req.uid)
            # finish immediately so the slot frees for the next round
            s.on_token(slot, 1)
            s.on_token(slot, 2)
        if u_long in placed_uids:
            break
    assert u_long in placed_uids, "long prompt starved by the short stream"
    # it waited the configured grace rounds first (shortest-first still wins
    # while the bonus hasn't kicked in)
    assert placed_uids.index(u_long) >= 2
    req = s.requests[u_long]
    assert req.admit_waits == 0  # reset on placement


def test_scheduler_aging_only_accrues_when_slots_were_free():
    """Full occupancy is not starvation: admit_waits must not accrue while
    every slot is busy (no admissions round ran with free capacity)."""
    a = PagedBlockAllocator(num_blocks=64, block_size=4, prefix_caching=False)
    s = InflightScheduler(num_slots=1, allocator=a)
    u_busy = s.submit([1], 8)
    s.admissions()
    u_wait = s.submit(list(range(12)), 2)
    for _ in range(5):
        assert s.admissions() == []  # no free slots: not a passed-over round
    assert s.requests[u_wait].admit_waits == 0
    # free the slot; now a passed-over round with a shorter rival does accrue
    s.on_token(0, 1)
    for t in range(7):
        s.on_token(0, t)
    assert s.requests[u_busy].done
    s.submit([2], 2)
    s.admissions()  # places the short one, passes over u_wait
    assert s.requests[u_wait].admit_waits == 1


# ------------------------------------------------------- preemption + replay


def test_spec_preemption_replays_accepted_draft_tokens(tiny_engine_parts):
    """KV-pressure preemption mid-speculation: a preempted request re-prefills
    from host state — prompt + everything generated INCLUDING tokens that
    arrived as accepted drafts — and finishes with exactly the tokens an
    unpressured non-speculative engine produces."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 37, size=n).tolist() for n in (6, 7, 8)]
    pol = ServingResiliencePolicy(preemption=True)
    tight = _spec_engine(
        tiny_engine_parts, spec_k=3, num_slots=3, num_blocks=8, policy=pol,
    )
    uids_t = [tight.submit(p, 10) for p in prompts]
    done_t = tight.run(uids_t)
    assert tight.scheduler.preempted_count > 0  # pressure actually preempted
    tight.allocator.check_invariants()
    assert tight.allocator.blocks_in_use == 0

    roomy = _spec_engine(tiny_engine_parts, spec_k=0, num_slots=3)
    uids_r = [roomy.submit(p, 10) for p in prompts]
    done_r = roomy.run(uids_r)
    for ut, ur in zip(uids_t, uids_r):
        assert done_t[ut].finish_reason == done_r[ur].finish_reason
        assert done_t[ut].generated == done_r[ur].generated
    preempted = [done_t[u] for u in uids_t if done_t[u].preemptions > 0]
    assert preempted
    # at least one victim was carrying generated output when evicted: its
    # replay re-prefilled accepted tokens, and the parity above proves the
    # re-prefilled KV reproduced the original context exactly
    assert any(len(r.generated) > 0 for r in preempted)


@pytest.mark.slow
def test_spec_chaos_soak_every_request_accounted(tiny_engine_parts):
    """Spec + chunked prefill under sustained KV pressure with preemption on:
    a 24-request stream through a tight pool must finish every request with
    greedy output identical to a roomy non-speculative engine, with zero
    block leaks across every preemption/re-prefill cycle."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 37, size=int(rng.integers(4, 12))).tolist()
               for _ in range(24)]
    budgets = [int(rng.integers(4, 9)) for _ in range(24)]
    pol = ServingResiliencePolicy(preemption=True)
    tight = _spec_engine(
        tiny_engine_parts, spec_k=3, prefill_chunk=4,
        num_slots=3, num_blocks=10, policy=pol,
    )
    uids_t = [tight.submit(p, b) for p, b in zip(prompts, budgets)]
    done_t = tight.run(uids_t)
    assert set(done_t) >= set(uids_t)
    assert tight.scheduler.preempted_count > 0
    assert tight.allocator.blocks_in_use == 0
    tight.allocator.check_invariants()

    roomy = _spec_engine(tiny_engine_parts, spec_k=0, num_slots=3)
    uids_r = [roomy.submit(p, b) for p, b in zip(prompts, budgets)]
    done_r = roomy.run(uids_r)
    for ut, ur in zip(uids_t, uids_r):
        assert done_t[ut].generated == done_r[ur].generated, (
            f"uid {ut} diverged after {done_t[ut].preemptions} preemptions"
        )


# ------------------------------------------------------- seeded regression


def test_seed_regression_accept_all_breaks_parity(tiny_engine_parts, monkeypatch):
    """The ci.sh tripwire: TRLX_SPEC_SEED_REGRESSION=accept_all forces every
    draft accepted, which MUST break greedy parity — proving the parity
    harness detects a broken accept rule rather than vacuously passing."""
    model, params, config = tiny_engine_parts
    monkeypatch.setenv("TRLX_SPEC_SEED_REGRESSION", "accept_all")
    eng = _spec_engine(tiny_engine_parts, spec_k=4)
    assert eng._spec_seed_regression == "accept_all"
    seqs, _, _ = GenerationClient(eng).generate_batch(
        [np.asarray(p, np.int32) for p in PROMPTS], 6
    )
    ref_seqs, _, _ = _reference_generate(model, params, PROMPTS, 6)
    assert not np.array_equal(seqs, ref_seqs), (
        "forced acceptance did not break parity: the harness cannot fail"
    )


def test_seed_regression_rejects_unknown_mode(tiny_engine_parts, monkeypatch):
    monkeypatch.setenv("TRLX_SPEC_SEED_REGRESSION", "bogus")
    with pytest.raises(ValueError, match="TRLX_SPEC_SEED_REGRESSION"):
        _spec_engine(tiny_engine_parts, spec_k=2)
