"""Continuous-batching serving tests: allocator invariants (block leaks on
cancel/stop-sequence, ref-counts under prefix sharing), scheduler admission,
engine/client parity against the one-shot generate path, the sampling
slow-path property test, and trainer integration (`train.serving` off by
default; quarantine diversion with serving active)."""

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
)
from trlx_tpu.serving.scheduler import (
    FINISH_CANCELLED,
    FINISH_EOS,
    FINISH_LENGTH,
    FINISH_STOP,
)

pytestmark = pytest.mark.serving

TINY = dict(
    vocab_size=37, hidden_size=16, num_layers=2, num_heads=2,
    max_position_embeddings=64, compute_dtype=jnp.float32,
)


# ---------------------------------------------------------------- allocator


def test_allocator_reserves_and_frees_without_leak():
    a = PagedBlockAllocator(num_blocks=9, block_size=4, prefix_caching=False)
    seqs = [a.allocate(list(range(5)), 5 + 6) for _ in range(2)]  # 3 blocks each
    assert all(s is not None for s in seqs)
    assert a.blocks_in_use == 6
    a.check_invariants()
    assert a.allocate(list(range(9)), 18) is None  # 5 blocks > 2 free
    for s in seqs:
        a.free(s)
    assert a.blocks_in_use == 0
    a.check_invariants()
    with pytest.raises(ValueError, match="double free"):
        a.free(type(seqs[0])(blocks=[1], num_shared=0))


def test_allocator_refcount_under_prefix_sharing():
    a = PagedBlockAllocator(num_blocks=16, block_size=4)
    shared_prompt = list(range(8))  # exactly 2 full shareable blocks
    s1 = a.allocate(shared_prompt + [100], 12)
    s2 = a.allocate(shared_prompt + [101], 12)
    assert s1.num_shared == 0  # first writer owns fresh blocks
    assert s2.num_shared == 2 and s2.blocks[:2] == s1.blocks[:2]
    # shared blocks are double-counted in refs, not in the census
    a.check_invariants()
    in_use_both = a.blocks_in_use
    a.free(s1)
    a.check_invariants()
    # the shared blocks stay live (s2 still holds them): only s1's exclusive
    # tail returned
    assert a.blocks_in_use == in_use_both - 1
    a.free(s2)
    a.check_invariants()
    # refcount 0 + registered hash -> parked in the prefix LRU, not leaked
    assert a.blocks_in_use == 0
    s3 = a.allocate(shared_prompt + [102], 12)
    assert s3.num_shared == 2  # revived from the parked LRU
    assert a.stats.prefix_hits == 4
    a.free(s3)
    a.check_invariants()


def test_allocator_flush_prefix_cache_returns_parked_blocks():
    a = PagedBlockAllocator(num_blocks=8, block_size=4)
    s = a.allocate(list(range(8)), 8)
    a.free(s)
    assert a.blocks_in_use == 0 and a.free_blocks == 7
    a.flush_prefix_cache()
    a.check_invariants()
    s2 = a.allocate(list(range(8)), 8)
    assert s2.num_shared == 0  # flushed: no stale-parameter sharing
    a.free(s2)


def test_allocator_write_frontier_never_in_shared_block():
    """Only FULL prompt blocks are shared: the partial tail (where decode
    writes begin) is always exclusive."""
    a = PagedBlockAllocator(num_blocks=16, block_size=4)
    p = list(range(10))  # 2 full blocks + 2 tokens in the tail
    s1 = a.allocate(p, 14)
    s2 = a.allocate(p, 14)
    assert s2.num_shared == 2
    assert s2.blocks[2] != s1.blocks[2]  # tail block exclusive to each


# ---------------------------------------------------------------- scheduler


def test_scheduler_slot_turnover_and_finish_reasons():
    a = PagedBlockAllocator(num_blocks=32, block_size=4, prefix_caching=False)
    s = InflightScheduler(num_slots=2, allocator=a)
    # admissions place shortest prompts first: u_eos then u_stop; u_len pends
    u_eos = s.submit([1], 8, eos_token_id=9)
    u_stop = s.submit([4, 5], 8, stop_sequences=[[7, 8]])
    u_len = s.submit([6, 7, 8], 2)
    placed = s.admissions()
    assert [r.uid for _, r in placed] == [u_eos, u_stop]  # third stays pending
    s.on_token(0, 5)
    assert s.on_token(0, 9).finish_reason == FINISH_EOS
    assert a.blocks_in_use > 0
    s.on_token(1, 7)
    assert s.on_token(1, 8).finish_reason == FINISH_STOP
    placed = s.admissions()  # freed slots admit the pending request
    assert [r.uid for _, r in placed] == [u_len]
    slot = placed[0][0]
    s.on_token(slot, 1)
    done = s.on_token(slot, 2)
    assert done.finish_reason == FINISH_LENGTH and len(done.generated) == 2
    assert a.blocks_in_use == 0  # every finish path freed its blocks
    a.check_invariants()
    fin = s.pop_finished()
    assert set(fin) == {u_eos, u_stop, u_len}


def test_scheduler_cancel_frees_blocks():
    a = PagedBlockAllocator(num_blocks=32, block_size=4, prefix_caching=False)
    s = InflightScheduler(num_slots=2, allocator=a)
    u1 = s.submit([1, 2, 3], 8)
    u2 = s.submit([4, 5, 6], 8)
    s.admissions()
    assert s.cancel(u1)  # in-flight: reaped next round
    assert s.reap_cancelled() == [0]
    assert s.requests[u1].finish_reason == FINISH_CANCELLED
    u3 = s.submit([7], 4)
    assert s.cancel(u3)  # still pending: finishes immediately
    assert s.requests[u3].finish_reason == FINISH_CANCELLED
    s.cancel(u2)
    s.reap_cancelled()
    assert a.blocks_in_use == 0
    a.check_invariants()


# ------------------------------------------------------------------- engine


@pytest.fixture(scope="module")
def tiny_engine_parts():
    config = PRESETS["gpt2"].replace(**TINY)
    model = TransformerLM(config)
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32)
    )["params"]
    return model, params, config


def _reference_generate(model, params, prompts, max_new, eos=None):
    """The one-shot path: ops.generation.generate, greedy."""
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


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
def test_engine_greedy_parity_with_generate(tiny_engine_parts, quant):
    """Continuous batching (5 prompts through 3 slots, mixed lengths, mid-run
    admissions) must produce byte-identical sequences and response masks to
    the one-shot generate path under greedy decoding."""
    model, params, config = tiny_engine_parts
    trunk = TransformerLM(config.replace(kv_cache_quant=quant))
    prompts = [
        [5, 9, 11], [2, 30, 7, 1, 3, 22, 4, 8, 15, 16, 23, 31],
        [1, 2, 3, 4, 5, 6, 7], [33, 12], [9, 9, 9, 9, 9],
    ]
    eng = ServingEngine(
        trunk, params, num_slots=3, max_seq_len=32, block_size=4,
        eos_token_id=None, pad_token_id=0,
        gen_kwargs=dict(do_sample=False), seed=0,
    )
    client = GenerationClient(eng)
    seqs, mask, P = client.generate_batch([np.asarray(p, np.int32) for p in prompts], 6)
    ref_seqs, ref_mask, ref_P = _reference_generate(model, params, prompts, 6)
    assert P == ref_P
    np.testing.assert_array_equal(seqs, ref_seqs)
    np.testing.assert_array_equal(mask, ref_mask)
    # continuous batching actually happened and nothing leaked
    assert eng.stats.prefill_waves >= 2
    assert eng.allocator.blocks_in_use == 0
    eng.allocator.check_invariants()


def test_engine_eos_parity_and_mask(tiny_engine_parts):
    """Pick an eos that actually fires mid-generation; mask must be 1 up to
    AND including eos, sequence padded after — exactly the generate contract."""
    model, params, config = tiny_engine_parts
    prompts = [[5, 9, 11, 2], [7, 1, 3]]
    ref_seqs, ref_mask, _ = _reference_generate(model, params, prompts, 8)
    # the token the reference generates second becomes our eos
    eos = int(ref_seqs[0, -8:][1])
    ref_seqs, ref_mask, P = _reference_generate(model, params, prompts, 8, eos=eos)
    eng = ServingEngine(
        TransformerLM(config), params, num_slots=2, max_seq_len=32, block_size=4,
        eos_token_id=eos, pad_token_id=0, gen_kwargs=dict(do_sample=False), seed=0,
    )
    seqs, mask, P2 = GenerationClient(eng).generate_batch(
        [np.asarray(p, np.int32) for p in prompts], 8
    )
    assert P2 == P
    np.testing.assert_array_equal(seqs, ref_seqs)
    np.testing.assert_array_equal(mask, ref_mask)
    eng.allocator.check_invariants()


def test_engine_stream_and_cancel_frees_blocks(tiny_engine_parts):
    model, params, config = tiny_engine_parts
    eng = ServingEngine(
        TransformerLM(config), params, num_slots=2, max_seq_len=32, block_size=4,
        eos_token_id=None, pad_token_id=0, gen_kwargs=dict(do_sample=False), seed=0,
    )
    client = GenerationClient(eng)
    uid = client.submit([5, 9, 11], 16)
    stream = client.stream(uid)
    got = [next(stream) for _ in range(3)]
    assert len(got) == 3
    assert client.cancel(uid)
    leftovers = list(stream)  # drains whatever was decoded before the reap
    eng.step()  # reap round
    req = eng.scheduler.requests[uid]
    assert req.finish_reason == FINISH_CANCELLED
    assert req.generated[:3] == got and len(req.generated) >= len(got) + len(leftovers) - 1
    assert eng.allocator.blocks_in_use == 0
    eng.allocator.check_invariants()


def test_engine_prefix_sharing_and_param_swap_flush(tiny_engine_parts):
    model, params, config = tiny_engine_parts
    eng = ServingEngine(
        TransformerLM(config), params, num_slots=2, max_seq_len=40, block_size=4,
        eos_token_id=None, pad_token_id=0, gen_kwargs=dict(do_sample=False), seed=0,
    )
    client = GenerationClient(eng)
    system = [5, 9, 11, 2, 30, 7, 1, 3]  # two full shareable blocks
    prompts = [np.asarray(system + [t], np.int32) for t in (4, 8, 15, 16)]
    first, _, _ = client.generate_batch(prompts, 4)
    assert eng.allocator.stats.prefix_hits > 0
    assert eng.allocator.blocks_in_use == 0
    # same params -> shared-prefix results identical to fresh-prefill results
    eng.set_params(params)  # flushes the prefix cache
    assert eng.allocator.stats.hit_rate < 1.0
    second, _, _ = client.generate_batch(prompts, 4)
    np.testing.assert_array_equal(first, second)
    eng.allocator.check_invariants()


def test_engine_rejects_oversized_requests(tiny_engine_parts):
    model, params, config = tiny_engine_parts
    eng = ServingEngine(
        TransformerLM(config), params, num_slots=1, max_seq_len=16, block_size=4,
        eos_token_id=None, pad_token_id=0, gen_kwargs=dict(do_sample=False), seed=0,
    )
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(list(range(12)), 8)


def test_engine_gauges_exported(tiny_engine_parts):
    from trlx_tpu.utils.metrics import gauges

    model, params, config = tiny_engine_parts
    eng = ServingEngine(
        TransformerLM(config), params, num_slots=2, max_seq_len=32, block_size=4,
        eos_token_id=None, pad_token_id=0, gen_kwargs=dict(do_sample=False), seed=0,
    )
    GenerationClient(eng).generate_batch([np.asarray([5, 9, 11], np.int32)], 4)
    snap = gauges.snapshot()
    for key in (
        "serving/slot_occupancy", "serving/prefix_cache_hit_rate",
        "serving/blocks_in_use", "serving/delivered_tokens",
    ):
        assert key in snap
    assert snap["serving/delivered_tokens"] >= 3.0
    gauges.clear(prefix="serving/")


# ----------------------------------------------------------------- sampling


def test_exact_top_k_property_bitwise_identical():
    """S1 property test: the two-stage grouped exact top-k must be
    bit-identical to jax.lax.top_k — values, indices, and smallest-index
    tie-breaks — across shapes, heavy ties, and masked vocabularies; and
    sample_token's exact path must emit IDENTICAL samples."""
    from trlx_tpu.ops.sampling import NEG_INF, _nucleus_keep, exact_top_k, sample_token

    rng = np.random.default_rng(0)
    for trial in range(40):
        B = int(rng.integers(1, 5))
        V = int(rng.integers(3, 400))
        k = int(rng.integers(1, min(V, 64) + 1))
        x = rng.standard_normal((B, V)).astype(np.float32)
        if trial % 3 == 0:
            x = np.round(x * 2) / 2  # force heavy ties
        if trial % 4 == 0:
            x[:, rng.integers(0, V, size=max(1, V // 3))] = NEG_INF
        v_ref, i_ref = jax.lax.top_k(jnp.asarray(x), k)
        v_got, i_got = exact_top_k(jnp.asarray(x), k)
        np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_got))
        np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_got))

    def full_vocab_reference(key, logits, temperature, top_k, top_p):
        logits = logits.astype(jnp.float32) / temperature
        vals, idx = jax.lax.top_k(logits, top_k)
        vals = jnp.where(_nucleus_keep(vals, top_p), vals, NEG_INF)
        choice = jax.random.categorical(key, vals, axis=-1)
        return jnp.take_along_axis(idx, choice[..., None], axis=-1)[..., 0]

    for trial in range(10):
        key = jax.random.PRNGKey(trial)
        logits = jnp.asarray(rng.standard_normal((8, 1031)).astype(np.float32) * 3)
        got = sample_token(key, logits, temperature=0.7, top_k=50, top_p=0.95,
                           top_k_impl="exact")
        ref = full_vocab_reference(key, logits, 0.7, 50, 0.95)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ------------------------------------------------------------------ trainer


def _tiny_ppo_config(tmp_path, serving=None, self_healing=None):
    from trlx_tpu.data.configs import (
        MeshConfig, ModelConfig, OptimizerConfig, SchedulerConfig,
        SelfHealingConfig, ServingConfig, TokenizerConfig, TrainConfig, TRLConfig,
    )
    from trlx_tpu.methods.ppo import PPOConfig

    alphabet = "abcdefgh "
    return TRLConfig(
        method=PPOConfig(
            num_rollouts=4, chunk_size=2, ppo_epochs=1, init_kl_coef=0.01,
            target=None, gen_kwargs=dict(max_new_tokens=4, do_sample=False),
        ),
        train=TrainConfig(
            seq_length=16, epochs=1, total_steps=1, batch_size=4, minibatch_size=2,
            checkpoint_interval=100, eval_interval=100,
            checkpoint_dir=str(tmp_path / "ckpts"), pipeline="PromptPipeline",
            trainer="PPOTrainer", tracker=None, seed=2,
            serving=serving or ServingConfig(),
            self_healing=self_healing or SelfHealingConfig(),
        ),
        model=ModelConfig(
            model_path="gpt2", num_layers_unfrozen=-1,
            model_overrides=dict(
                vocab_size=len(alphabet) + 3, hidden_size=32, num_layers=2,
                num_heads=2, intermediate_size=64, max_position_embeddings=64,
            ),
        ),
        tokenizer=TokenizerConfig(tokenizer_path=f"char://{alphabet}"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(name="cosine_annealing", kwargs=dict(T_max=100, eta_min=1e-3)),
        mesh=MeshConfig(data=1, fsdp=1, model=1, compute_dtype="float32"),
    )


@pytest.fixture
def single_device_mesh(monkeypatch):
    """Serving requires a single-device mesh; conftest exposes 8 virtual CPU
    devices, so pin trainer meshes to the first."""
    from trlx_tpu.parallel import mesh as mesh_lib

    real = mesh_lib.make_mesh
    monkeypatch.setattr(
        mesh_lib, "mesh_from_config",
        lambda cfg, devices=None: real(
            data=1, fsdp=1, model=1, devices=jax.devices()[:1]
        ),
    )


def _build_ppo(config):
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
    from trlx_tpu.utils.loading import get_trainer

    def reward(samples, **kw):
        return [float(s.count("a")) for s in samples]

    trainer = get_trainer("PPOTrainer")(config=config, reward_fn=reward)
    prompts = ["ab", "cd ef", "gh", "a b c"]
    trainer.add_prompt_pipeline(PromptPipeline(prompts, 12, trainer.tokenizer))
    return trainer


def _store_dump(trainer):
    return [
        (np.asarray(e.query_tensor).tolist(), np.asarray(e.response_tensor).tolist())
        for e in trainer.store.history
    ]


def test_serving_config_off_by_default():
    from trlx_tpu.data.configs import ServingConfig, TrainConfig

    assert ServingConfig().enabled is False
    assert TrainConfig(
        seq_length=8, epochs=1, total_steps=1, batch_size=2,
        checkpoint_interval=1, eval_interval=1, pipeline="PromptPipeline",
        trainer="PPOTrainer",
    ).serving.enabled is False


@pytest.mark.slow
def test_trainer_serving_rollout_parity(tmp_path, single_device_mesh):
    """`train.serving.enabled` must produce the identical rollout store the
    one-shot generate path produces (greedy, same seeds)."""
    from trlx_tpu.data.configs import ServingConfig

    t_off = _build_ppo(_tiny_ppo_config(tmp_path / "off"))
    t_off._resolve_serving()
    assert t_off._serving_client is None  # off by default
    t_off.make_experience(4, 0)
    ref = _store_dump(t_off)

    t_on = _build_ppo(_tiny_ppo_config(
        tmp_path / "on", serving=ServingConfig(enabled=True, num_slots=3, block_size=4)
    ))
    t_on._resolve_serving()
    assert t_on._serving_client is not None
    t_on.make_experience(4, 0)
    assert _store_dump(t_on) == ref
    assert t_on._serving_engine.allocator.blocks_in_use == 0
    t_on._serving_engine.allocator.check_invariants()


@pytest.mark.slow
def test_trainer_serving_quarantine_diversion(tmp_path, single_device_mesh):
    """With serving active, a corrupted scored element is still diverted by
    the experience quarantine at the post-assembly choke point: the store only
    receives clean elements and the engine keeps running."""
    from trlx_tpu.data.configs import SelfHealingConfig, ServingConfig
    from trlx_tpu.resilience.chaos import chaos

    config = _tiny_ppo_config(
        tmp_path, serving=ServingConfig(enabled=True, num_slots=3, block_size=4),
        self_healing=SelfHealingConfig(enabled=True),
    )
    trainer = _build_ppo(config)
    trainer._resolve_serving()
    assert trainer._serving_client is not None
    chaos.configure("bad-element:1")
    try:
        trainer.make_experience(4, 0)
    finally:
        chaos.configure("")
    assert trainer._quarantine is not None and trainer._quarantine.count == 1
    for e in trainer.store.history:
        assert np.isfinite(np.asarray(e.logprobs, np.float32)).all()
    # the serving engine is unaffected by the diversion: no leaked blocks
    assert trainer._serving_engine.allocator.blocks_in_use == 0
    trainer._serving_engine.allocator.check_invariants()


def test_serving_fallback_reasons(tmp_path, single_device_mesh):
    """Unsupported shapes fall back to the generate path with a warning, they
    never crash the run."""
    from trlx_tpu.data.configs import ServingConfig

    config = _tiny_ppo_config(
        tmp_path, serving=ServingConfig(enabled=True, num_slots=2)
    )
    config.method.gen_kwargs["num_beams"] = 2  # unsupported knob
    trainer = _build_ppo(config)
    trainer._resolve_serving()
    assert trainer._serving_client is None
