"""Pipeline parallelism: stacked-layer layout + GPipe schedule over the pipe axis.

The reference's PP is Apex's fwd/bwd microbatch engine driven from NeMo
(`modeling_nemo_ppo.py:713-731`); here it's a shard_map GPipe schedule over
``ppermute`` (trlx_tpu/parallel/pipeline.py). These tests check the stacked
param layout is exactly equivalent to the listed layout, and that the pipelined
forward/backward matches the plain model to float32 tolerance on real
multi-device meshes (which the reference's test suite cannot do at all —
SURVEY.md §4)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.models.presets import PRESETS
from trlx_tpu.models.transformer import TransformerLM
from trlx_tpu.parallel.mesh import make_mesh, put_batch
from trlx_tpu.parallel.pipeline import (
    pick_microbatches,
    stack_layer_params,
    unstack_layer_params,
)
from trlx_tpu.parallel.sharding import make_param_shardings

CFG = PRESETS["gpt2"].replace(
    vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
    intermediate_size=256, max_position_embeddings=64, compute_dtype=jnp.float32,
)
CFG_PP = CFG.replace(pipeline_stages=4, pipeline_microbatches=4)
B, T = 8, 16


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, CFG.vocab_size, (B, T)), jnp.int32)
    mask = np.ones((B, T), np.int32)
    mask[:, :3] = 0  # left padding
    mask = jnp.asarray(mask)
    m_list = TransformerLM(CFG)
    p_list = m_list.init(jax.random.PRNGKey(0), ids[:1], mask[:1])["params"]
    logits_ref, hidden_ref, _, _ = m_list.apply({"params": p_list}, ids, mask)
    p_stack = stack_layer_params(jax.device_get(p_list), CFG.num_layers)
    return ids, mask, m_list, p_list, logits_ref, hidden_ref, p_stack


def test_stacked_layout_matches_listed(setup):
    ids, mask, _, p_list, logits_ref, _, p_stack = setup
    m_pp = TransformerLM(CFG_PP)
    logits, _, _, _ = m_pp.apply({"params": p_stack}, ids, mask)
    assert float(jnp.max(jnp.abs(logits - logits_ref))) < 1e-5

    p_round = unstack_layer_params(p_stack, CFG.num_layers)
    ok = jax.tree.map(
        lambda a, b: np.allclose(np.asarray(a), np.asarray(b)), p_list, p_round
    )
    assert all(jax.tree.leaves(ok))


def test_pipelined_forward_matches(setup):
    ids, mask, _, _, logits_ref, _, p_stack = setup
    m_pp = TransformerLM(CFG_PP)
    mesh = make_mesh(data=2, fsdp=1, model=1, pipe=4)
    shardings = make_param_shardings({"transformer": p_stack}, mesh)["transformer"]
    p_dev = jax.tree.map(jax.device_put, p_stack, shardings)
    batch = put_batch(mesh, {"ids": np.asarray(ids), "mask": np.asarray(mask)})
    with mesh:
        logits = jax.jit(lambda p, i, m: m_pp.apply({"params": p}, i, m)[0])(
            p_dev, batch["ids"], batch["mask"]
        )
    assert float(jnp.max(jnp.abs(logits - logits_ref))) < 1e-4


def test_pipelined_composes_with_tp(setup):
    """pipe=2 × model=2 × data=2: PP composes with tensor parallelism (the
    reference's TPxPPxDP grid, nemo_ppo_trainer.py:344-346)."""
    ids, mask, _, _, logits_ref, _, p_stack = setup
    m_pp = TransformerLM(CFG.replace(pipeline_stages=2, pipeline_microbatches=2))
    mesh = make_mesh(data=2, fsdp=1, model=2, pipe=2)
    shardings = make_param_shardings({"transformer": p_stack}, mesh)["transformer"]
    p_dev = jax.tree.map(jax.device_put, p_stack, shardings)
    batch = put_batch(mesh, {"ids": np.asarray(ids), "mask": np.asarray(mask)})
    with mesh:
        logits = jax.jit(lambda p, i, m: m_pp.apply({"params": p}, i, m)[0])(
            p_dev, batch["ids"], batch["mask"]
        )
    assert float(jnp.max(jnp.abs(logits - logits_ref))) < 1e-4


def test_pipelined_grad_matches(setup):
    ids, mask, m_list, p_list, _, _, p_stack = setup
    m_pp = TransformerLM(CFG_PP)
    mesh = make_mesh(data=2, fsdp=1, model=1, pipe=4)
    shardings = make_param_shardings({"transformer": p_stack}, mesh)["transformer"]
    p_dev = jax.tree.map(jax.device_put, p_stack, shardings)

    def loss_list(p):
        lg, _, _, _ = m_list.apply({"params": p}, ids, mask)
        return jnp.mean((lg * mask[..., None]) ** 2)

    def loss_pp(p):
        lg, _, _, _ = m_pp.apply({"params": p}, ids, mask)
        return jnp.mean((lg * mask[..., None]) ** 2)

    g_ref = stack_layer_params(jax.device_get(jax.grad(loss_list)(p_list)), CFG.num_layers)
    with mesh:
        g_pp = jax.device_get(jax.jit(jax.grad(loss_pp))(p_dev))
    errs = jax.tree.map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))), g_ref, g_pp
    )
    assert max(jax.tree.leaves(errs)) < 1e-4


def test_stacked_cached_decode_matches(setup):
    """Generation path: stacked models run a sequential layer scan over the cache
    (prefill + decode steps) and must match the listed model exactly."""
    ids, mask, m_list, p_list, _, _, p_stack = setup
    m_pp = TransformerLM(CFG_PP)
    S = T + 2
    cache_l = m_list.init_cache(B, S)
    cache_s = m_pp.init_cache(B, S)

    def mask_at(extra):  # [B, S] validity over cache slots, `extra` decoded tokens
        m = np.concatenate(
            [np.asarray(mask), np.zeros((B, 2), np.asarray(mask).dtype)], axis=1
        )
        m[:, T : T + extra] = 1
        return jnp.asarray(m)

    lg_l, _, _, cache_l = m_list.apply({"params": p_list}, ids, mask_at(0), cache=cache_l)
    lg_s, _, _, cache_s = m_pp.apply({"params": p_stack}, ids, mask_at(0), cache=cache_s)
    np.testing.assert_allclose(np.asarray(lg_l), np.asarray(lg_s), atol=1e-5)

    tok = jnp.full((B, 1), 7, jnp.int32)
    for i in range(2):
        lg_l, _, _, cache_l = m_list.apply(
            {"params": p_list}, tok, mask_at(i + 1), cache=cache_l
        )
        lg_s, _, _, cache_s = m_pp.apply(
            {"params": p_stack}, tok, mask_at(i + 1), cache=cache_s
        )
        np.testing.assert_allclose(np.asarray(lg_l), np.asarray(lg_s), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(cache_l["k"]), np.asarray(cache_s["k"]), atol=1e-5
    )


def test_pipelined_cached_decode_matches_on_mesh(setup):
    """Cached decode under ``pipeline_stages > 1`` on a REAL multi-device mesh
    (data=2 x pipe=2 x model=2): the sequential layer scan streams every
    stage's param shards (transformer.py `_apply_stacked` cache branch), and
    its prefill + per-token logits must match the single-stage listed model.
    This path was once trusted single-device, untested multi-device."""
    ids, mask, m_list, p_list, _, _, p_stack = setup
    m_pp = TransformerLM(CFG.replace(pipeline_stages=2, pipeline_microbatches=2))
    mesh = make_mesh(data=2, fsdp=1, model=2, pipe=2)
    shardings = make_param_shardings({"transformer": p_stack}, mesh)["transformer"]
    p_dev = jax.tree.map(jax.device_put, p_stack, shardings)
    S = T + 2

    def mask_at(extra):
        m = np.concatenate(
            [np.asarray(mask), np.zeros((B, 2), np.asarray(mask).dtype)], axis=1
        )
        m[:, T : T + extra] = 1
        return m

    @jax.jit
    def prefill(p, i, m):
        cache = m_pp.init_cache(B, S)
        cache = {**cache, "index": 0}
        lg, _, _, cache = m_pp.apply({"params": p}, i, m, cache=cache)
        return lg, cache

    @jax.jit
    def decode(p, tok, m, cache):
        lg, _, _, cache = m_pp.apply({"params": p}, tok, m, cache=cache)
        return lg, cache

    # reference: the listed single-stage model, no mesh
    cache_l = m_list.init_cache(B, S)
    lg_ref, _, _, cache_l = m_list.apply(
        {"params": p_list}, ids, jnp.asarray(mask_at(0)), cache=cache_l
    )

    batch = put_batch(mesh, {"ids": np.asarray(ids), "mask": mask_at(0)})
    with mesh:
        lg, cache = prefill(p_dev, batch["ids"], batch["mask"])
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref), atol=1e-4)

    tok = jnp.full((B, 1), 7, jnp.int32)
    for i in range(2):
        lg_ref, _, _, cache_l = m_list.apply(
            {"params": p_list}, tok, jnp.asarray(mask_at(i + 1)), cache=cache_l
        )
        dbatch = put_batch(mesh, {"tok": np.asarray(tok), "mask": mask_at(i + 1)})
        with mesh:
            lg, cache = decode(p_dev, dbatch["tok"], dbatch["mask"], cache)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref), atol=1e-4)


def test_pipelined_bf16_forward_compiles(setup):
    """bf16 regression: XLA-CPU's AllReducePromotion pass crashed on the GPipe
    output psum in bf16 ('Invalid binary instruction opcode copy'); the psum now
    runs in f32."""
    ids, mask, _, _, _, _, p_stack = setup
    m_pp = TransformerLM(
        CFG.replace(pipeline_stages=2, pipeline_microbatches=2, compute_dtype=jnp.bfloat16)
    )
    mesh = make_mesh(data=2, fsdp=1, model=2, pipe=2)
    shardings = make_param_shardings({"transformer": p_stack}, mesh)["transformer"]
    p_dev = jax.tree.map(jax.device_put, p_stack, shardings)
    with mesh:
        logits = jax.jit(lambda p, i, m: m_pp.apply({"params": p}, i, m)[0])(
            p_dev, ids, mask
        )
    assert logits.dtype == jnp.bfloat16 or logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_scan_layers_matches_listed(setup):
    """scan_layers=True uses the stacked layout + lax.scan WITHOUT pipelining
    (O(1)-in-depth compile); numerics must match the listed model, including
    cached decode."""
    ids, mask, m_list, p_list, logits_ref, _, p_stack = setup
    m_scan = TransformerLM(CFG.replace(scan_layers=True))
    logits, _, _, _ = m_scan.apply({"params": p_stack}, ids, mask)
    assert float(jnp.max(jnp.abs(logits - logits_ref))) < 1e-5

    S = T + 1
    cache_l = m_list.init_cache(B, S)
    cache_s = m_scan.init_cache(B, S)
    full = jnp.pad(mask, ((0, 0), (0, 1)))
    lg_l, _, _, _ = m_list.apply({"params": p_list}, ids, full, cache=cache_l)
    lg_s, _, _, _ = m_scan.apply({"params": p_stack}, ids, full, cache=cache_s)
    np.testing.assert_allclose(np.asarray(lg_l), np.asarray(lg_s), atol=1e-5)


@pytest.mark.slow
def test_sft_trains_with_scan_layers(tmp_path):
    """End-to-end SFT with scan_layers through model_overrides (no pipe axis)."""
    import trlx_tpu
    from trlx_tpu.methods.sft import SFTConfig

    config = _trl_config(tmp_path, "SFTTrainer", SFTConfig(gen_kwargs=dict(max_new_tokens=4)))
    config.mesh.pipe = 1
    config.mesh.model = 2
    config.mesh.fsdp = 2
    config.model.model_overrides["scan_layers"] = True
    trainer = trlx_tpu.train(
        samples=["ab ab abab", "cd cdcd", "efgh ef", "a b a b"] * 2,
        eval_prompts=["ab", "cd"],
        config=config,
    )
    assert trainer.iter_count >= 3
    assert "layers_scan" in trainer.params["transformer"]


def test_pick_microbatches():
    assert pick_microbatches(8, 4) == 4
    assert pick_microbatches(6, 4) == 3
    assert pick_microbatches(7, 4) == 1
    assert pick_microbatches(2, 16) == 2


ALPHABET = "abcdefgh "


def _trl_config(tmp_path, trainer, method):
    from trlx_tpu.data.configs import (
        MeshConfig,
        ModelConfig,
        OptimizerConfig,
        SchedulerConfig,
        TokenizerConfig,
        TrainConfig,
        TRLConfig,
    )

    return TRLConfig(
        method=method,
        train=TrainConfig(
            seq_length=16, epochs=2, total_steps=3, batch_size=4, minibatch_size=2,
            checkpoint_interval=100, eval_interval=2,
            checkpoint_dir=str(tmp_path / "ckpts"),
            pipeline="PromptPipeline", trainer=trainer, tracker=None, seed=2,
        ),
        model=ModelConfig(
            model_path="gpt2", num_layers_unfrozen=-1,
            model_overrides=dict(
                vocab_size=len(ALPHABET) + 3, hidden_size=32, num_layers=2,
                num_heads=2, intermediate_size=64, max_position_embeddings=64,
            ),
        ),
        tokenizer=TokenizerConfig(tokenizer_path=f"char://{ALPHABET}"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(name="cosine_annealing", kwargs=dict(T_max=100, eta_min=1e-3)),
        mesh=MeshConfig(
            data=2, fsdp=1, pipe=2, model=2, compute_dtype="float32",
            pipeline_microbatches=2,
        ),
    )


@pytest.mark.slow
def test_sft_trains_on_pipe_mesh(tmp_path):
    """End-to-end SFT on a data×pipe×model mesh (TPxPPxDP grid parity:
    nemo_sft_trainer + megatron_trainer, nemo_ilql_trainer.py:31-82)."""
    import trlx_tpu
    from trlx_tpu.methods.sft import SFTConfig

    config = _trl_config(tmp_path, "SFTTrainer", SFTConfig(gen_kwargs=dict(max_new_tokens=4)))
    trainer = trlx_tpu.train(
        samples=["ab ab abab", "cd cdcd", "efgh ef", "a b a b"] * 2,
        eval_prompts=["ab", "cd"],
        config=config,
    )
    assert trainer.iter_count >= 3
    assert trainer.model_config.pipeline_stages == 2
    assert "layers_scan" in trainer.params["transformer"]


@pytest.mark.slow
def test_ppo_trains_on_pipe_mesh(tmp_path):
    """End-to-end PPO (rollout generation through the stacked decode path + a
    pipelined train step with the full-copy reference model)."""
    import trlx_tpu
    from trlx_tpu.methods.ppo import PPOConfig

    config = _trl_config(
        tmp_path, "PPOTrainer",
        PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=1, init_kl_coef=0.01, target=None,
            gen_kwargs=dict(max_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
        ),
    )
    trainer = trlx_tpu.train(
        reward_fn=lambda samples, **kw: [float(s.count("a")) for s in samples],
        prompts=["ab", "cd ef", "gh", "a b c"] * 2,
        eval_prompts=["ab", "cd"],
        config=config,
    )
    assert trainer.iter_count >= 3
    assert trainer.ref_params is not None  # full-copy reference under PP


def test_pipe_rejects_partial_freeze(tmp_path):
    from trlx_tpu.methods.sft import SFTConfig
    from trlx_tpu.utils.loading import get_trainer

    config = _trl_config(tmp_path, "SFTTrainer", SFTConfig())
    config.model.num_layers_unfrozen = 1
    with pytest.raises(ValueError, match="num_layers_unfrozen"):
        get_trainer("SFTTrainer")(config=config)


def test_config_validation():
    with pytest.raises(ValueError):
        TransformerLM(CFG.replace(pipeline_stages=3)).init(
            jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32)
        )
    with pytest.raises(ValueError):
        TransformerLM(CFG.replace(pipeline_stages=2, attention_impl="ring")).init(
            jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32)
        )
