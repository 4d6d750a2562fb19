"""The vocabulary head over the response window only (``utils.modeling.response_logprobs``).

Parity: its values and gradients are those of the spelling it replaced,
``logprobs_of_labels(logits[:, :-1], tokens[:, 1:])[:, P-1 : P-1+R]`` over the
logits of every position. Structure: the learner's and the scorer's programs
hold no ``[·, P+R, V]`` array, the learner's backward keeps no float32 array as
wide as the vocabulary, and the two gauges say how much of the forward's rows
the head was taken over. The scorer's shapes: each axis pads to its rung of the
length ladder and no further than the longest length the configuration allows,
and the positions that takes away reach no stored number."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import trlx_tpu
from trlx_tpu.data.configs import (
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    SchedulerConfig,
    TokenizerConfig,
    TrainConfig,
    TRLConfig,
)
from trlx_tpu.data.ppo_types import PPORLBatch
from trlx_tpu.methods.ppo import PPOConfig
from trlx_tpu.models.policy import (
    CausalLMWithValueHead,
    branch_param_subtree,
    head_of,
)
from trlx_tpu.models.presets import PRESETS
from trlx_tpu.models.transformer import TransformerLM
from trlx_tpu.utils.metrics import gauges
from trlx_tpu.utils.modeling import logprobs_of_labels, response_logprobs

V, D = 97, 32


def tiny(**overrides):
    return PRESETS["gpt2"].replace(
        vocab_size=V, hidden_size=D, num_layers=2, num_heads=4, intermediate_size=64,
        max_position_embeddings=64, param_dtype=jnp.float32, **overrides,
    )


def as_it_was(logits, tokens, P, R):
    """``next_token_logprobs(logits, tokens)[:, P-1 : P-1+R]``, spelled out."""
    return logprobs_of_labels(logits[:, :-1], tokens[:, 1:])[:, P - 1 : P - 1 + R]


def tokens_of(B, T, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0, V)


def close(got, want, dtype, scale=None):
    """Equal to float32 rounding; in bfloat16 the gradients pass through bfloat16 products, so a
    rounding of the operand that differs by one float32 ulp may move a bfloat16 one."""
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    scale = scale or max(float(jnp.abs(want).max()), 1e-6)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol * scale)


def close_trees(got, want, dtype):
    """Leaf by leaf, rounding measured against the largest gradient of the tree: a leaf whose
    gradient is zero by construction (a key bias) holds rounding and nothing else."""
    scale = max(float(jnp.abs(leaf).max()) for leaf in jax.tree.leaves(want))
    jax.tree.map(lambda a, b: close(a, b, dtype, scale), got, want)


HEADS = {
    "tied-float32": dict(tie_word_embeddings=True, compute_dtype=jnp.float32),
    "tied-bfloat16": dict(tie_word_embeddings=True, compute_dtype=jnp.bfloat16),
    "untied_with_bias-float32": dict(tie_word_embeddings=False, head_bias=True, compute_dtype=jnp.float32),
    "untied_with_bias-bfloat16": dict(tie_word_embeddings=False, head_bias=True, compute_dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("P,R", [(5, 6), (1, 7), (8, 1)], ids=["P5-R6", "P1", "R1"])
@pytest.mark.parametrize("case", sorted(HEADS))
def test_values_and_gradients_from_hidden_states_are_the_full_head_s(case, P, R):
    """The function itself: gradients with respect to the hidden states and the head's weights."""
    config = tiny(**HEADS[case])
    dtype = config.compute_dtype
    model = TransformerLM(config)
    B, T = 3, P + R
    tokens = tokens_of(B, T)
    params = model.init(jax.random.PRNGKey(0), tokens, jnp.ones_like(tokens))["params"]
    if config.head_bias:  # a bias that is drawn, not the zeros of init
        params["lm_head"]["bias"] = jax.random.normal(jax.random.PRNGKey(5), (V,))
    head_params = {k: params[k] for k in ("embed_tokens", "lm_head") if k in params}
    hidden = jax.random.normal(jax.random.PRNGKey(2), (B, T, D)).astype(dtype)
    weights = jax.random.normal(jax.random.PRNGKey(3), (B, R))

    def window(hidden, head_params):
        return response_logprobs(hidden, head_of(model, head_params), tokens, P - 1, R)

    def every_row(hidden, head_params):
        return as_it_was(head_of(model, head_params)(hidden), tokens, P, R)

    got, want = window(hidden, head_params), every_row(hidden, head_params)
    assert got.shape == (B, R) and got.dtype == jnp.float32
    close(got, want, jnp.float32)  # the same rows through the same products: rounding of the sum at most
    grads = [
        jax.grad(lambda h, p: (fn(h, p) * weights).sum(), argnums=(0, 1))(hidden, head_params)
        for fn in (window, every_row)
    ]
    assert grads[0][0].dtype == dtype
    # rows outside the window take no gradient; the backward pads [B, R, d] to [B, T, d]
    outside = np.ones(T, bool)
    outside[P - 1 : P - 1 + R] = False
    assert not np.asarray(grads[0][0], np.float32)[:, outside].any()
    close_trees(grads[0], grads[1], dtype)


def _policy(config, B, T):
    model = CausalLMWithValueHead(config)
    tokens = tokens_of(B, T)
    mask = jnp.ones_like(tokens).at[0, :2].set(0)  # one row left-padded
    params = model.init(jax.random.PRNGKey(0), tokens, mask)["params"]
    return model, params, tokens, mask


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_policy_forward_without_the_head_then_the_window(dtype):
    P, R = 5, 6
    model, params, tokens, mask = _policy(tiny(compute_dtype=dtype), 2, P + R)

    def window(params):
        hidden, values, _, _ = model.apply({"params": params}, tokens, mask, with_head=False)
        return response_logprobs(hidden, head_of(model, params), tokens, P - 1, R), values

    def every_row(params):
        logits, values, _, _ = model.apply({"params": params}, tokens, mask)
        return as_it_was(logits, tokens, P, R), values

    (got, got_values), (want, want_values) = window(params), every_row(params)
    close(got, want, jnp.float32)
    np.testing.assert_array_equal(np.asarray(got_values), np.asarray(want_values))
    grads = [jax.grad(lambda p: fn(p)[0].sum())(params) for fn in (window, every_row)]
    close_trees(grads[0], grads[1], dtype)


def test_prompt_tuning_s_virtual_rows_are_dropped_before_the_window():
    """The forward prepends ``num_virtual_tokens`` rows; the hidden states come back without them,
    so the window's rows are the tokens' own."""
    P, R = 4, 5
    config = tiny(compute_dtype=jnp.float32, peft_type="prompt", num_virtual_tokens=3)
    model, params, tokens, mask = _policy(config, 2, P + R)
    hidden, _, _, _ = model.apply({"params": params}, tokens, mask, with_head=False)
    logits, _, _, _ = model.apply({"params": params}, tokens, mask)
    assert hidden.shape == (2, P + R, D) and logits.shape == (2, P + R, V)
    got = response_logprobs(hidden, head_of(model, params), tokens, P - 1, R)
    close(got, as_it_was(logits, tokens, P, R), jnp.float32)

    def loss(params, windowed):
        if windowed:
            hidden, _, _, _ = model.apply({"params": params}, tokens, mask, with_head=False)
            return response_logprobs(hidden, head_of(model, params), tokens, P - 1, R).sum()
        return as_it_was(model.apply({"params": params}, tokens, mask)[0], tokens, P, R).sum()

    close_trees(jax.grad(loss)(params, True), jax.grad(loss)(params, False), jnp.float32)


def test_hydra_branch_hands_over_hidden_states_and_its_own_head():
    P, R, start = 5, 6, 1
    config = tiny(compute_dtype=jnp.float32)
    model, params, tokens, mask = _policy(config, 2, P + R)
    trunk = TransformerLM(config)
    branch = branch_param_subtree(params["transformer"], start, config)
    branch = jax.tree.map(lambda x: x * 1.01, branch)  # a reference that is not the policy
    _, _, branch_hidden, _ = model.apply({"params": params}, tokens, mask, branch_layer=start, with_head=False)
    ref_logits = model.apply(
        {"params": {"transformer": branch}}, branch_hidden, mask, None, start, method=model.forward_branch
    )
    ref_hidden = model.apply(
        {"params": {"transformer": branch}}, branch_hidden, mask, None, start, with_head=False,
        method=model.forward_branch,
    )
    assert ref_hidden.shape == (2, P + R, D)
    got = response_logprobs(ref_hidden, head_of(trunk, branch), tokens, P - 1, R)
    close(got, as_it_was(ref_logits, tokens, P, R), jnp.float32)


# ------------------------------------------------------------------ structure

ALPHABET = "abcdefgh "
B_, P_, R_ = 4, 8, 8  # prompt bucket 8; a response bucket of 8 for the programs lowered by hand


def ppo_config(tmp_path, seq_length=16, max_new_tokens=6, compute_dtype="bfloat16", **model):
    model = model or dict(
        model_path="gpt2", num_layers_unfrozen=-1,
        model_overrides=dict(vocab_size=len(ALPHABET) + 3, hidden_size=32, num_layers=2, num_heads=2,
                             intermediate_size=64, max_position_embeddings=64),
    )
    return TRLConfig(
        method=PPOConfig(
            num_rollouts=B_, chunk_size=B_, ppo_epochs=1, init_kl_coef=0.01, target=None,
            gen_kwargs=dict(max_new_tokens=max_new_tokens, min_new_tokens=max_new_tokens, do_sample=True,
                            top_k=0, top_p=1.0),
        ),
        train=TrainConfig(
            seq_length=seq_length, epochs=1, total_steps=1, batch_size=B_, minibatch_size=B_ // 2,
            checkpoint_interval=10 ** 9, eval_interval=10 ** 9, checkpoint_dir=str(tmp_path / "ckpts"),
            pipeline="PromptPipeline", trainer="PPOTrainer", tracker=None, seed=2,
        ),
        model=ModelConfig(**model),
        tokenizer=TokenizerConfig(tokenizer_path=f"char://{ALPHABET}"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(lr=1e-3)),
        scheduler=SchedulerConfig(name="cosine_annealing", kwargs=dict(T_max=100, eta_min=1e-3)),
        mesh=MeshConfig(data=1, fsdp=1, model=1, compute_dtype=compute_dtype),
    )


def _mesh_of_one_device(monkeypatch):
    """The session has 8 virtual devices; the cells' mesh is one chip's."""
    from trlx_tpu.parallel import mesh as mesh_lib

    build = mesh_lib.mesh_from_config
    monkeypatch.setattr(mesh_lib, "mesh_from_config", lambda c: build(c, devices=jax.devices()[:1]))


@pytest.fixture
def one_device(monkeypatch):
    _mesh_of_one_device(monkeypatch)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One tiny PPO run through ``trlx_tpu.train()``: one experience phase, one optimizer step."""
    gauges.clear("learn/")
    gauges.clear("score/")
    config = ppo_config(tmp_path_factory.mktemp("ppo"))
    with pytest.MonkeyPatch.context() as patch:
        _mesh_of_one_device(patch)
        trainer = trlx_tpu.train(
            reward_fn=lambda samples, **kwargs: [float(s.count("a")) for s in samples],
            prompts=["abcdefgh", "hgfedcba", "aabbccdd", "a b c d "], eval_prompts=["abcdefgh"], config=config,
        )
    assert trainer.iter_count == 1
    return trainer, dict(gauges.snapshot("learn/"), **gauges.snapshot("score/"))


def test_gauges_after_a_tiny_ppo_run_give_the_window_s_share(trained):
    """Rows the head is taken over ÷ rows the forward runs, of the shapes the run compiled: the
    scorer's responses are padded to their bucket, which stops at the longest response the
    configuration allows (6 new tokens and the re-appended eos), the store's to the longest."""
    trainer, read = trained
    ((_, P, R),), ((_, score_P, score_R),) = trainer._train_steps, trainer._score_fns
    assert (P, score_P) == (P_, P_) and R == score_R == 7
    assert read == {
        "learn/head_rows_share": R / (P + R),
        "score/head_rows_share": score_R / (score_P + score_R),
        "score/padded_positions_share": 0.0,
    }


def test_gauges_read_one_for_seq2seq(tmp_path, one_device):
    """The decoder's positions are the response already: the head is taken over every one."""
    config = ppo_config(
        tmp_path, model_path="t5", model_arch_type="seq2seq", num_layers_unfrozen=-1,
        model_overrides=dict(vocab_size=len(ALPHABET) + 3, d_model=32, d_kv=8, d_ff=64, num_layers=2,
                             num_decoder_layers=2, num_heads=4, relative_attention_num_buckets=8,
                             decoder_start_token_id=1),
    )
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    trainer = PPOTrainer(config=config, reward_fn=lambda samples, **kwargs: [0.0] * len(samples))
    trainer.num_mb = 2
    gauges.clear("learn/")
    gauges.clear("score/")
    trainer._get_score_fn(B_, P_, R_)
    trainer._get_train_step(B_, P_, R_)
    assert gauges.snapshot("learn/") == {"learn/head_rows_share": 1.0}
    assert gauges.snapshot("score/") == {"score/head_rows_share": 1.0}


def _abstract(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _batch(trainer):
    ints, floats = jnp.zeros((B_, P_), jnp.int32), jnp.zeros((B_, R_), jnp.float32)
    return PPORLBatch(
        query_tensors=ints, response_tensors=jnp.zeros((B_, R_), jnp.int32), logprobs=floats, values=floats,
        rewards=floats, attention_mask=jnp.ones_like(ints), response_mask=jnp.ones((B_, R_), jnp.int32),
    )


def _arrays_as_wide_as_the_sequence_and_the_vocabulary(text, vocab):
    """Tensor types ``…x<P+R>x<V>x<dtype>`` (and one row short) of a lowered program's text."""
    import re

    return sorted(set(re.findall(rf"tensor<(?:\d+x)*(?:{P_ + R_}|{P_ + R_ - 1})x{vocab}x\w+>", text)))


@pytest.mark.parametrize("program", ["jit_ppo_train_step", "jit_ppo_score"])
def test_lowered_program_holds_no_array_over_every_position_and_the_vocabulary(trained, program):
    trainer, _ = trained
    vocab = trainer.model_config.vocab_size
    with trainer.mesh:
        if program == "jit_ppo_train_step":
            lowered = trainer._get_train_step(B_, P_, R_).lower(
                _abstract(trainer.params), _abstract(trainer.opt_state), _batch(trainer))
        else:
            seq = jnp.zeros((B_, P_ + R_), jnp.int32)
            lowered = trainer._get_score_fn(B_, P_, R_).lower(
                _abstract(trainer.params), _abstract(trainer.ref_params), None, seq, jnp.ones_like(seq))
    text = lowered.as_text()
    assert program in text  # the name the profiler's module events read
    assert f"x{R_}x{vocab}xbf16>" in text  # the window's logits are there, in the compute dtype
    assert _arrays_as_wide_as_the_sequence_and_the_vocabulary(text, vocab) == []


def test_learner_s_backward_keeps_no_float32_array_as_wide_as_the_vocabulary(trained, monkeypatch, capsys):
    import re

    from jax.ad_checkpoint import print_saved_residuals

    trainer, _ = trained
    vocab = trainer.model_config.vocab_size
    # the step builder hands make_grad_accum_step the loss function: take it from there
    monkeypatch.setattr(trainer, "make_grad_accum_step", lambda loss_fn, num_mb, name: loss_fn)
    monkeypatch.setattr(trainer, "_train_steps", {})
    loss_fn = trainer._get_train_step(B_, P_, R_)
    half = jax.tree.map(lambda x: x[: B_ // 2], _batch(trainer))
    capsys.readouterr()
    print_saved_residuals(lambda params: loss_fn(params, half)[0], trainer.params)
    kept = re.findall(r"^(\w+)\[([\d,]*)\]", capsys.readouterr().out, re.M)
    assert len(kept) > 20, kept  # the trunk's residuals are listed too
    wide = [(dtype, shape) for dtype, shape in kept if shape.split(",")[-1] == str(vocab)]
    # the window's logits as the head gave them (beside the head's weights in the compute dtype)
    assert ("bf16", f"{B_ // 2},{R_},{vocab}") in wide, wide
    assert all(dtype == "bf16" for dtype, _ in wide), wide


# ------------------------------------------------------- the scorer's shapes

SEQ, NEW = 24, 8  # prompts are cut to 16 tokens; a response is 8 tokens and the re-appended eos


@pytest.fixture(scope="module")
def scorer(tmp_path_factory):
    """A float32 trainer whose scoring forward is called by hand, with a recorder of the shapes it asks for."""
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    config = ppo_config(tmp_path_factory.mktemp("scorer"), seq_length=SEQ, max_new_tokens=NEW, compute_dtype="float32")
    with pytest.MonkeyPatch.context() as patch:
        _mesh_of_one_device(patch)
        trainer = PPOTrainer(config=config, reward_fn=lambda samples, **kwargs: [0.0] * len(samples))
    keys = []
    build = trainer._get_score_fn
    trainer._get_score_fn = lambda B, P, R, **kw: keys.append((B, P, R)) or build(B, P, R, **kw)
    return trainer, keys


def _chunk(longest_p, longest_r):
    """Four rows of different lengths, the longest prompt and response as given (token ids drawn from the seed)."""
    rng = np.random.default_rng(longest_p * 100 + longest_r)
    p_lens = [longest_p, max(1, longest_p - 3), max(1, longest_p - 1), 1]
    r_lens = [max(1, longest_r - 2), longest_r, 1, max(1, longest_r - 1)]
    prompts = [rng.integers(3, len(ALPHABET) + 3, n).tolist() for n in p_lens]
    responses = [rng.integers(3, len(ALPHABET) + 3, n).astype(np.int32) for n in r_lens]
    return prompts, responses


def _score(trainer, chunk):
    elements = []
    gauges.clear("score/")
    trainer._score_and_store(chunk, [0.5, -1.0, 2.0, 0.25], elements, [], [])
    return elements, gauges.snapshot("score/")["score/padded_positions_share"]


@pytest.mark.parametrize(
    "longest_p,longest_r,key",
    [(16, NEW + 1, (16, NEW + 1)), (16, 5, (16, 8)), (20, NEW + 1, (32, NEW + 1))],
    ids=["fixed_length_stops_at_the_caps", "response_below_the_cap_s_rung_keeps_the_rung",
         "prompt_past_its_cap_pads_to_the_rung"],
)
def test_score_shape_is_the_rung_stopped_at_the_configuration_s_caps(scorer, longest_p, longest_r, key):
    """``seq_length`` 24 and ``max_new_tokens`` 8 cap the prompt at 16 and the response at 9: a chunk that
    reaches a cap is scored at it, not at the next rung (16); one that stops below the cap's rung keeps the
    rung; a prompt longer than its cap (a caller that did not truncate) pads to the rung and is never cut."""
    trainer, keys = scorer
    chunk = _chunk(longest_p, longest_r)
    keys.clear()
    elements, padded_share = _score(trainer, chunk)
    assert keys == [(4, *key)]
    assert padded_share == pytest.approx(1.0 - (longest_p + longest_r) / sum(key))
    for e, prompt, response in zip(elements, *chunk):
        np.testing.assert_array_equal(e.query_tensor, prompt)
        np.testing.assert_array_equal(e.response_tensor, response)
        assert e.logprobs.shape == e.values.shape == e.rewards.shape == response.shape
        assert np.isfinite(e.logprobs).all() and np.isfinite(e.values).all()


@pytest.mark.parametrize("caps", [(16, 9), (12, 9), (20, 33), (8, 8)], ids=lambda c: f"caps{c[0]}x{c[1]}")
def test_capped_ladder_gives_no_more_score_shapes_than_its_rungs(caps):
    """Over every length the caps allow, the capped ladder's (P, R) keys are no more than the rungs'."""
    from trlx_tpu.ops.generation import LENGTH_BUCKETS, pad_to_bucket

    lengths = [(p, r) for p in range(1, caps[0] + 1) for r in range(1, caps[1] + 1)]
    capped = {tuple(pad_to_bucket(n, LENGTH_BUCKETS, cap=c) for n, c in zip(pr, caps)) for pr in lengths}
    rungs = {tuple(pad_to_bucket(n, LENGTH_BUCKETS) for n in pr) for pr in lengths}
    assert len(capped) <= len(rungs)
    assert all(p <= caps[0] and r <= caps[1] for p, r in capped)


def test_stored_experience_at_the_capped_shape_is_the_rung_shape_s(scorer, monkeypatch):
    """The positions the cap takes away hold no token: the stored log-probabilities, values and KL-penalised
    rewards are those of today's rung shape on the same rows (float32)."""
    trainer, keys = scorer
    chunk = _chunk(16, NEW + 1)
    keys.clear()
    capped, capped_share = _score(trainer, chunk)
    monkeypatch.setattr(trainer, "_length_caps", lambda: (None, None))
    rung, rung_share = _score(trainer, chunk)
    assert keys == [(4, 16, NEW + 1), (4, 16, 16)]
    assert capped_share == 0.0 and rung_share == pytest.approx(1.0 - 25 / 32)
    for a, b in zip(capped, rung):
        for name in ("logprobs", "values", "rewards"):
            np.testing.assert_allclose(getattr(a, name), getattr(b, name), rtol=1e-5, atol=1e-6, err_msg=name)
