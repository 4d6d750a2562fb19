"""The keye_vl family's learner side and its surroundings at a small size on the
CPU: the PPO loss's gradient against the reference (the XLA path and the
kernels), softmax routing, the share of the experts and the passes of a long
prefill, the frozen indexer, the refusals, the sharding rules, the published
names. ``test_keye_vl.py`` holds the forward, the selection and the cache."""

import functools
import re
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark import reference as base
from benchmark.families import keye_vl as family
from keye_vl_tiny import tiny_config
from test_keye_vl import F32_ATOL, inputs, program
from trlx_tpu.models.presets import PRESETS, get_preset
from trlx_tpu.models.transformer import SparseMLP, TransformerLM
from trlx_tpu.ops import moe

reference = family.reference


@functools.lru_cache(maxsize=None)
def tiny_program():
    """``program(tiny_config())``, traced once a file."""
    return program(tiny_config())


def test_the_ppo_losss_gradient_matches_the_reference_leaf_by_leaf():
    """trlX's clipped PPO loss over the response window, differentiated through the
    program and through the reference: every leaf's gradient, entry by entry; the
    indexer's leaves take none in either. Through the kernels (interpreted), as the learner runs on the
    chip; float32: 1e-4 of a leaf's largest entry."""
    config = tiny_config(layers=1, topk=8)
    module, like = program(config, attention_impl="flash")
    weights = reference.init_weights(config, 11)
    params = harness.to_program_tree(family, weights, like, jnp.float32)
    P, R, B = 24, 8, 2
    ids, mask = inputs(5, B=B, T=P + R, pads=(3, 0))
    old_lp, old_v, _ = reference.response_window(weights, config, ids, mask, P, R)
    rng = np.random.default_rng(0)
    noise = lambda scale: jnp.asarray(rng.normal(size=(B, R)) * scale, jnp.float32)
    rewards = jnp.zeros((B, R)).at[:, -1].set(jnp.asarray(rng.uniform(0.1, 0.9, size=B), jnp.float32))
    rmask = jnp.ones((B, R), jnp.float32)
    adv, ret = base.gae(old_v + noise(0.05), rewards, rmask, 1.0, 0.95)
    old_lp, old_v = old_lp + noise(0.1), old_v + noise(0.05)
    hp = dict(cliprange=0.2, cliprange_value=0.2, vf_coef=1.0)

    def loss(logprobs, values):
        pg, vf = base.ppo_token_losses(logprobs, values, old_lp, old_v, adv, ret, rmask, hp)
        return (pg.sum() + vf.sum()) / rmask.sum()

    def of_program(p):
        logits, values, _, _ = module.apply({"params": p}, ids, mask)
        logprobs = jax.nn.log_softmax(logits[:, P - 1 : P - 1 + R].astype(jnp.float32), axis=-1)
        logprobs = jnp.take_along_axis(logprobs, ids[:, P : P + R, None], -1)[..., 0]
        return loss(logprobs, values[:, P - 1 : P - 1 + R])

    def of_reference(w):
        logprobs, values, _ = reference.response_window(w, config, ids, mask, P, R)
        return loss(logprobs, values)

    got = jax.jit(jax.grad(of_program))(params)
    want = harness.to_program_tree(family, jax.jit(jax.grad(of_reference))(weights), like, jnp.float32)
    for (path, a), (_, b) in zip(harness._paths(got), harness._paths(want)):
        scale = float(np.abs(np.asarray(b)).max())
        if "indexer" in path:
            assert scale == 0.0 and float(np.abs(np.asarray(a)).max()) == 0.0, path
            continue
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4 * scale, err_msg="/".join(path))


@functools.lru_cache(maxsize=None)
def _recomputed_layer(saves_selection: bool):
    """One learner microbatch's loss and gradient under ``remat="per_layer"``,
    through the kernels (interpreted), the collections the learner asks for
    included: (the ``dsa_select`` calls of the lowered program, the gradient).
    ``saves_selection`` False empties the names the layer's remat keeps, as the
    program was before it kept the packed selection."""
    from trlx_tpu.models import transformer

    saved = transformer._saved_names if saves_selection else (lambda config: ())
    module, like = program(tiny_config(topk=8), attention_impl="flash", remat="per_layer", num_experts=0)
    weights = jax.tree.map(lambda x: jax.random.normal(jax.random.PRNGKey(3), x.shape, x.dtype) * 0.05, like)
    ids, mask = inputs(6, B=2, T=40, pads=(3, 0))

    def loss(p):
        (logits, values, _, _), sown = module.apply({"params": p}, ids, mask, mutable=["dsa_stats"])
        return jax.nn.log_softmax(logits.astype(jnp.float32))[:, 20:].mean() + values.mean(), sown

    step = jax.jit(jax.grad(loss, has_aux=True))
    with mock.patch.object(transformer, "_saved_names", saved):  # the layers' remat is made as they are traced
        calls = len(re.findall(r"\bcall @dsa_select\w*\(", step.lower(weights).as_text()))
        return calls, step(weights)[0]


@pytest.mark.parametrize("saves_selection", [True, False], ids=["saved", "recomputed"])
def test_the_learner_selects_once_a_layer(saves_selection):
    """The packed selection kept across the layer's recomputation: the backward
    reads it and runs neither the indexer nor the selection kernel again, one
    ``dsa_select`` call a layer in the lowered program where it was two."""
    calls, _ = _recomputed_layer(saves_selection)
    assert calls == 2 * (1 if saves_selection else 2), calls


def test_the_kept_selection_gives_the_recomputed_gradient():
    """The gradient through the kept selection is the one through a selection
    made again, to float32 round-off: the same bits, the same attention."""
    _, kept = _recomputed_layer(True)
    _, again = _recomputed_layer(False)
    for (path, a), (_, b) in zip(harness._paths(kept), harness._paths(again)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=0, err_msg="/".join(path))
    assert any(float(np.abs(np.asarray(a)).max()) > 0 for _, a in harness._paths(kept))


@pytest.mark.parametrize("name", ["full", "per_layer", "nothing_saveable", "dots_saveable"])
def test_only_a_model_with_an_indexer_keeps_its_selection(name):
    """Without an indexer a remat policy is what it was (``None`` for ``full`` and
    ``per_layer``, jax's own policies else); with one, every policy but
    ``nothing_saveable`` also keeps the name the packed selection carries."""
    from trlx_tpu.models.transformer import _saved_names, remat_policy
    from trlx_tpu.ops.sparse_attention import SELECTION_NAME

    module, _ = tiny_program()
    plain = module.config.replace(index_topk=0)
    assert _saved_names(plain) == () and _saved_names(module.config) == (SELECTION_NAME,)
    before = {"full": None, "per_layer": None, "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
              "dots_saveable": jax.checkpoint_policies.dots_saveable}[name]
    assert remat_policy(name, _saved_names(plain)) is before
    kept = remat_policy(name, _saved_names(module.config))
    assert (kept is before) == (name == "nothing_saveable")


def test_softmax_routing_is_the_direct_computation():
    """Qwen3-MoE's rule: a softmax over all experts in float32, the top ``k``,
    their probabilities over their sum; the sigmoid rule is untouched."""
    h = jax.random.normal(jax.random.PRNGKey(6), (32, 64))
    kernel = jax.random.normal(jax.random.PRNGKey(7), (64, 16)) * 0.3
    chosen, weights = moe.route(h, kernel, None, top_k=4, norm_topk=True, scale=1.0, scoring="softmax")
    logits = np.asarray(h, np.float64) @ np.asarray(kernel, np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.argsort(-probs, axis=-1, kind="stable")[:, :4]
    np.testing.assert_array_equal(np.asarray(chosen), want)
    picked = np.take_along_axis(probs, want, -1)
    np.testing.assert_allclose(np.asarray(weights), picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    bias = jnp.zeros((16,))
    sig_chosen, sig_weights = moe.route(h, kernel, bias, top_k=4, norm_topk=True, scale=1.0)
    sig = 1 / (1 + np.exp(-logits))
    np.testing.assert_array_equal(np.asarray(sig_chosen), np.argsort(-sig, axis=-1, kind="stable")[:, :4])


def test_a_long_prefill_takes_passes_of_token_chunk_tokens():
    """One pass up to 32,768 tokens; beyond, passes of 16,384, the last padded:
    the cell's prefill of 16 x 8,192 takes 8, a row of a prime 40,009 takes 3."""
    assert moe.token_chunks(32768) == 1 and moe.token_chunks(16 * 7680) == 8
    assert moe.token_chunks(16 * 8192) == 8 and moe.token_chunks(40009) == 3


def test_the_programs_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 and 4-7 of the small model's 8, run as two shares, add up to
    the uncut reference's whole expert layer; a long prefill's tokens, taken in
    passes with the last padded, give what one pass gives, load and all."""
    whole = tiny_config()
    weights = reference.init_weights(whole, 11)
    s = reference.dims(whole)
    lw = {k[2:]: v[1] for k, v in weights.items() if k.startswith("h.")}
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 64))
    want = reference.experts(h, lw, s)
    total, loads = 0.0, []
    for offset in (0, 4):
        config = tiny_config(held=4, offset=offset)
        model_config = get_preset(family.MODEL_PATH, {
            **family.program_overrides(config), "param_dtype": jnp.float32, "compute_dtype": jnp.float32})
        params = {"router": {"kernel": lw["moe.router.w"]},
                  "experts": {n: lw[f"moe.experts.{n}"][offset : offset + 4] for n in ("gate", "up", "down")}}
        apply = jax.jit(lambda p, h: SparseMLP(model_config).apply({"params": p}, h, mutable=["moe_stats"]))
        out, sown = apply(params, h)
        total, loads = total + out, loads + [sown["moe_stats"]["load"][0]]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-6)
    with mock.patch.object(moe, "ONE_PASS_TOKENS", 4), mock.patch.object(moe, "TOKEN_CHUNK", 7):
        passes, sown_passes = jax.jit(apply.__wrapped__)(params, h)  # traced anew under the passes
    np.testing.assert_allclose(np.asarray(passes), np.asarray(out), atol=1e-6)
    # 18 tokens as three passes of 7, the last padded with 3 that go to no expert
    np.testing.assert_array_equal(sown_passes["moe_stats"]["load"][0], loads[-1])
    assert int(sum(x.sum() for x in loads)) == 2 * 9 * 2  # every assignment fell to one share


def test_the_indexers_leaves_take_no_update():
    """The trainers' freeze predicate labels every indexer leaf frozen: an
    optimizer step moves every other leaf and leaves these as they were."""
    import optax

    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    _, like = tiny_program()
    params = jax.tree.map(lambda x: jnp.ones(x.shape, x.dtype), like)
    trainer = object.__new__(PPOTrainer)
    trainer.config = mock.Mock()
    trainer.config.model.peft_config = None
    trainer.config.model.num_layers_unfrozen = -1
    labels = trainer._trainable_labels(params)
    tx = optax.multi_transform({"train": optax.adamw(1e-2, weight_decay=0.1), "freeze": optax.set_to_zero()}, labels)
    grads = jax.tree.map(jnp.ones_like, params)
    new = jax.jit(lambda p, g: optax.apply_updates(p, tx.update(g, tx.init(p), p)[0]))(params, grads)
    for path, leaf in harness._paths(new):
        moved = float(jnp.abs(leaf - 1.0).max())
        assert (moved == 0.0) == ("indexer" in path), (path, moved)


REFUSED = {
    "scan_layers": (dict(scan_layers=True, num_experts=0), "caches slot-major keys"),
    "pipeline": (dict(pipeline_stages=2, num_experts=0), "caches slot-major keys"),
    "kv_cache_quant": (dict(kv_cache_quant=True), "gathers slot-major rows"),
    "ring": (dict(attention_impl="ring"), "ranks each query's keys over the whole sequence"),
    "sequence_sharding": (dict(sequence_sharding=True), "ranks each query's keys over the whole sequence"),
    "prompt_tuning": (dict(peft_type="prompt", num_virtual_tokens=2), "the indexer"),
    "prefix_tuning": (dict(peft_type="prefix", num_virtual_tokens=2), "the indexer"),
    "latent_attention": (dict(attention_kind="mla"), "not on latent attention"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_layouts_not_run_yet_are_refused_with_the_reason(case):
    overrides, reason = REFUSED[case]
    with pytest.raises(ValueError, match=reason):
        program(tiny_config(), **overrides)


@pytest.mark.parametrize("entry", ["init_paged_cache", "paged_decode", "paged_verify"])
def test_the_paged_engine_is_refused_with_the_reason(entry):
    module, like = tiny_program()
    trunk = TransformerLM(module.config)
    reason = "serving/allocator.py"
    if entry == "init_paged_cache":
        with pytest.raises(ValueError, match=reason):
            trunk.init_paged_cache(8, 16, 4, 2)
        return
    cache = TransformerLM(module.config.replace(index_topk=0)).init_paged_cache(8, 16, 4, 2)
    with pytest.raises(ValueError, match=reason):
        trunk.apply({"params": like["transformer"]}, jnp.zeros((2, 1), jnp.int32), cache, method=getattr(trunk, entry))


def test_the_kernels_refuse_a_mesh_of_several_devices(mesh8):
    module, like = program(tiny_config(), attention_impl="flash", num_experts=0)
    params = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), like)
    ids, mask = inputs(1)
    with mesh8, pytest.raises(ValueError, match="one-device mesh only"):
        jax.eval_shape(lambda p: module.apply({"params": p}, ids, mask), params)


def test_sharding_rules_name_the_indexers_leaves(mesh8):
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.parallel.sharding import default_lm_rules, make_param_shardings

    _, like = tiny_program()
    indexer = make_param_shardings(like, mesh8)["transformer"]["layers_0"]["attn"]["indexer"]
    for name in ("wq", "wk", "weights_proj"):
        assert indexer[name]["kernel"].spec == P("fsdp", None), name
    rules = default_lm_rules()[:-1]  # by a rule of their own, not by the catch-all
    assert any(re.match(pattern, "transformer/layers_0/attn/indexer/wq/kernel") for pattern, _ in rules)


def test_a_published_state_dict_round_trips_through_the_programs_tree():
    """A made-up checkpoint under Qwen3-MoE's names (and the indexer's assumed
    ones) loads into the program's tree (a forward through it is the reference's
    on the same weights) and the exporter writes the same names and arrays back;
    the preset and the ``from_hf_config`` branch read the published keys."""
    from types import SimpleNamespace

    from trlx_tpu.models.hf_loading import hf_state_dict_to_params, make_hf_config, params_to_hf_state_dict
    from trlx_tpu.models.presets import from_hf_config

    config = tiny_config()
    module, like = tiny_program()
    w = {k: np.asarray(v) for k, v in reference.init_weights(config, 13).items()}
    w["h.idx.k_norm.b"] = w["h.idx.k_norm.b"] + 0.1  # told apart from zeros
    sd = {"model.embed_tokens.weight": w["wte"], "model.norm.weight": w["ln_f.g"], "lm_head.weight": w["head.w"].T}
    for i in range(2):
        pre, lw = f"model.layers.{i}", {k[2:]: v[i] for k, v in w.items() if k.startswith("h.")}
        sd[f"{pre}.input_layernorm.weight"], sd[f"{pre}.post_attention_layernorm.weight"] = lw["ln_1.g"], lw["ln_2.g"]
        for n in "qkvo":
            sd[f"{pre}.self_attn.{n}_proj.weight"] = lw[f"attn.{n}.w"].T
        sd[f"{pre}.self_attn.q_norm.weight"] = lw["attn.q_norm.g"]
        sd[f"{pre}.self_attn.k_norm.weight"] = lw["attn.k_norm.g"]
        for ours, theirs in (("q", "wq"), ("k", "wk"), ("w", "weights_proj")):
            sd[f"{pre}.self_attn.indexer.{theirs}.weight"] = lw[f"idx.{ours}.w"].T
        sd[f"{pre}.self_attn.indexer.k_norm.weight"] = lw["idx.k_norm.g"]
        sd[f"{pre}.self_attn.indexer.k_norm.bias"] = lw["idx.k_norm.b"]
        sd[f"{pre}.mlp.gate.weight"] = lw["moe.router.w"].T
        for e in range(8):
            for n in ("gate", "up", "down"):
                sd[f"{pre}.mlp.experts.{e}.{n}_proj.weight"] = lw[f"moe.experts.{n}"][e].T

    loaded = hf_state_dict_to_params("KeyeVL2", sd, module.config)
    assert jax.tree.map(np.shape, loaded) == jax.tree.map(lambda a: a.shape, like["transformer"])
    ids, mask = inputs(4, B=2, T=32, pads=(3, 0))
    logits = jax.jit(lambda p: TransformerLM(module.config).apply({"params": p}, ids, mask)[0])(loaded)
    want, _ = jax.jit(lambda w: reference.forward(w, config, ids, mask))({k: jnp.asarray(v) for k, v in w.items()})
    real = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(logits)[real], np.asarray(want)[real], atol=F32_ATOL)

    back = params_to_hf_state_dict("keye_vl", loaded, module.config)
    assert set(back) == set(sd)
    for name in sd:
        np.testing.assert_array_equal(back[name], sd[name], err_msg=name)
    share = module.config.replace(experts_held=4, expert_offset=4)
    half = hf_state_dict_to_params("KeyeVL2", sd, share)
    up = lambda tree: tree["layers_1"]["mlp"]["experts"]["up"]
    np.testing.assert_array_equal(up(half), up(loaded)[4:])
    assert "model.layers.1.mlp.experts.7.up_proj.weight" in params_to_hf_state_dict("KeyeVL2", half, share)

    preset = PRESETS["keye_vl"]
    hf = make_hf_config("keye_vl", preset)
    assert hf.model_type == "KeyeVL2" and hf.sa_config["topk"] == 2048 and hf.num_experts == 128
    assert from_hf_config(hf) == preset
    with pytest.raises(ValueError, match="use_sliding_window"):
        from_hf_config(SimpleNamespace(**{**hf.to_dict(), "use_sliding_window": True}))
    assert (preset.num_heads, preset.kv_heads, preset.dim_per_head, preset.index_rope_dim) == (32, 4, 128, 32)
    assert preset.router_scoring == "softmax" and not preset.tie_word_embeddings and preset.first_dense_layers == 0
    assert get_preset("Keye-VL-2.0-30B-A3B").num_layers == 48
