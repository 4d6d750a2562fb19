"""The kimi_vl family on the program's normal path against its plain reference
(``benchmark/reference_kimi_vl.py``) at a small size: latent attention through
the flash kernels and through the latent cache, routed and shared experts as a
share of the layer, the PPO step. Seeded random weights, CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.families import kimi_vl as family
from kimi_vl_tiny import tiny_config
from trlx_tpu.models.policy import CausalLMWithValueHead
from trlx_tpu.models.presets import get_preset
from trlx_tpu.models.transformer import SparseMLP, TransformerLM
from trlx_tpu.ops import moe

reference = family.reference


def program(config, compute_dtype=jnp.float32, **overrides):
    """(the policy module at the configuration's sizes, its parameter shapes)."""
    model_config = get_preset(family.MODEL_PATH, {
        **family.program_overrides(config), "param_dtype": jnp.float32, "compute_dtype": compute_dtype, **overrides})
    module = CausalLMWithValueHead(model_config)
    like = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2), jnp.int32))
    )["params"]
    return module, like


def inputs(seed, B=4, T=48, pad=5):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 3, 300)
    return ids, jnp.ones((B, T), jnp.int32).at[0, :pad].set(0)  # row 0 left-padded


def test_reference_covers_every_program_leaf():
    config = tiny_config()
    _, like = program(config)
    weights = reference.init_weights(config, 7)
    tree = harness.to_program_tree(family, weights, like, jnp.float32)
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(lambda a: a.shape, like)
    assert {family.leaf_name(path)[0] for path, _ in harness._paths(like)} == set(weights)


@pytest.mark.parametrize("attention_impl", ["xla", "flash"])
def test_float32_logits_and_values_match_the_reference(attention_impl):
    """float32 compute against the float32 reference: the same arithmetic in
    another order (sorted grouped products against every expert on every token,
    flash tiles against one softmax), so float32 round-off alone: 1e-5 on
    logits of size 0.6."""
    config = tiny_config()
    module, like = program(config, attention_impl=attention_impl)
    weights = reference.init_weights(config, 7)
    params = harness.to_program_tree(family, weights, like, jnp.float32)
    ids, mask = inputs(1)
    logits, values, _, _ = jax.jit(lambda p, i, m: module.apply({"params": p}, i, m))(params, ids, mask)
    want_logits, want_values = jax.jit(lambda w, i, m: reference.forward(w, config, i, m))(weights, ids, mask)
    real = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(logits)[real], np.asarray(want_logits)[real], atol=1e-5)
    np.testing.assert_allclose(np.asarray(values)[real], np.asarray(want_values)[real], atol=1e-5)


#: the experts' outputs times 8, so that an expert chosen wrongly shows over bfloat16's rounding
AMPLIFIED = 8.0
#: mean |logit gap| under bfloat16 compute with the router in float32: 0.0008 to 0.0018 over
#: seeds 7..11 (rounding, and the few tokens whose 4th and 5th scores the rounding upstream of
#: the router swaps); with the router's matmul, sigmoid and weights in bfloat16: 0.0053 to 0.0080
BF16_MEAN_LIMIT = 0.0035
#: the largest gap is one such swapped token's: up to 0.17 in those seeds, either way
BF16_MAX_LIMIT = 0.35


@pytest.mark.parametrize("seed", [10])
def test_bfloat16_compute_logits_stay_near_the_reference(seed):
    """Fails if the router is computed in bfloat16: rounded scores choose other
    experts for several tokens in a hundred, and the mean gap triples."""
    config = tiny_config()
    module, like = program(config, jnp.bfloat16)
    weights = dict(reference.init_weights(config, seed))
    weights["h.moe.experts.down"] = weights["h.moe.experts.down"] * AMPLIFIED
    params = harness.to_program_tree(family, weights, like, jnp.float32)
    ids, mask = inputs(seed)
    logits = jax.jit(lambda p, i, m: module.apply({"params": p}, i, m)[0])(params, ids, mask).astype(jnp.float32)
    want, _ = jax.jit(lambda w, i, m: reference.forward(w, config, i, m))(weights, ids, mask)
    gap = np.abs(np.asarray(logits) - np.asarray(want))[np.asarray(mask) > 0]
    assert gap.mean() < BF16_MEAN_LIMIT and gap.max() < BF16_MAX_LIMIT, (gap.mean(), gap.max())


@pytest.mark.parametrize("attention_impl", ["xla", "flash"])
def test_prefill_then_decode_through_the_latent_cache(attention_impl):
    """The prompt through the prefill (flash over expanded keys and values, or
    the absorbed form over the cache), then token by token over the latent
    cache in the absorbed form: the logits at every position against the
    reference's one full forward."""
    config = tiny_config()
    module, like = program(config, attention_impl=attention_impl)
    trunk = TransformerLM(module.config)
    weights = reference.init_weights(config, 3)
    params = harness.to_program_tree(family, weights, like, jnp.float32)["transformer"]
    P, N = 20, 6
    ids, mask = inputs(2, B=3, T=P + N, pad=4)
    want, _ = reference.forward(weights, config, ids, mask)

    cache = {**trunk.init_cache(3, P + N), "index": 0}
    assert set(cache) == {"c", "k_rope", "index"} and cache["c"][0].shape == (3, P + N, 32)
    assert cache["k_rope"][1].shape == (3, P + N, 8) and len(cache["c"]) == 3
    seen = mask.at[:, P:].set(0)
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None)
    prefill = jax.jit(lambda p, i, m, pos, c: trunk.apply({"params": p}, i, m, pos, {**c, "index": 0}))
    step = jax.jit(lambda p, i, m, pos, c: trunk.apply({"params": p}, i, m, pos, c))
    cache.pop("index")  # a concrete 0 inside the jitted prefill, as generate() gives it
    logits, _, _, cache = prefill(params, ids[:, :P], seen, positions[:, :P], cache)
    got = [logits]
    for t in range(P, P + N):
        seen = seen.at[:, t].set(1)
        logits, _, _, cache = step(params, ids[:, t : t + 1], seen, positions[:, t : t + 1], cache)
        got.append(logits)
    got = np.asarray(jnp.concatenate(got, axis=1))
    real = np.asarray(mask) > 0
    np.testing.assert_allclose(got[real], np.asarray(want)[real], atol=2e-5)


def _sparse_layer(config, weights, layer, h):
    """The program's expert FFN on ``h`` with the reference's layer weights
    (the held experts' slice), and the loads it sowed."""
    model_config = get_preset(family.MODEL_PATH, {
        **family.program_overrides(config), "param_dtype": jnp.float32, "compute_dtype": jnp.float32})
    s = reference.dims(config)
    lw = {k[2:]: v[layer] for k, v in weights.items() if k.startswith("h.moe.")}
    held = slice(s["offset"], s["offset"] + s["held"])
    params = {
        "router": {"kernel": lw["moe.router.w"], "bias": lw["moe.router.b"]},
        "experts": {n: lw[f"moe.experts.{n}"][held] for n in ("gate", "up", "down")},
        "shared": {f"{n}_proj": {"kernel": lw[f"moe.shared.{n}.w"]} for n in ("gate", "up", "down")},
    }
    out, sown = SparseMLP(model_config).apply({"params": params}, h, mutable=["moe_stats"])
    return out, sown["moe_stats"]["load"][0], lw


def test_the_programs_shares_add_up_to_the_uncut_layer():
    """16 experts as two shares of 8: what the two shares' layers give, the
    shared experts counted once, is the uncut reference's layer output."""
    whole = tiny_config()
    weights = reference.init_weights(whole, 11)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 64))
    lw = {k[2:]: v[1] for k, v in weights.items() if k.startswith("h.moe.")}
    routed, shared = reference.moe_parts(h, lw, reference.dims(whole))
    total, loads = -shared, []
    for offset in (0, 8):
        out, load, _ = _sparse_layer(tiny_config(held=8, offset=offset), weights, 1, h)
        total, loads = total + out, loads + [load]
    np.testing.assert_allclose(np.asarray(total), np.asarray(routed + shared), atol=1e-5)
    assert int(sum(x.sum() for x in loads)) == 2 * 9 * 4  # every assignment fell to one share


def test_one_expert_taking_every_token_loses_none():
    """Dropless: with a selection bias that sends every token to expert 2, its
    load is the whole batch and the layer still equals the reference, which
    applies every expert to every token."""
    config = tiny_config(held=8)
    weights = dict(reference.init_weights(config, 5))
    weights["h.moe.router.b"] = weights["h.moe.router.b"].at[:, 2].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(3), (4, 32, 64))
    out, load, lw = _sparse_layer(config, weights, 0, h)
    assert int(load[2]) == 4 * 32 and int(load.sum()) <= 4 * 32 * 4
    want = sum(reference.moe_parts(h, lw, reference.dims(config)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("case", ["a-few-rows", "whole-tiles"])
def test_expert_ffn_gradients_match_a_dense_sum(case):
    """The grouped products over rows laid by expert from tile boundaries on,
    and both gathers, differentiate as the sum over experts they stand for:
    with a few rows in one tile each, and with one held expert filling three
    tiles, its neighbour empty and the others part of one."""
    rng = np.random.default_rng(0)
    N, k, E, d, f = (24, 3, 4, 16, 8) if case == "a-few-rows" else (300, 3, 4, 16, 8)
    x = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(E, d, f)), jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(E, f, d)), jnp.float32)
    if case == "a-few-rows":
        chosen = np.stack([rng.permutation(8)[:k] for _ in range(N)])  # experts 0..7, 2..5 held
    else:  # expert 2 takes every token (300 rows: three tiles of 128), expert 3 none
        chosen = np.stack([np.concatenate([[2], rng.permutation([0, 1, 4, 5, 6, 7])[: k - 1]]) for _ in range(N)])
    chosen = jnp.asarray(chosen, jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(N, k)), jnp.float32)

    def sparse(x, weights, gate, up, down):
        return (moe.expert_ffn(x, chosen, weights, gate, up, down, expert_offset=2)[0] ** 2).sum()

    def dense(x, weights, gate, up, down):
        each = jnp.einsum("nef,efd->ned", jax.nn.silu(jnp.einsum("nd,edf->nef", x, gate))
                          * jnp.einsum("nd,edf->nef", x, up), down)
        share = (jax.nn.one_hot(chosen - 2, E) * weights[..., None]).sum(1)  # nothing for one not held
        return (jnp.einsum("ned,ne->nd", each, share) ** 2).sum()

    load = moe.expert_ffn(x, chosen, weights, gate, up, down, expert_offset=2)[1]
    assert case == "a-few-rows" or (int(load[0]), int(load[1])) == (N, 0)
    got = jax.grad(sparse, argnums=range(5))(x, weights, gate, up, down)
    want = jax.grad(dense, argnums=range(5))(x, weights, gate, up, down)
    for a, b in zip(got, want):
        scale = float(np.abs(np.asarray(b)).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4 * max(scale, 1.0))


REFUSED = {
    "scan_layers": (dict(scan_layers=True), "layers of this model are not alike"),
    "pipeline": (dict(pipeline_stages=3), "layers of this model are not alike"),
    "scan_layers-latent-alone": (dict(scan_layers=True, num_experts=0), "stack one cache array"),
    "kv_cache_quant": (dict(kv_cache_quant=True), "a latent row has no heads"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_layouts_for_later_issues_are_refused_with_the_reason(case):
    overrides, reason = REFUSED[case]
    with pytest.raises(ValueError, match=reason):
        program(tiny_config(), **overrides)


def test_the_paged_cache_is_refused_with_the_reason():
    module, _ = program(tiny_config())
    with pytest.raises(ValueError, match="paged"):
        TransformerLM(module.config).init_paged_cache(8, 16, 4, 2)


def test_a_multi_device_mesh_is_refused_with_the_reason(mesh8):
    """The grouped products are one Pallas kernel over the experts a device
    holds; until the mesh has an expert axis, more than one device is refused."""
    x = jnp.ones((8, 16), jnp.float32)
    chosen, weights = jnp.zeros((8, 2), jnp.int32), jnp.ones((8, 2), jnp.float32)
    gate, down = jnp.ones((4, 16, 8), jnp.float32), jnp.ones((4, 8, 16), jnp.float32)
    with mesh8, pytest.raises(ValueError, match="no expert axis"):
        moe.expert_ffn(x, chosen, weights, gate, gate, down, expert_offset=0)


def test_sharding_rules_name_the_new_leaves(mesh8):
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.parallel.sharding import make_param_shardings

    _, like = program(tiny_config())
    layer = make_param_shardings(like, mesh8)["transformer"]["layers_1"]
    assert layer["mlp"]["experts"]["gate"].spec == P(None, "fsdp", "model")
    assert layer["mlp"]["experts"]["down"].spec == P(None, "model", "fsdp")
    assert layer["mlp"]["router"]["kernel"].spec == P("fsdp", None)
    assert layer["mlp"]["router"]["bias"].spec == P()
    assert layer["attn"]["kv_a_proj"]["kernel"].spec == P("fsdp", None)
    assert layer["attn"]["kv_b_proj"]["kernel"].spec == P("fsdp", "model")
    assert layer["mlp"]["shared"]["down_proj"]["kernel"].spec == P("model", "fsdp")


def test_dense_presets_build_what_they_built():
    """No dense family gains a leaf or a module from the new fields."""
    for name in ("gpt2", "llama"):
        c = get_preset(name, dict(num_layers=2, hidden_size=32, num_heads=2, vocab_size=64, intermediate_size=64))
        tree = jax.eval_shape(lambda: TransformerLM(c).init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32)))
        names = {"/".join(path) for path, _ in harness._paths(tree["params"])}
        assert not [n for n in names if any(new in n for new in ("router", "experts", "shared", "kv_a", "kv_b"))]
        assert c.attention_kind == "mha" and not c.is_expert_layer(1)


def _published_rotary(x, cos, sin):
    """Rotary as the published model applies it to a ``[..., rope]`` vector of
    its checkpoint's layout: dimensions (2i, 2i + 1) are a pair."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return np.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _interleaved(kernel, rope):
    """The last ``rope`` columns from halves (i, i + rope / 2) to the published
    checkpoint's pairs (2i, 2i + 1): what a checkpoint would hold."""
    order = np.stack([np.arange(rope // 2), np.arange(rope // 2) + rope // 2], axis=1).reshape(-1)
    head = kernel.shape[-1] - rope
    return np.concatenate([kernel[..., :head], kernel[..., head:][..., order]], axis=-1)


def test_a_published_state_dict_loads_into_the_programs_tree():
    """A small synthetic checkpoint under the published names (all 16 experts,
    the whole vocabulary, interleaved rotary pairs) loads into the tree of a
    share that holds experts 8..15 and 300 of 320 rows, and a forward through
    it equals the reference on the same weights. The rotary layout is pinned
    against the published pairing itself."""
    from trlx_tpu.models.hf_loading import _rotary_halves, hf_state_dict_to_params

    config = tiny_config(held=8, offset=8)
    module, like = program(config)
    whole = np.random.default_rng(0)
    weights = {k: np.asarray(v) for k, v in reference.init_weights(config, 13).items()}
    s = reference.dims(config)
    H, rope, V = s["heads"], s["rope"], s["vocab"]
    extra = lambda *shape: whole.normal(size=shape).astype(np.float32) * 0.02  # what this share leaves behind

    sd = {
        "language_model.model.embed_tokens.weight": np.concatenate([weights["wte"], extra(20, 64)]),
        "language_model.model.norm.weight": weights["ln_f.g"],
        "language_model.lm_head.weight": np.concatenate([weights["head.w"].T, extra(20, 64)]),
        "vision_tower.patch_embed.weight": extra(4, 4),  # not loaded
    }
    for i in range(s["layers"]):
        pre = f"language_model.model.layers.{i}"
        q = weights["h.q.w"][i]
        sd[f"{pre}.self_attn.q_proj.weight"] = _interleaved(q.reshape(64, H, -1), rope).reshape(q.shape).T
        sd[f"{pre}.self_attn.kv_a_proj_with_mqa.weight"] = _interleaved(weights["h.kva.w"][i], rope).T
        sd[f"{pre}.self_attn.kv_a_layernorm.weight"] = weights["h.kva_norm.g"][i]
        sd[f"{pre}.self_attn.kv_b_proj.weight"] = weights["h.kvb.w"][i].T
        sd[f"{pre}.self_attn.o_proj.weight"] = weights["h.o.w"][i].T
        sd[f"{pre}.input_layernorm.weight"] = weights["h.ln_1.g"][i]
        sd[f"{pre}.post_attention_layernorm.weight"] = weights["h.ln_2.g"][i]
        if i < s["dense_layers"]:
            for name in ("gate", "up", "down"):
                sd[f"{pre}.mlp.{name}_proj.weight"] = weights[f"h.dense.{name}.w"][i].T
            continue
        j = i - s["dense_layers"]
        sd[f"{pre}.mlp.gate.weight"] = weights["h.moe.router.w"][j].T
        sd[f"{pre}.mlp.gate.e_score_correction_bias"] = weights["h.moe.router.b"][j]
        for name in ("gate", "up", "down"):
            sd[f"{pre}.mlp.shared_experts.{name}_proj.weight"] = weights[f"h.moe.shared.{name}.w"][j].T
            for e in range(16):
                held = weights[f"h.moe.experts.{name}"][j]
                sd[f"{pre}.mlp.experts.{e}.{name}_proj.weight"] = (
                    held[e - 8].T if e >= 8 else extra(*held[0].T.shape))

    loaded = hf_state_dict_to_params("kimi_vl", sd, module.config)
    assert jax.tree.map(np.shape, loaded) == jax.tree.map(lambda a: a.shape, like["transformer"])
    ids, mask = inputs(4, B=2, T=16, pad=3)
    logits, _, _, _ = TransformerLM(module.config).apply({"params": loaded}, ids, mask)
    want, _ = reference.forward(weights, config, ids, mask)
    real = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(logits)[real], np.asarray(want)[real], atol=1e-5)

    # the layout itself: the published rotation of a checkpoint-layout vector, brought to halves,
    # is this program's rotate-half of the vector brought to halves
    from trlx_tpu.models.transformer import apply_rotary

    x = whole.normal(size=(1, 1, 1, rope)).astype(np.float32)
    angle = whole.uniform(0, 3, size=(1, 1, rope // 2)).astype(np.float32)
    published = _published_rotary(x, np.cos(angle)[:, :, None], np.sin(angle)[:, :, None])
    ours = apply_rotary(jnp.asarray(_rotary_halves(x, rope)), jnp.cos(angle), jnp.sin(angle), "neox")
    np.testing.assert_allclose(_rotary_halves(published, rope), np.asarray(ours), atol=1e-6)
    np.testing.assert_array_equal(_rotary_halves(_interleaved(x, rope), rope), x)
