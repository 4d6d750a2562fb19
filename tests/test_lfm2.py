"""The lfm2 family (a mixer chosen layer by layer: gated short convolutions
beside grouped-query attention with a norm on each head, over routed experts
with no shared one) on the program's normal path against its plain reference
(``benchmark/reference_lfm2.py``) at a small size: through the flash kernels and
through the two-kind cache with left-padded prompts of different lengths, the
PPO loss's gradient, the share of the experts, planted faults, the refusals,
the published names. Seeded random weights, CPU."""

import contextlib
import dataclasses
import re
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark import reference as base
from benchmark.families import lfm2 as family
from lfm2_tiny import KINDS, tiny_config
from trlx_tpu.models.policy import CausalLMWithValueHead, apply_hydra_branch, branch_param_subtree
from trlx_tpu.models.presets import PRESETS, get_preset
from trlx_tpu.models.transformer import ShortConv, SparseMLP, TransformerConfig, TransformerLM
from trlx_tpu.ops import kv_cache
from trlx_tpu.utils.metrics import gauges

reference = family.reference

#: float32 compute against the float32 reference: the same arithmetic in another order (flash tiles
#: against one softmax, sorted grouped products against every expert on every token, fused norms):
#: float32 round-off alone, 1e-5 on logits as large as 1.5 (read: 2.4e-7)
F32_ATOL = 1e-5
#: bfloat16 compute (8 bits of mantissa, 4e-3 a rounding) through four layers with the router in
#: float32: the largest logit gap over seeds 7..11 read 0.012-0.018, the mean 0.0016-0.0018. Ten
#: times tighter than these float32 still passes (its gaps are ten thousand times smaller), and
#: the planted faults read 0.15 (the head norms), 0.58 (the mask) and 0.86 (b and c)
BF16_MAX_LIMIT, BF16_MEAN_LIMIT = 0.04, 0.004


def init_weights(config, seed):
    """The reference's weights with the convolutions' made to matter: at d 64 the drawn ones (in_proj
    0.02, the filter 0.02) add a fiftieth of the residual stream, and a fault in them would hide under
    the tolerances; in_proj times 4 and the filter times 50 make a convolution's output the stream's size."""
    weights = dict(reference.init_weights(config, seed))
    weights["h.conv.in.w"], weights["h.conv.filter"] = weights["h.conv.in.w"] * 4.0, weights["h.conv.filter"] * 50.0
    return weights


def program(config, compute_dtype=jnp.float32, **overrides):
    """(the policy module at the configuration's sizes, its parameter shapes)."""
    model_config = get_preset(family.MODEL_PATH, {
        **family.program_overrides(config), "param_dtype": jnp.float32, "compute_dtype": compute_dtype, **overrides})
    module = CausalLMWithValueHead(model_config)
    like = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2), jnp.int32))
    )["params"]
    return module, like


def inputs(seed, B=4, T=48, pads=(5, 0, 2, 0)):
    """Rows left-padded by ``pads`` tokens each; the pad positions hold a token like any other."""
    ids = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 3, 300)
    mask = (jnp.arange(T)[None, :] >= jnp.asarray(pads[:B])[:, None]).astype(jnp.int32)
    return ids, mask


def forwards(config, seed=7, compute_dtype=jnp.float32, data_seed=1, alter=None, **overrides):
    """(program logits, values, reference logits, values) at the real
    positions. ``alter`` changes the program's parameters after they are laid."""
    module, like = program(config, compute_dtype, **overrides)
    weights = init_weights(config, seed)
    params = harness.to_program_tree(family, weights, like, jnp.float32)
    if alter is not None:
        params = alter(params)
    ids, mask = inputs(data_seed)
    logits, values, _, _ = jax.jit(lambda p, i, m: module.apply({"params": p}, i, m))(params, ids, mask)
    want_logits, want_values = jax.jit(lambda w, i, m: reference.forward(w, config, i, m))(weights, ids, mask)
    real = np.asarray(mask) > 0
    as_f32 = lambda x: np.asarray(x.astype(jnp.float32))
    return as_f32(logits)[real], as_f32(values)[real], np.asarray(want_logits)[real], np.asarray(want_values)[real]


def test_reference_covers_every_program_leaf_and_the_head_is_tied():
    config = tiny_config()
    module, like = program(config)
    weights = reference.init_weights(config, 7)
    tree = harness.to_program_tree(family, weights, like, jnp.float32)
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(lambda a: a.shape, like)
    assert {family.leaf_name(path)[0] for path, _ in harness._paths(like)} == set(weights)
    trunk = like["transformer"]
    assert "lm_head" not in trunk and set(trunk["layers_0"]) == {"ln_1", "ln_2", "conv", "mlp"}
    assert set(trunk["layers_1"]) == {"ln_1", "ln_2", "attn", "mlp"}
    assert set(trunk["layers_1"]["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"}
    assert trunk["layers_0"]["conv"]["conv"]["kernel"].shape == (64, 3)
    assert set(trunk["layers_0"]["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
    assert set(trunk["layers_2"]["mlp"]) == {"router", "experts"}  # no shared expert, so no such module
    assert module.config.layer_kinds == ("conv", "attention", "conv", "conv")
    with pytest.raises(ValueError, match="which leaf_name counts from"):
        family.program_overrides(dict(config, layer_types=["full_attention", "conv", "conv", "conv"]))


@pytest.mark.parametrize("attention_impl", ["xla", "flash"])
def test_float32_logits_and_values_match_the_reference(attention_impl):
    logits, values, want_logits, want_values = forwards(tiny_config(), attention_impl=attention_impl)
    np.testing.assert_allclose(logits, want_logits, atol=F32_ATOL)
    np.testing.assert_allclose(values, want_values, atol=F32_ATOL)
    # and ten times tighter than bfloat16's tolerance, many times over
    assert np.abs(logits - want_logits).max() < BF16_MAX_LIMIT / 10


@pytest.mark.parametrize("seed", [8])
def test_bfloat16_compute_logits_stay_near_the_reference(seed):
    logits, _, want, _ = forwards(tiny_config(), seed, jnp.bfloat16, data_seed=seed)
    gap = np.abs(logits - want)
    assert gap.max() < BF16_MAX_LIMIT and gap.mean() < BF16_MEAN_LIMIT, (gap.max(), gap.mean())
    assert gap.max() > 10 * F32_ATOL  # the precision shows: float32's tolerance would refuse it


def _swap_b_and_c(params):
    """The columns of every ``in_proj`` that give ``b`` and ``c`` exchanged: the
    program then gates the filter's input by ``c`` and its output by ``b``."""
    params = jax.tree.map(lambda x: x, params)
    for name, layer in params["transformer"].items():
        if "conv" in layer:
            b, c, x = jnp.split(layer["conv"]["in_proj"]["kernel"], 3, axis=1)
            layer["conv"]["in_proj"]["kernel"] = jnp.concatenate([c, b, x], axis=1)
    return params


def _unmasked():
    """The convolution's gated input left unmasked at padded positions."""
    original = ShortConv.__call__
    unmasked = lambda self, x, cache=None, valid=None: original(self, x, cache, None)
    return mock.patch.object(ShortConv, "__call__", unmasked)


def _stale_state():
    """A decode step leaves the convolution's state as it found it."""
    original = kv_cache.roll_conv_state

    def roll(cache, u):
        seen, new = original(cache, u)
        return seen, ({"conv": cache["conv"]} if u.shape[1] == 1 else new)

    return mock.patch.object(kv_cache, "roll_conv_state", roll)


#: planted fault -> (what is patched around the forward, the overrides, the parameters' alteration)
FAULTS = {
    "mask_left_out": (_unmasked, {}, None),
    "b_and_c_swapped": (contextlib.nullcontext, {}, _swap_b_and_c),
    "head_norms_left_out": (contextlib.nullcontext, {"qk_norm": False}, None),
}


def test_the_padded_rows_are_the_unpadded_rows():
    """What the mask on the gated input is for: a left-padded row's logits at
    its real positions are those of the row alone, in program and reference."""
    config = tiny_config()
    module, like = program(config)
    weights = init_weights(config, 7)
    params = harness.to_program_tree(family, weights, like, jnp.float32)
    ids, mask = inputs(1)
    logits = module.apply({"params": params}, ids, mask)[0]
    alone = module.apply({"params": params}, ids[:1, 5:], mask[:1, 5:])[0]
    np.testing.assert_allclose(np.asarray(logits[0, 5:]), np.asarray(alone[0]), atol=F32_ATOL)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(fault):
    """Each fault moves the logits by thousands of times the float32 tolerance
    and by more than bfloat16's: no precision hides it."""
    patch, overrides, alter = FAULTS[fault]
    with patch():
        logits, _, want, _ = forwards(tiny_config(), alter=alter, **overrides)
    assert np.abs(logits - want).max() > 3 * BF16_MAX_LIMIT > 1000 * F32_ATOL, np.abs(logits - want).max()


def _cached_decode(config, attention_impl, P=20, N=6, pads=(4, 0, 9)):
    """The left-padded prompts through the prefill, then token by token over
    the cache: the logits at every position, and the reference's from one full
    forward over each row."""
    module, like = program(config, attention_impl=attention_impl)
    trunk = TransformerLM(module.config)
    weights = init_weights(config, 3)
    params = harness.to_program_tree(family, weights, like, jnp.float32)["transformer"]
    ids, mask = inputs(2, B=3, T=P + N, pads=pads)
    want, _ = reference.forward(weights, config, ids, mask)

    cache = trunk.init_cache(3, P + N)
    # keys and values over the one attention layer, a state for each of the three convolution layers
    assert set(cache) == {"k", "v", "conv", "index"} and len(cache["k"]) == len(cache["v"]) == 1
    # (the decode kernel's cache holds the 2 kv heads beside each of the 3 rows)
    assert cache["k"][0].shape == ((6, 1) if attention_impl == "flash" else (3, 2)) + (P + N, 16)
    assert len(cache["conv"]) == 3
    assert cache["conv"][2].shape == (3, 2, 64)  # the two gated inputs the next token's three taps read
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
    assert set(cache) == {"k", "v", "conv", "index"} and len(cache["conv"]) == 3 and int(cache["index"]) == P + N
    real = np.asarray(mask) > 0
    prefilled = real & (np.arange(P + N) < P)
    return np.asarray(jnp.concatenate(got, axis=1)), np.asarray(want), prefilled, real & ~prefilled


@pytest.mark.parametrize("attention_impl", ["xla", "flash"])
def test_prefill_then_decode_through_the_two_kind_cache(attention_impl):
    """Prompts of 16, 20 and 11 tokens left-padded to 20: the prefill writes
    each convolution's state from the prompt's last positions and the keys and
    values from slot 0; every decode step reads the state, forms the filter's
    output and rolls it. Every position's logits, prefilled or decoded, are the
    reference's full forward's on that row. The flash case prefills through the
    flash kernel and decodes through the Pallas decode kernel (interpreted)."""
    got, want, prefilled, decoded = _cached_decode(tiny_config(), attention_impl)
    np.testing.assert_allclose(got[prefilled], want[prefilled], atol=F32_ATOL)
    np.testing.assert_allclose(got[decoded], want[decoded], atol=F32_ATOL)


def test_a_stale_state_fails_the_comparison():
    with _stale_state():
        got, want, prefilled, decoded = _cached_decode(tiny_config(), "xla")
    np.testing.assert_allclose(got[prefilled], want[prefilled], atol=F32_ATOL)
    assert np.abs(got[decoded] - want[decoded]).max() > 300 * F32_ATOL


def test_a_cached_forward_of_several_tokens_without_its_mask_is_refused():
    module, like = program(tiny_config())
    trunk = TransformerLM(module.config)
    params = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), like["transformer"])
    cache = {**trunk.init_cache(2, 16), "index": jnp.array(4, jnp.int32)}  # not the prefill from slot 0
    with pytest.raises(ValueError, match="only as the prefill from slot 0"):
        trunk.apply({"params": params}, jnp.zeros((2, 3), jnp.int32), jnp.ones((2, 16), jnp.int32), None, cache)


def _ppo_inputs(config, weights, P=10, R=8, B=4):
    ids, mask = inputs(5, B=B, T=P + R, pads=(3, 0, 1, 0))
    old_lp, old_v, _ = reference.response_window(weights, config, ids, mask, P, R)
    rng = np.random.default_rng(0)
    noise = lambda scale: jnp.asarray(rng.normal(size=(B, R)) * scale, jnp.float32)
    rewards = jnp.zeros((B, R)).at[:, -1].set(jnp.asarray(rng.uniform(0.1, 0.9, size=B), jnp.float32))
    rmask = jnp.ones((B, R), jnp.float32)
    adv, ret = base.gae(old_v + noise(0.05), rewards, rmask, 1.0, 0.95)
    return ids, mask, old_lp + noise(0.1), old_v + noise(0.05), adv, ret, rmask


@pytest.mark.parametrize("remat", ["none", "dots_saveable"])
def test_the_ppo_losss_gradient_matches_the_reference_leaf_by_leaf(remat):
    """trlX's clipped PPO loss over the response window, differentiated through
    the program (the filter's three shifted products, the head norms, the
    grouped products' two gathers) and through the reference: every leaf's
    gradient, entry by entry; the selection bias takes none. float32 both
    sides: 1e-4 of a leaf's largest entry (the orders of summation differ)."""
    config = tiny_config()
    module, like = program(config, remat=remat)
    weights = init_weights(config, 11)
    params = harness.to_program_tree(family, weights, like, jnp.float32)
    P, R = 10, 8
    ids, mask, old_lp, old_v, adv, ret, rmask = _ppo_inputs(config, weights, P, R)
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
        if path[-2:] == ("router", "bias"):
            assert scale == 0.0 and float(np.abs(np.asarray(a)).max()) == 0.0, path
            continue
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4 * scale, err_msg="/".join(path))


def _sparse_layer(config, weights, layer, h, **overrides):
    """The program's expert FFN on ``h`` with the reference's layer weights
    (the held experts' slice), and the loads it sowed."""
    model_config = get_preset(family.MODEL_PATH, {
        **family.program_overrides(config), "param_dtype": jnp.float32, "compute_dtype": jnp.float32, **overrides})
    s = reference.dims(config)
    lw = reference.layer_weights(weights, reference.dims(tiny_config()), layer)
    held = slice(s["offset"], s["offset"] + s["held"])
    params = {
        "router": {"kernel": lw["moe.router.w"], "bias": lw["moe.router.b"]},
        "experts": {n: lw[f"moe.experts.{n}"][held] for n in ("gate", "up", "down")},
    }
    out, sown = SparseMLP(model_config).apply({"params": params}, h, mutable=["moe_stats"])
    return out, sown["moe_stats"]["load"][0], lw


def test_the_programs_shares_add_up_to_the_uncut_layer():
    """The share tied to the model: experts 0-3 and 4-7 of the small model's 8,
    run as two shares, add up to the uncut reference's whole expert layer."""
    whole = tiny_config()
    weights = reference.init_weights(whole, 11)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 64))
    want = reference.experts(h, reference.layer_weights(weights, reference.dims(whole), 2), reference.dims(whole))
    total, loads = 0.0, []
    for offset in (0, 4):
        out, load, _ = _sparse_layer(tiny_config(held=4, offset=offset), weights, 2, h)
        total, loads = total + out, loads + [load]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-6)
    assert int(sum(x.sum() for x in loads)) == 2 * 9 * 2  # every assignment fell to one share


def test_the_routers_epsilon_is_the_published_one():
    """``+ 1e-6`` beside a sum of two sigmoids of 0.5 moves a logit by 1e-8: no
    end-to-end tolerance sees it aside, float32's neither. So it is held at the
    layer, with a router whose chosen scores sum to about 1e-4: the published
    epsilon is then a hundredth of the weights, and leaving it out shows."""
    config = tiny_config()
    weights = dict(reference.init_weights(config, 5))
    weights["h.moe.router.w"] = jnp.full_like(weights["h.moe.router.w"], -0.2)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (2, 16, 64)))
    out, _, lw = _sparse_layer(config, weights, 1, h)
    want = reference.experts(h, lw, reference.dims(config))
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4 * scale)
    aside, _, _ = _sparse_layer(config, weights, 1, h, router_norm_eps=0.0)
    assert float(jnp.abs(aside - want).max()) > 3e-3 * scale
    assert PRESETS["lfm2_moe"].router_norm_eps == 1e-6 and PRESETS["kimi_vl"].router_norm_eps == 0.0


def test_the_convolutions_are_scoped_and_the_cache_gauges_are_set():
    config = tiny_config()
    module, like = program(config)
    params = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), like)
    ids, mask = inputs(3)
    text = jax.jit(lambda p: module.apply({"params": p}, ids, mask)[0]).lower(params).as_text(debug_info=True)
    assert len(set(re.findall(r"layers_\d/conv/conv", text))) == 3 and "moe.experts" in text
    gauges.clear("hybrid/")
    TransformerLM(module.config).init_cache(2, 32)
    assert gauges.get("hybrid/attention_layers") == 1 and gauges.get("hybrid/conv_layers") == 3
    # one attention layer: (k + v) x 2 kv heads x 16 x float32; three states of 2 x 64 float32
    assert gauges.get("hybrid/cache_bytes_per_token") == 2 * 2 * 16 * 4
    assert gauges.get("hybrid/state_bytes_per_row") == 3 * 2 * 64 * 4
    # at the published widths, as the cell runs them: bfloat16, published layers 1-5
    cut = PRESETS["lfm2_moe"].replace(num_layers=5, layer_kinds=("conv", "attention", "conv", "conv", "conv"))
    TransformerLM(cut).init_cache(1, 8)
    assert gauges.get("hybrid/cache_bytes_per_token") == 2048 and gauges.get("hybrid/state_bytes_per_row") == 32768


def test_without_layer_kinds_and_head_norms_the_model_is_what_it_was():
    """Every layer "attention" and no ``qk_norm``: the tree, the cache and the
    output are those of the same configuration built without the two fields;
    and no existing family gains a leaf, a cache key, a gauge or a scope from them."""
    config = tiny_config()
    module, like = program(config, layer_kinds=("attention",) * 4, qk_norm=False)
    fields = {f.name: getattr(module.config, f.name) for f in dataclasses.fields(TransformerConfig)}
    for name in ("layer_kinds", "qk_norm", "conv_taps"):
        fields.pop(name)
    plain = CausalLMWithValueHead(TransformerConfig(**fields))
    ids, mask = inputs(1)
    plain_like = jax.eval_shape(lambda: plain.init(jax.random.PRNGKey(0), ids, mask))["params"]
    assert jax.tree.map(lambda a: a.shape, plain_like) == jax.tree.map(lambda a: a.shape, like)
    params = plain.init(jax.random.PRNGKey(1), ids, mask)["params"]
    got, same = (m.apply({"params": params}, ids, mask)[0] for m in (module, plain))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
    assert set(TransformerLM(module.config).init_cache(1, 8)) == {"k", "v", "index"}
    gauges.clear("hybrid/")
    for name in ("gpt2", "llama", "kimi_vl", "ouro"):
        c = get_preset(name, dict(num_layers=2, hidden_size=32, num_heads=2, vocab_size=64, intermediate_size=64))
        assert c.layer_kinds == () and not c.qk_norm and c.conv_layers == 0 and c.attention_layers == 2
        assert c.cache_entries == 2 * c.loop_steps
        if name == "kimi_vl":
            continue
        model = TransformerLM(c)
        tree = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32)))
        names = {"/".join(path) for path, _ in harness._paths(tree["params"])}
        assert not [n for n in names if any(new in n for new in ("conv", "in_proj", "out_proj", "q_norm", "k_norm"))]
        assert set(model.init_cache(1, 8)) == {"k", "v", "index"}
        text = jax.jit(lambda p: model.apply(p, jnp.zeros((1, 2), jnp.int32))[0]).lower(tree).as_text(debug_info=True)
        assert "/conv/" not in text  # the module's scope (every program says "convert")
    assert gauges.snapshot("hybrid/") == {}


REFUSED = {
    "scan_layers": (dict(scan_layers=True), "the layers of this model are not alike"),
    "pipeline": (dict(pipeline_stages=2), "the layers of this model are not alike"),
    "kv_cache_quant": (dict(kv_cache_quant=True), "beside a convolution's float state"),
    "ring": (dict(attention_impl="ring"), "reads its left neighbours across the split"),
    "sequence_sharding": (dict(sequence_sharding=True), "reads its left neighbours across the split"),
    "prompt_tuning": (dict(peft_type="prompt", num_virtual_tokens=2), "has no such rows"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_layouts_for_later_issues_are_refused_with_the_reason(case):
    overrides, reason = REFUSED[case]
    dense = dict(num_experts=0)  # the expert layers refuse a stacked layout first, with their own reason
    with pytest.raises(ValueError, match=reason):
        program(tiny_config(), **dense, **overrides)


def test_layer_kinds_must_name_every_layer():
    overrides = family.program_overrides(tiny_config())
    for kinds in (("conv", "attention"), ["conv", "window"] * 2):
        with pytest.raises(ValueError, match="does not name each of 4 layers"):
            get_preset(family.MODEL_PATH, {**overrides, "layer_kinds": kinds})
    # a depth cut by num_layers alone keeps the leading layers' kinds: the published c c a c ...
    assert PRESETS["lfm2_moe"].replace(num_layers=3).layer_kinds == ("conv", "conv", "attention")


@pytest.mark.parametrize("entry", ["init_paged_cache", "paged_decode", "paged_verify"])
def test_the_paged_engine_is_refused_with_the_reason(entry):
    module, like = program(tiny_config())
    trunk = TransformerLM(module.config)
    reason = "a slot of its own in serving/allocator.py"
    if entry == "init_paged_cache":
        with pytest.raises(ValueError, match=reason):
            trunk.init_paged_cache(8, 16, 4, 2)
        return
    cache = TransformerLM(module.config.replace(layer_kinds=())).init_paged_cache(8, 16, 4, 2)
    with pytest.raises(ValueError, match=reason):
        trunk.apply({"params": like["transformer"]}, jnp.zeros((2, 1), jnp.int32), cache, method=getattr(trunk, entry))


def test_a_hydra_branch_over_the_top_layers_is_the_full_copy_at_initialisation():
    """``num_layers_unfrozen`` 2: the frozen branch (layers 2 and 3, both
    convolutions over experts, the final norm and the tied head) run cache-free
    from the activation captured under them gives the full model's logits."""
    config = tiny_config()
    module, like = program(config)
    params = harness.to_program_tree(family, init_weights(config, 7), like, jnp.float32)
    ids, mask = inputs(1)
    logits, _, branch_hidden, _ = module.apply({"params": params}, ids, mask, branch_layer=2)
    branch = branch_param_subtree(params["transformer"], 2, module.config)
    assert set(branch) == {"layers_2", "layers_3", "ln_f", "embed_tokens"}
    again = apply_hydra_branch(module, branch, branch_hidden, mask, 2)
    real = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(again)[real], np.asarray(logits)[real], atol=1e-6)


def test_sharding_rules_name_the_new_leaves(mesh8):
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.parallel.sharding import default_lm_rules, make_param_shardings

    _, like = program(tiny_config())
    trunk = make_param_shardings(like, mesh8)["transformer"]
    assert trunk["layers_0"]["conv"]["in_proj"]["kernel"].spec == P("fsdp", "model")
    assert trunk["layers_0"]["conv"]["out_proj"]["kernel"].spec == P("model", "fsdp")
    assert trunk["layers_0"]["conv"]["conv"]["kernel"].spec == P("model", None)
    assert trunk["layers_1"]["attn"]["q_norm"]["scale"].spec == P() == trunk["layers_1"]["attn"]["k_norm"]["scale"].spec
    assert trunk["layers_1"]["attn"]["k_proj"]["kernel"].spec == P("fsdp", "model")
    rules = default_lm_rules()[:-1]  # by rules of their own, not by the catch-all
    for path in ("transformer/layers_0/conv/in_proj/kernel", "transformer/layers_0/conv/conv/kernel",
                 "transformer/layers_0/conv/out_proj/kernel", "transformer/layers_1/attn/q_norm/scale"):
        assert any(re.match(pattern, path) for pattern, _ in rules), path


def test_a_published_state_dict_round_trips_through_the_programs_tree():
    """A made-up checkpoint under the published names loads into the program's
    tree (a forward through it is the reference's on the same weights) and the
    exporter writes the same names and arrays back; the preset and the
    ``from_hf_config`` branch read the published keys."""
    from types import SimpleNamespace

    from trlx_tpu.models.hf_loading import hf_state_dict_to_params, make_hf_config, params_to_hf_state_dict
    from trlx_tpu.models.presets import from_hf_config

    config = tiny_config()
    module, like = program(config)
    w = {k: np.asarray(v) for k, v in reference.init_weights(config, 13).items()}
    for name in ("h.ln_1.g", "h.ln_2.g"):
        w[name] = w[name] + 0.01 * np.arange(1, 5, dtype=np.float32)[:, None]  # told apart
    w["h.attn.q_norm.g"], w["h.attn.k_norm.g"] = w["h.attn.q_norm.g"] * 1.1, w["h.attn.k_norm.g"] * 0.9
    sd = {"model.embed_tokens.weight": w["wte"], "model.embedding_norm.weight": w["ln_f.g"]}
    s = reference.dims(config)
    for i, kind in enumerate(KINDS):
        pre, lw = f"model.layers.{i}", reference.layer_weights(w, s, i)
        sd[f"{pre}.operator_norm.weight"], sd[f"{pre}.ffn_norm.weight"] = lw["ln_1.g"], lw["ln_2.g"]
        if kind == "conv":
            sd[f"{pre}.conv.in_proj.weight"], sd[f"{pre}.conv.out_proj.weight"] = lw["conv.in.w"].T, lw["conv.out.w"].T
            sd[f"{pre}.conv.conv.weight"] = lw["conv.filter"][:, None, :]  # [d, 1, taps] as published
        else:
            for ours, theirs in (("q", "q_proj"), ("k", "k_proj"), ("v", "v_proj"), ("o", "out_proj")):
                sd[f"{pre}.self_attn.{theirs}.weight"] = lw[f"attn.{ours}.w"].T
            sd[f"{pre}.self_attn.q_layernorm.weight"] = lw["attn.q_norm.g"]
            sd[f"{pre}.self_attn.k_layernorm.weight"] = lw["attn.k_norm.g"]
        if i == 0:
            for ours, theirs in (("gate", "w1"), ("up", "w3"), ("down", "w2")):
                sd[f"{pre}.feed_forward.{theirs}.weight"] = lw[f"dense.{ours}.w"].T
        else:
            sd[f"{pre}.feed_forward.gate.weight"] = lw["moe.router.w"].T
            sd[f"{pre}.feed_forward.expert_bias"] = lw["moe.router.b"]
            for e in range(8):
                for ours, theirs in (("gate", "w1"), ("up", "w3"), ("down", "w2")):
                    sd[f"{pre}.feed_forward.experts.{e}.{theirs}.weight"] = lw[f"moe.experts.{ours}"][e].T

    loaded = hf_state_dict_to_params("lfm2_moe", sd, module.config)
    assert jax.tree.map(np.shape, loaded) == jax.tree.map(lambda a: a.shape, like["transformer"])
    ids, mask = inputs(4, B=2, T=16, pads=(3, 0))
    logits, _, _, _ = TransformerLM(module.config).apply({"params": loaded}, ids, mask)
    want, _ = reference.forward({k: jnp.asarray(v) for k, v in w.items()}, config, ids, mask)
    real = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(logits)[real], np.asarray(want)[real], atol=F32_ATOL)

    back = params_to_hf_state_dict("lfm2_moe", loaded, module.config)
    assert set(back) == set(sd)
    for name in sd:
        np.testing.assert_array_equal(back[name], sd[name], err_msg=name)
    # a share takes its experts from its offset on, and gives them back under their published indices
    share = module.config.replace(experts_held=4, expert_offset=4)
    half = hf_state_dict_to_params("lfm2_moe", sd, share)
    experts = lambda tree: tree["layers_2"]["mlp"]["experts"]["up"]
    np.testing.assert_array_equal(experts(half), experts(loaded)[4:])
    assert "model.layers.2.feed_forward.experts.7.w3.weight" in params_to_hf_state_dict("lfm2_moe", half, share)

    preset = PRESETS["lfm2_moe"]
    hf = make_hf_config("lfm2_moe", preset)
    assert hf.model_type == "lfm2_moe" and hf.layer_types.count("full_attention") == 10 and hf.conv_L_cache == 3
    assert hf.layer_types[:7] == ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention"]
    assert from_hf_config(hf) == preset
    with pytest.raises(ValueError, match="conv_bias"):
        from_hf_config(SimpleNamespace(**{**hf.to_dict(), "conv_bias": True}))
    assert (preset.conv_layers, preset.attention_layers, preset.kv_heads, preset.dim_per_head) == (30, 10, 8, 64)
    assert preset.qk_norm and preset.tie_word_embeddings and preset.num_shared_experts == 0
    assert (preset.ffn_dim, preset.moe_intermediate_size, preset.experts_per_token) == (11776, 1536, 4)
    assert get_preset("LFM2-MoE-24B").num_layers == 40
