"""The ouro family (looped layers) on the program's normal path against its
plain reference (``benchmark/reference_ouro.py``) at a small size: the stack of
layers run four times over shared weights, through the flash kernels and through
the (pass, layer) cache, sandwich norms, the exit gate's leaf and counters, the
PPO loss's gradient, the refusals, the published names. Seeded random weights, CPU."""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark import reference as base
from benchmark.families import ouro as family
from ouro_tiny import tiny_config
from trlx_tpu.models.policy import CausalLMWithValueHead, branch_param_subtree
from trlx_tpu.models.presets import PRESETS, get_preset
from trlx_tpu.models.transformer import TransformerConfig, TransformerLM, loop_counters
from trlx_tpu.utils.metrics import gauges

reference = family.reference


def program(config, compute_dtype=jnp.float32, **overrides):
    """(the policy module at the configuration's sizes, its parameter shapes)."""
    model_config = get_preset(family.MODEL_PATH, {
        **family.program_overrides(config), "param_dtype": jnp.float32, "compute_dtype": compute_dtype,
        "remat": "none", **overrides})
    module = CausalLMWithValueHead(model_config)
    like = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2), jnp.int32))
    )["params"]
    return module, like


def inputs(seed, B=4, T=48, pad=5):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 3, 300)
    return ids, jnp.ones((B, T), jnp.int32).at[0, :pad].set(0)  # row 0 left-padded


def forwards(config, seed=7, compute_dtype=jnp.float32, data_seed=1, **overrides):
    """(program logits, values, reference logits, values, the real positions)."""
    module, like = program(config, compute_dtype, **overrides)
    weights = reference.init_weights(config, seed)
    params = harness.to_program_tree(family, weights, like, jnp.float32)
    ids, mask = inputs(data_seed)
    logits, values, _, _ = jax.jit(lambda p, i, m: module.apply({"params": p}, i, m))(params, ids, mask)
    want_logits, want_values = jax.jit(lambda w, i, m: reference.forward(w, config, i, m))(weights, ids, mask)
    real = np.asarray(mask) > 0
    as_f32 = lambda x: np.asarray(x.astype(jnp.float32))
    return as_f32(logits)[real], as_f32(values)[real], np.asarray(want_logits)[real], np.asarray(want_values)[real]


def test_reference_covers_every_program_leaf_the_gate_included():
    config = tiny_config()
    _, like = program(config)
    weights = reference.init_weights(config, 7)
    tree = harness.to_program_tree(family, weights, like, jnp.float32)
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(lambda a: a.shape, like)
    assert {family.leaf_name(path)[0] for path, _ in harness._paths(like)} == set(weights)
    # a leaf is one leaf however many passes use it: 3 layers, four norms each, and the gate's two
    names = {"/".join(path) for path, _ in harness._paths(like["transformer"])}
    assert sum("ln_1_post" in n or "ln_2_post" in n for n in names) == 6
    assert {"exit_gate/kernel", "exit_gate/bias"} <= names and not any("layers_3" in n for n in names)


#: float32 compute against the float32 reference: the same arithmetic in another order (flash tiles
#: against one softmax, fused norms), through 12 block applications whose sandwich norms rescale
#: every sub-layer's output: float32 round-off alone, 1e-5 on logits as large as 0.56 (read: 7e-7)
F32_ATOL = 1e-5
#: bfloat16 compute (8 bits of mantissa, 4e-3 a rounding) through the same 12 applications: the
#: largest logit gap over seeds 7..11 read 0.015-0.026, the mean 0.0020-0.0024. Ten times
#: tighter than these float32 still passes (its gaps are ten thousand times smaller)
BF16_MAX_LIMIT, BF16_MEAN_LIMIT = 0.05, 0.005


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


def test_a_pass_left_out_fails_the_comparison():
    """Three passes where the model runs four: the logits miss the reference's
    by thousands of times the float32 tolerance."""
    logits, _, want, _ = forwards(tiny_config(), loop_steps=3)
    assert np.abs(logits - want).max() > 1000 * F32_ATOL


def _cached_decode(config, attention_impl, scramble=None, P=20, N=6):
    """The prompt through the prefill, then token by token over the cache: the
    logits at every position, and the reference's from one full forward.
    ``scramble`` re-orders the cache's entries after the prefill."""
    module, like = program(config, attention_impl=attention_impl)
    trunk = TransformerLM(module.config)
    weights = reference.init_weights(config, 3)
    params = harness.to_program_tree(family, weights, like, jnp.float32)["transformer"]
    ids, mask = inputs(2, B=3, T=P + N, pad=4)
    want, _ = reference.forward(weights, config, ids, mask)

    cache = trunk.init_cache(3, P + N)
    # an entry for every (pass, layer): 4 passes of 3 layers, per-head keys and values
    assert set(cache) == {"k", "v", "index"} and len(cache["k"]) == len(cache["v"]) == 12
    # (the decode kernel's cache holds the 4 kv heads beside each of the 3 rows)
    assert cache["k"][11].shape == ((12, 1) if attention_impl == "flash" else (3, 4)) + (P + N, 16)
    seen = mask.at[:, P:].set(0)
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None)
    prefill = jax.jit(lambda p, i, m, pos, c: trunk.apply({"params": p}, i, m, pos, {**c, "index": 0}))
    step = jax.jit(lambda p, i, m, pos, c: trunk.apply({"params": p}, i, m, pos, c))
    cache.pop("index")  # a concrete 0 inside the jitted prefill, as generate() gives it
    logits, _, _, cache = prefill(params, ids[:, :P], seen, positions[:, :P], cache)
    if scramble is not None:
        cache = {key: ([value[i] for i in scramble] if key != "index" else value) for key, value in cache.items()}
    got = [logits]
    for t in range(P, P + N):
        seen = seen.at[:, t].set(1)
        logits, _, _, cache = step(params, ids[:, t : t + 1], seen, positions[:, t : t + 1], cache)
        got.append(logits)
    real = np.asarray(mask) > 0
    prefilled = real & (np.arange(P + N) < P)
    return np.asarray(jnp.concatenate(got, axis=1)), np.asarray(want), prefilled, real & ~prefilled


@pytest.mark.parametrize("attention_impl", ["xla", "flash"])
def test_prefill_then_decode_through_the_pass_layer_cache(attention_impl):
    """Pass t of a decoded token attends over entry (t - 1) L + i of the tokens
    before it: the logits of every position, prefilled or decoded, are the
    reference's full forward's. The flash case decodes through the Pallas
    decode kernel (interpreted), entry by entry."""
    got, want, prefilled, decoded = _cached_decode(tiny_config(), attention_impl)
    np.testing.assert_allclose(got[prefilled], want[prefilled], atol=F32_ATOL)
    np.testing.assert_allclose(got[decoded], want[decoded], atol=F32_ATOL)


def test_reading_another_passes_cache_entry_fails_the_comparison():
    """The same decode over a cache whose first two passes' entries changed
    places after the prefill: the prefilled logits stand, the decoded ones miss."""
    scramble = [3, 4, 5, 0, 1, 2] + list(range(6, 12))
    got, want, prefilled, decoded = _cached_decode(tiny_config(), "xla", scramble)
    np.testing.assert_allclose(got[prefilled], want[prefilled], atol=F32_ATOL)
    assert np.abs(got[decoded] - want[decoded]).max() > 1000 * F32_ATOL


def _ppo_inputs(config, weights, P=10, R=8, B=4):
    ids, mask = inputs(5, B=B, T=P + R, pad=3)
    old_lp, old_v, _ = reference.response_window(weights, config, ids, mask, P, R)
    rng = np.random.default_rng(0)
    noise = lambda scale: jnp.asarray(rng.normal(size=(B, R)) * scale, jnp.float32)
    rewards = jnp.zeros((B, R)).at[:, -1].set(jnp.asarray(rng.uniform(0.1, 0.9, size=B), jnp.float32))
    rmask = jnp.ones((B, R), jnp.float32)
    adv, ret = base.gae(old_v + noise(0.05), rewards, rmask, 1.0, 0.95)
    return ids, mask, old_lp + noise(0.1), old_v + noise(0.05), adv, ret, rmask


@pytest.mark.parametrize("remat", ["none", "dots_saveable", "full"])
def test_the_ppo_losss_gradient_matches_the_reference_leaf_by_leaf(remat):
    """trlX's clipped PPO loss over the response window, differentiated through
    the program's four passes (with and without recomputation per block
    application) and through the reference's: every leaf's gradient, entry by
    entry. A layer's leaf is used four times a forward, so its gradient is the
    sum over the passes, which a single walk's would miss by most of its size;
    the gate takes none. float32 both sides: 1e-4 of a leaf's largest entry
    (the orders of summation differ), which read 2e-6 to 4e-5."""
    config = tiny_config()
    module, like = program(config, remat=remat)
    weights = reference.init_weights(config, 11)
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
        if "exit_gate" in path:
            assert scale == 0.0 and float(np.abs(np.asarray(a)).max()) == 0.0, path
            continue
        assert scale > 0, path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4 * scale, err_msg="/".join(path))
    # one walk's gradient is not the four walks' sum
    one = harness.to_program_tree(family, jax.jit(jax.grad(
        lambda w: loss(*reference.response_window(w, tiny_config(passes=1), ids, mask, P, R)[:2])))(weights),
        like, jnp.float32)
    leaf = lambda tree: np.asarray(tree["transformer"]["layers_1"]["mlp"]["up_proj"]["kernel"])
    assert np.abs(leaf(got) - leaf(one)).max() > 0.3 * np.abs(leaf(got)).max()


def test_the_loops_counters_are_the_references():
    """``loop/hidden_delta_t`` and ``loop/exit_pass_expected`` out of the
    ``loop_stats`` collection against the reference's states and exit
    distribution, means over the real tokens; and nothing is sown (nor the gate
    evaluated) where the caller asks for no such collection."""
    config = tiny_config()
    module, like = program(config)
    weights = dict(reference.init_weights(config, 13))
    weights["exit.b"] = jnp.full((1,), 0.4)  # off the symmetric point
    params = harness.to_program_tree(family, weights, like, jnp.float32)
    ids, mask = inputs(3)
    out, sown = module.apply({"params": params}, ids, mask, mutable=["loop_stats"])
    counters = {k: float(v) for k, v in loop_counters(sown["loop_stats"]).items()}
    assert set(counters) == {"loop/hidden_delta_2", "loop/hidden_delta_3", "loop/hidden_delta_4",
                             "loop/exit_pass_expected"}
    states = reference.hidden_states(weights, config, ids, mask)
    real = np.asarray(mask, np.float32)
    mean = lambda x: float((np.asarray(x) * real).sum() / real.sum())
    for t in (1, 2, 3):
        moved = jnp.linalg.norm(states[t] - states[t - 1], axis=-1) / jnp.linalg.norm(states[t - 1], axis=-1)
        assert counters[f"loop/hidden_delta_{t + 1}"] == pytest.approx(mean(moved), rel=1e-4)
        assert counters[f"loop/hidden_delta_{t + 1}"] > 0.01  # every pass still moves the state
    p = reference.exit_distribution(weights, states)
    expected = mean(sum((t + 1) * p[t] for t in range(4)))
    assert counters["loop/exit_pass_expected"] == pytest.approx(expected, rel=1e-4)
    assert 1.0 < expected < 4.0
    plain = module.apply({"params": params}, ids, mask)
    np.testing.assert_array_equal(np.asarray(plain[0]), np.asarray(out[0]))
    text = jax.jit(lambda p: module.apply({"params": p}, ids, mask)[0]).lower(params).as_text()
    assert "logistic" not in text  # the gate's sigmoid is in no program that does not count


def test_the_passes_are_scoped_and_the_cache_gauges_are_set():
    config = tiny_config()
    module, like = program(config)
    params = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), like)
    ids, mask = inputs(3)
    lowered = jax.jit(lambda p: module.apply({"params": p}, ids, mask)[0]).lower(params)
    assert "loop.pass" in lowered.as_text(debug_info=True)
    gauges.clear("loop/")
    TransformerLM(module.config).init_cache(2, 32)
    assert gauges.get("loop/passes") == 4
    # 4 passes x 3 layers x (k + v) x 4 heads x 16 x float32
    assert gauges.get("loop/cache_bytes_per_token") == 12 * 2 * 4 * 16 * 4


def test_with_one_pass_the_model_is_the_model_without_the_loop():
    """``loop_steps`` 1: the tree and the output are those of the same
    configuration built without the field, the one-pass reference agrees, and
    no existing family gains a leaf, a cache entry or a scope from the new fields."""
    config = tiny_config(passes=1)
    module, like = program(config)
    assert module.config.loop_steps == 1 and module.config.cache_entries == 3
    fields = {f.name: getattr(module.config, f.name) for f in dataclasses.fields(TransformerConfig)}
    fields.pop("loop_steps")
    plain = CausalLMWithValueHead(TransformerConfig(**fields))
    weights = reference.init_weights(config, 7)
    params = harness.to_program_tree(family, weights, like, jnp.float32)
    ids, mask = inputs(1)
    got = module.apply({"params": params}, ids, mask)
    same = plain.apply({"params": params}, ids, mask)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(same[0]))
    assert jax.tree.map(lambda a: a.shape, jax.eval_shape(
        lambda: plain.init(jax.random.PRNGKey(0), ids, mask))["params"]) == jax.tree.map(lambda a: a.shape, like)
    want, _ = reference.forward(weights, config, ids, mask)
    real = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(got[0])[real], np.asarray(want)[real], atol=F32_ATOL)
    # four passes build the very same tree: a parameter is one leaf used four times
    assert jax.tree.map(lambda a: a.shape, program(tiny_config())[1]) == jax.tree.map(lambda a: a.shape, like)
    for name in ("gpt2", "llama", "kimi_vl"):
        c = get_preset(name, dict(num_layers=2, hidden_size=32, num_heads=2, vocab_size=64, intermediate_size=64))
        assert c.loop_steps == 1 and not c.sandwich_norms and not c.exit_gate and c.cache_entries == 2
        if name == "kimi_vl":
            continue
        model = TransformerLM(c)
        tree = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32)))
        assert set(tree) == {"params"}  # no loop_stats collection either
        names = {"/".join(path) for path, _ in harness._paths(tree["params"])}
        assert not [n for n in names if any(new in n for new in ("_post", "exit_gate"))]
        assert len(model.init_cache(1, 8)["k"]) == 2
        text = jax.jit(lambda p: model.apply(p, jnp.zeros((1, 2), jnp.int32))[0]).lower(tree).as_text(debug_info=True)
        assert "loop.pass" not in text


REFUSED = {
    "scan_layers": (dict(scan_layers=True), ValueError, "walk the stack loop_steps times"),
    "pipeline": (dict(pipeline_stages=3), ValueError, "walk the stack loop_steps times"),
    "kv_cache_quant": (dict(kv_cache_quant=True), ValueError, "later passes read what the earlier ones rounded"),
    "early_exit_threshold": (dict(early_exit_threshold=0.9), ValueError, "leave at different passes"),
    "final_norm": (dict(final_norm=False), ValueError, "final norm's output"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_layouts_for_later_issues_are_refused_with_the_reason(case):
    overrides, error, reason = REFUSED[case]
    with pytest.raises(error, match=reason):
        program(tiny_config(), **overrides)


@pytest.mark.parametrize("entry", ["init_paged_cache", "paged_decode", "paged_verify"])
def test_the_paged_engine_is_refused_with_the_reason(entry):
    module, like = program(tiny_config())
    trunk = TransformerLM(module.config)
    if entry == "init_paged_cache":
        with pytest.raises(ValueError, match="one for every \\(pass, layer\\)"):
            trunk.init_paged_cache(8, 16, 4, 2)
        return
    cache = TransformerLM(module.config.replace(loop_steps=1)).init_paged_cache(8, 16, 4, 2)
    with pytest.raises(ValueError, match="one for every \\(pass, layer\\)"):
        trunk.apply({"params": like["transformer"]}, jnp.zeros((2, 1), jnp.int32), cache, method=getattr(trunk, entry))


@pytest.mark.parametrize("case", ["branch_capture", "forward_from", "value_branch", "branch_subtree"])
def test_a_branch_off_a_looped_trunk_is_refused_with_the_reason(case):
    """No frozen trunk under unfrozen top layers: the top layers feed the
    bottom ones of the next pass. ``num_layers_unfrozen > 0`` reaches
    ``branch_param_subtree`` when the trainer is built, ``num_value_layers_unfrozen
    > 0`` the policy module's set-up."""
    config = tiny_config()
    module, like = program(config)
    ids, mask = inputs(1)
    reason = "no frozen trunk under unfrozen top layers"
    if case == "branch_capture":
        with pytest.raises(NotImplementedError, match=reason):
            module.apply({"params": like}, ids, mask, branch_layer=1)
    elif case == "forward_from":
        with pytest.raises(NotImplementedError, match=reason):
            module.apply({"params": like}, jnp.zeros((4, 48, 64)), mask, None, 1, method=module.forward_branch)
    elif case == "value_branch":
        with pytest.raises(ValueError, match=reason):
            CausalLMWithValueHead(module.config, num_value_layers=1).init(jax.random.PRNGKey(0), ids, mask)
    else:
        with pytest.raises(ValueError, match=reason):
            branch_param_subtree(like["transformer"], 1, module.config)


def test_sharding_rules_name_the_new_leaves(mesh8):
    from jax.sharding import PartitionSpec as P

    from trlx_tpu.parallel.sharding import default_lm_rules, make_param_shardings, spec_for_path

    _, like = program(tiny_config())
    trunk = make_param_shardings(like, mesh8)["transformer"]
    assert trunk["exit_gate"]["kernel"].spec == P("fsdp", None) and trunk["exit_gate"]["bias"].spec == P()
    assert trunk["layers_1"]["ln_1_post"]["scale"].spec == P() == trunk["layers_1"]["ln_2_post"]["scale"].spec
    assert trunk["layers_1"]["attn"]["k_proj"]["kernel"].spec == P("fsdp", "model")
    # by rules of their own, not by the catch-all
    rules = default_lm_rules()[:-1]
    for path in ("transformer/exit_gate/kernel", "transformer/exit_gate/bias", "transformer/layers_0/ln_1_post/scale"):
        assert any(re.match(pattern, path) for pattern, _ in rules), path
    assert spec_for_path("transformer/exit_gate/kernel", default_lm_rules()) == P("fsdp", None)


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
    sd = {
        "model.embed_tokens.weight": w["wte"], "model.norm.weight": w["ln_f.g"], "lm_head.weight": w["head.w"].T,
        "model.early_exit_gate.weight": w["exit.w"].T, "model.early_exit_gate.bias": w["exit.b"],
    }
    published = {"ln_1": "input_layernorm", "ln_1_post": "input_layernorm_2",
                 "ln_2": "post_attention_layernorm", "ln_2_post": "post_attention_layernorm_2"}
    for i in range(3):
        for ours, theirs in published.items():
            sd[f"model.layers.{i}.{theirs}.weight"] = w[f"h.{ours}.g"][i] + 0.01 * (i + 1)  # told apart
        for n in "qkvo":
            sd[f"model.layers.{i}.self_attn.{n}_proj.weight"] = w[f"h.{n}.w"][i].T
        for n in ("gate", "up", "down"):
            sd[f"model.layers.{i}.mlp.{n}_proj.weight"] = w[f"h.{n}.w"][i].T
    for ours in published:
        w[f"h.{ours}.g"] = w[f"h.{ours}.g"] + 0.01 * np.arange(1, 4, dtype=np.float32)[:, None]

    loaded = hf_state_dict_to_params("ouro", sd, module.config)
    assert jax.tree.map(np.shape, loaded) == jax.tree.map(lambda a: a.shape, like["transformer"])
    ids, mask = inputs(4, B=2, T=16, pad=3)
    logits, _, _, _ = TransformerLM(module.config).apply({"params": loaded}, ids, mask)
    want, _ = reference.forward({k: jnp.asarray(v) for k, v in w.items()}, config, ids, mask)
    real = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(logits)[real], np.asarray(want)[real], atol=F32_ATOL)

    back = params_to_hf_state_dict("ouro", loaded, module.config)
    assert set(back) == set(sd)
    for name in sd:
        np.testing.assert_array_equal(back[name], sd[name], err_msg=name)

    hf = make_hf_config("ouro", PRESETS["ouro"])
    assert hf.model_type == "ouro" and hf.total_ut_steps == 4 and hf.num_hidden_layers == 48 and hf.head_dim == 128
    assert from_hf_config(hf) == PRESETS["ouro"].replace(num_kv_heads=16)  # the preset leaves it to num_heads
    with pytest.raises(ValueError, match="use_sliding_window"):
        from_hf_config(SimpleNamespace(**{**hf.to_dict(), "use_sliding_window": True}))
    preset = PRESETS["ouro"]
    assert (preset.loop_steps, preset.sandwich_norms, preset.exit_gate, preset.dim_per_head) == (4, True, True, 128)
    assert preset.cache_entries == 192 and preset.ffn_dim == 5632 and not preset.tie_word_embeddings
