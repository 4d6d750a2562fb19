"""The keye_vl family (grouped-query attention over the keys a learned indexer
selects, an indexer cache beside keys and values, softmax-routed experts) on the
program's normal path against its plain reference
(``benchmark/reference_keye_vl.py``) at a small size, where the selection bites
(16 of up to 64 keys): through the XLA path and through the selection and
attention kernels (interpreted), through the cache with left-padded prompts,
the PPO loss's gradient, the router, the share of the experts, the frozen
indexer, the counters, planted faults, the refusals, the published names.
Seeded random weights, CPU."""

import functools
import importlib.util
import os
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness
from benchmark.families import keye_vl as family
from keye_vl_tiny import tiny_config
from trlx_tpu.models.policy import CausalLMWithValueHead
from trlx_tpu.models.presets import get_preset
from trlx_tpu.models.transformer import TransformerLM, dsa_counters
from trlx_tpu.ops import sparse_attention as dsa
from trlx_tpu.utils.metrics import gauges

reference = family.reference


def _faults():
    """``benchmark/tests/faults_keye_vl.py``, the faults the chip's readings plant, loaded from its file."""
    path = os.path.join(harness.HERE, "tests", "faults_keye_vl.py")
    spec = importlib.util.spec_from_file_location("faults_keye_vl", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module

#: float32 compute against the float32 reference: the same arithmetic in another order (the kernels' tiles
#: against one softmax, sorted grouped products against every expert on every token): round-off alone,
#: 1e-5 on logits of about 1 (read: 1.8e-7). The selection's own faults move them by 0.05 to 0.2.
F32_ATOL = 1e-5


CONFIG = tiny_config()


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


@functools.lru_cache(maxsize=None)
def _compiled(attention_impl):
    """(module, parameter shapes, jitted forward with its ``dsa_stats``) of the shared
    configuration: one compile a file."""
    module, like = program(CONFIG, attention_impl=attention_impl)
    return module, like, jax.jit(lambda p, i, m: module.apply({"params": p}, i, m, mutable=["dsa_stats"]))


#: the reference's forward over the shared configuration, compiled once a file
reference_forward = jax.jit(lambda w, i, m: reference.forward(w, CONFIG, i, m))


def inputs(seed, B=2, T=64, pads=(5, 0, 9)):
    """Rows left-padded by ``pads`` tokens each; the pad positions hold a token like any other."""
    ids = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 3, 300)
    mask = (jnp.arange(T)[None, :] >= jnp.asarray(pads[:B])[:, None]).astype(jnp.int32)
    return ids, mask


@functools.lru_cache(maxsize=None)
def forwards(attention_impl):
    """(program logits, values, reference logits, values) at the real positions of
    two rows of 64 left-padded by 5 and 0, and the program's ``dsa_stats``."""
    _, like, apply = _compiled(attention_impl)
    weights = reference.init_weights(CONFIG, 7)
    params = harness.to_program_tree(family, weights, like, jnp.float32)
    ids, mask = inputs(1)
    (logits, values, _, _), sown = apply(params, ids, mask)
    want_logits, want_values = reference_forward(weights, ids, mask)
    real = np.asarray(mask) > 0
    return (np.asarray(logits)[real], np.asarray(values)[real], np.asarray(want_logits)[real],
            np.asarray(want_values)[real], sown["dsa_stats"])


def test_reference_covers_every_program_leaf():
    config = tiny_config()
    _, like, _ = _compiled("xla")
    weights = reference.init_weights(config, 7)
    tree = harness.to_program_tree(family, weights, like, jnp.float32)
    assert jax.tree.map(lambda a: a.shape, tree) == jax.tree.map(lambda a: a.shape, like)
    assert {family.leaf_name(path)[0] for path, _ in harness._paths(like)} == set(weights)
    layer = like["transformer"]["layers_0"]
    assert set(layer["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm", "indexer"}
    assert set(layer["attn"]["indexer"]) == {"wq", "wk", "k_norm", "weights_proj"}
    assert set(layer["mlp"]["router"]) == {"kernel"}  # softmax routing: no selection bias
    assert "lm_head" in like["transformer"]


def test_the_reference_draws_the_embedding_at_the_files_embedding_std():
    """The cell's file draws the embedding at ``embedding_std`` (1: routing follows
    the token); a file without the key draws it at ``initializer_range``."""
    cell = harness.load_json("configs", "keye-vl-2.0-30b-a3b.json")
    assert cell["embedding_std"] == 1.0 and cell["initializer_range"] == 0.02
    for config, std in ((tiny_config(), 0.02), (cell, 1.0)):
        spec = reference.weight_spec(config)
        assert spec["wte"][1] == std and spec["head.w"][1] == 0.02 and spec["h.moe.router.w"][1] == 0.02


@pytest.mark.parametrize("attention_impl", ["xla", "flash"])
def test_float32_logits_and_values_match_the_reference(attention_impl):
    logits, values, want_logits, want_values, _ = forwards(attention_impl)
    np.testing.assert_allclose(logits, want_logits, atol=F32_ATOL)
    np.testing.assert_allclose(values, want_values, atol=F32_ATOL)


@pytest.mark.parametrize("fault", ["selection_last_positions", "selection_ignored"])
def test_a_planted_fault_of_the_selection_fails_the_comparison(fault):
    """Dense attention, or the latest keys, in place of the indexer's choice (the
    one entry point both paths select through, here the XLA path's): the logits
    move by thousands of times the tolerance."""
    faults_keye_vl = _faults()
    module, like = program(CONFIG, attention_impl="xla")  # traced anew under the fault
    weights = reference.init_weights(CONFIG, 7)
    ids, mask = inputs(1)
    with faults_keye_vl.FAULTS[fault]():
        logits = jax.jit(lambda p: module.apply({"params": p}, ids, mask)[0])(
            harness.to_program_tree(family, weights, like, jnp.float32))
    real = np.asarray(mask) > 0
    logits, want = np.asarray(logits)[real], np.asarray(reference_forward(weights, ids, mask)[0])[real]
    assert np.abs(logits - want).max() > 1000 * F32_ATOL, np.abs(logits - want).max()


def test_the_selection_keeps_the_top_scores_and_ties_go_to_the_earlier_key():
    """Keys repeated at several slots tie exactly: the kernel's bisection
    (interpreted) keeps what a stable sort keeps, and so do the XLA path's
    ``lax.top_k`` and the reference's sorted row; a row with fewer allowed keys
    than ``topk`` keeps them all."""
    B, T, H, D, topk = 2, 40, 3, 8, 9
    keys = jax.random.normal(jax.random.PRNGKey(1), (B, T, D))
    keys = keys.at[:, 20:30].set(keys[:, 5:6])  # ten slots tie with slot 5
    qi = jax.random.normal(jax.random.PRNGKey(2), (B, T, H, D))
    qi = qi.at[:, :, :, :].set(jnp.broadcast_to(keys[:, 5:6, None], (B, T, H, D)) * jnp.abs(qi))
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (B, T, H)))
    valid = jnp.ones((B, T), jnp.int32).at[1, :4].set(0)
    scores = np.asarray(dsa.index_scores(qi, keys, w))
    allowed = np.asarray(dsa.allowed_pairs(valid, T, T))
    want = np.zeros_like(allowed)
    for b in range(B):
        for t in range(T):
            slots = np.flatnonzero(allowed[b, t])
            order = slots[np.argsort(-scores[b, t, slots], kind="stable")]  # the earlier first among equals
            want[b, t, order[:topk]] = True
    def tied_at_the_threshold(row, ok):
        kept = np.sort(row[ok])[::-1]
        return len(kept) > topk and (kept == kept[topk - 1]).sum() > 1 and kept[topk] == kept[topk - 1]

    ties = [(b, t) for b in range(B) for t in range(T) if tied_at_the_threshold(scores[b, t], allowed[b, t])]
    assert len(ties) > 5  # the case is there: more keys tie at the threshold than are left to keep
    xla = np.asarray(dsa.select_mask_xla(jnp.asarray(scores), jnp.asarray(allowed), topk))
    np.testing.assert_array_equal(xla, want)
    kernel = np.asarray(dsa.select_keys(qi, keys, w, valid, topk, True))[:, :T, :T] > 0
    np.testing.assert_array_equal(kernel, want)
    np.testing.assert_array_equal(np.asarray(reference.select(jnp.asarray(scores), jnp.asarray(allowed), topk)), want)


def test_the_attention_kernels_match_xla_forward_and_backward():
    """The kernels over a selection (forward, dq, dkv, interpreted) against the XLA
    path's masked attention, values and gradients, with grouped heads and a query
    block that selected nothing."""
    B, T, H, Hkv, D = 1, 300, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q, k, v = (jax.random.normal(kk, (B, T, h, D)) for kk, h in zip(ks, (H, Hkv, Hkv)))
    selected = (jax.random.uniform(ks[3], (B, T, T)) < 0.2) & np.tril(np.ones((T, T), bool))
    selected = selected.at[:, 256:].set(False)  # the last query block selects nothing
    Tp, Sp = dsa.padded_lengths(T, T)
    mask = jnp.pad(selected, ((0, 0), (0, Tp - T), (0, Sp - T))).astype(jnp.int8)
    blocks = dsa.block_table(mask)
    assert int(blocks.sum()) < blocks.size  # some blocks are skipped
    g = jax.random.normal(jax.random.PRNGKey(5), (B, T, H, D))
    f_kernel = lambda q, k, v: (dsa.sparse_attention(q, k, v, mask, blocks, 0.25, True) * g).sum()
    f_xla = lambda q, k, v: (dsa.masked_attention_xla(q, k, v, selected, 0.25) * g).sum()
    np.testing.assert_allclose(np.asarray(dsa.sparse_attention(q, k, v, mask, blocks, 0.25, True)),
                               np.asarray(dsa.masked_attention_xla(q, k, v, selected, 0.25)), atol=1e-5)
    for got, want in zip(jax.grad(f_kernel, (0, 1, 2))(q, k, v), jax.grad(f_xla, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@functools.lru_cache(maxsize=None)
def _prefilled(P=40, N=8):
    """Prompts of 36, 40 and 29 tokens left-padded to 40 through the prefill (the
    kernels, interpreted, as on the chip), once a file: (a function that runs the
    8 decode steps after it through a step traced anew, the prefill's logits, the
    reference's full forward, the real positions)."""
    config = tiny_config()
    module, like = program(config, attention_impl="flash")
    trunk = TransformerLM(module.config)
    weights = reference.init_weights(config, 3)
    params = harness.to_program_tree(family, weights, like, jnp.float32)["transformer"]
    ids, mask = inputs(2, B=3, T=P + N, pads=(4, 0, 11))
    want, _ = reference_forward(weights, ids, mask)

    cache = trunk.init_cache(3, P + N)
    assert set(cache) == {"k", "v", "ki", "index"} and len(cache["ki"]) == 2
    assert cache["k"][0].shape == (3, P + N, 2, 16) and cache["ki"][1].shape == (3, P + N, 16)  # slot-major
    seen = mask.at[:, P:].set(0)
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None)
    prefill = jax.jit(lambda p, i, m, pos, c: trunk.apply({"params": p}, i, m, pos, {**c, "index": 0}))
    cache.pop("index")  # a concrete 0 inside the jitted prefill, as generate() gives it
    prefilled_logits, _, _, prefilled_cache = prefill(params, ids[:, :P], seen, positions[:, :P], cache)

    def decode():
        step = jax.jit(lambda p, i, m, pos, c: trunk.apply({"params": p}, i, m, pos, c))
        got, c, m = [], prefilled_cache, seen
        for t in range(P, P + N):
            m = m.at[:, t].set(1)
            logits, _, _, c = step(params, ids[:, t : t + 1], m, positions[:, t : t + 1], c)
            got.append(logits)
        assert int(c["index"]) == P + N
        return np.asarray(jnp.concatenate(got, axis=1))

    return decode, np.asarray(prefilled_logits), np.asarray(want), np.asarray(mask) > 0


def test_prefill_then_decode_through_the_indexer_cache():
    """The prefill writes keys, values and the indexer's keys from slot 0; every
    decode step scores every written slot and attends over the 16 it selects.
    Every position's logits, prefilled or decoded, are the reference's full
    forward's on that row."""
    decode, prefilled, want, real = _prefilled()
    P = prefilled.shape[1]
    np.testing.assert_allclose(prefilled[real[:, :P]], want[:, :P][real[:, :P]], atol=F32_ATOL)
    np.testing.assert_allclose(decode()[real[:, P:]], want[:, P:][real[:, P:]], atol=F32_ATOL)


def test_a_decode_step_that_attends_over_every_slot_fails_the_comparison():
    decode, prefilled, want, real = _prefilled()
    P = prefilled.shape[1]
    original = dsa.decode_selected
    every_slot = lambda *args, topk, scale: original(*args, topk=10 ** 6, scale=scale)
    with mock.patch.object(dsa, "decode_selected", every_slot):
        assert np.abs(decode()[real[:, P:]] - want[:, P:][real[:, P:]]).max() > 1000 * F32_ATOL


def test_the_counters_count_kept_and_causal_pairs():
    """``dsa_stats``: at 64 tokens a row and 16 kept, the query at t keeps
    min(16, t + 1) of its t + 1 causal keys, in each of 2 layers; padded rows count
    their real tokens alone. The collection is computed only where asked for."""
    counters = dsa_counters(forwards("flash")[-1])
    rows = [np.arange(59), np.arange(64)]
    kept = sum(np.minimum(16, t + 1).sum() for t in rows) * 2
    causal = sum((t + 1).sum() for t in rows) * 2
    assert float(counters["dsa/kept_pairs"]) == kept and float(counters["dsa/causal_pairs"]) == causal
    assert float(counters["dsa/queries_off"]) == 0
    module, like, apply = _compiled("flash")
    text = apply.lower(like, *inputs(1)).as_text(debug_info=True)
    for scope in ("dsa.index", "dsa.select", "dsa.attend"):
        assert scope in text, scope
    gauges.clear("dsa/")
    TransformerLM(module.config).init_cache(2, 32)
    # (k + v) x 2 kv heads x 16 + the indexer's 16, float32, in 2 layers
    assert gauges.get("dsa/cache_bytes_per_token") == 2 * (2 * 2 * 16 + 16) * 4
    index, selected = dsa.decode_bytes_per_step(4, 16, 8192, 2048, 4, 128, 64)
    assert (index, selected) == (4 * 16 * 8192 * 64 * 2, 4 * 16 * 2048 * 2 * 4 * 128 * 2)


