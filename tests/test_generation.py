"""Generation-engine tests: cached greedy decode must equal a naive full-forward
re-computation loop; eos handling; sampling filters."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.models.presets import PRESETS
from trlx_tpu.models.transformer import TransformerLM
from trlx_tpu.ops.generation import generate, left_pad_batch, pad_to_bucket
from trlx_tpu.ops.sampling import apply_top_k, apply_top_p, sample_token

TINY = dict(
    vocab_size=37, hidden_size=16, num_layers=2, num_heads=2,
    max_position_embeddings=64, compute_dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def tiny_model():
    config = PRESETS["gpt2"].replace(**TINY)
    model = TransformerLM(config)
    rng = jax.random.PRNGKey(0)
    ids = jnp.ones((1, 4), jnp.int32)
    params = model.init(rng, ids, jnp.ones_like(ids))["params"]
    return model, params, config


def model_step_fn(model):
    def step(params, ids, mask, positions, cache):
        logits, hidden, _, cache = model.apply({"params": params}, ids, mask, positions, cache)
        return logits, hidden, cache

    return step


def naive_greedy(model, params, prompt, n_new):
    """Reference loop: full forward each step, argmax over the last position."""
    ids = np.asarray(prompt, dtype=np.int32)[None, :]
    for _ in range(n_new):
        logits, *_ = model.apply(
            {"params": params}, jnp.asarray(ids), jnp.ones_like(jnp.asarray(ids))
        )
        nxt = int(jnp.argmax(logits[0, -1]))
        ids = np.concatenate([ids, [[nxt]]], axis=1)
    return ids[0]


def test_cached_greedy_matches_naive(tiny_model):
    model, params, config = tiny_model
    prompt = np.array([5, 9, 11, 2, 30], np.int32)
    n_new = 6
    expected = naive_greedy(model, params, prompt, n_new)

    ids, mask = left_pad_batch([prompt], pad_token_id=0, target_len=8)
    out = generate(
        model_step_fn(model), params, lambda b, s: model.init_cache(b, s, jnp.float32),
        jnp.asarray(ids), jnp.asarray(mask), jax.random.PRNGKey(0),
        max_new_tokens=n_new, do_sample=False, pad_token_id=0,
    )
    got = np.asarray(out["sequences"])[0, 8:]
    np.testing.assert_array_equal(got, expected[len(prompt):])


@pytest.mark.parametrize("family", ["bloom", "gpt_bigcode"])
def test_cached_greedy_matches_naive_new_families(family):
    """ALiBi (bloom) and MQA (gpt_bigcode) must decode identically through the
    KV-cache path and the full re-forward path."""
    config = PRESETS[family].replace(
        vocab_size=48, hidden_size=32, num_layers=2, num_heads=4,
        max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    model = TransformerLM(config)
    params = model.init(jax.random.PRNGKey(1), jnp.ones((1, 4), jnp.int32),
                        jnp.ones((1, 4), jnp.int32))["params"]
    prompt = np.array([5, 9, 11, 2, 30], np.int32)
    n_new = 6
    expected = naive_greedy(model, params, prompt, n_new)

    ids, mask = left_pad_batch([prompt], pad_token_id=0, target_len=8)
    out = generate(
        model_step_fn(model), params, lambda b, s: model.init_cache(b, s, jnp.float32),
        jnp.asarray(ids), jnp.asarray(mask), jax.random.PRNGKey(0),
        max_new_tokens=n_new, do_sample=False, pad_token_id=0,
    )
    got = np.asarray(out["sequences"])[0, 8:]
    np.testing.assert_array_equal(got, expected[len(prompt):])


def test_left_padded_batch_generation_consistent(tiny_model):
    """Each sample in a ragged left-padded batch decodes the same as alone."""
    model, params, config = tiny_model
    prompts = [np.array([3, 4, 5], np.int32), np.array([7, 1, 2, 8, 9, 10], np.int32)]
    n_new = 4
    ids, mask = left_pad_batch(prompts, pad_token_id=0, target_len=8)
    out = generate(
        model_step_fn(model), params, lambda b, s: model.init_cache(b, s, jnp.float32),
        jnp.asarray(ids), jnp.asarray(mask), jax.random.PRNGKey(0),
        max_new_tokens=n_new, do_sample=False, pad_token_id=0,
    )
    for i, prompt in enumerate(prompts):
        expected = naive_greedy(model, params, prompt, n_new)
        got = np.asarray(out["sequences"])[i, 8:]
        np.testing.assert_array_equal(got, expected[len(prompt):], err_msg=f"sample {i}")


def test_eos_stops_and_masks(tiny_model):
    model, params, config = tiny_model
    prompt = np.array([5, 9, 11], np.int32)
    ids, mask = left_pad_batch([prompt], pad_token_id=0, target_len=4)
    # find which token greedy decode emits first, use it as "eos"
    first = int(
        naive_greedy(model, params, prompt, 1)[-1]
    )
    out = generate(
        model_step_fn(model), params, lambda b, s: model.init_cache(b, s, jnp.float32),
        jnp.asarray(ids), jnp.asarray(mask), jax.random.PRNGKey(0),
        max_new_tokens=5, do_sample=False, pad_token_id=0, eos_token_id=first,
    )
    resp_mask = np.asarray(out["response_mask"])[0]
    seq = np.asarray(out["sequences"])[0, 4:]
    assert resp_mask.tolist() == [1, 0, 0, 0, 0]
    assert seq[0] == first
    assert (seq[1:] == 0).all()


def test_sampling_reproducible_and_filtered(tiny_model):
    model, params, config = tiny_model
    prompt = np.array([1, 2, 3], np.int32)
    ids, mask = left_pad_batch([prompt, prompt], pad_token_id=0, target_len=4)
    kwargs = dict(max_new_tokens=4, do_sample=True, temperature=0.9, top_k=5, pad_token_id=0)
    gen = lambda key: np.asarray(
        generate(
            model_step_fn(model), params, lambda b, s: model.init_cache(b, s, jnp.float32),
            jnp.asarray(ids), jnp.asarray(mask), key, **kwargs
        )["sequences"]
    )
    a = gen(jax.random.PRNGKey(7))
    b = gen(jax.random.PRNGKey(7))
    c = gen(jax.random.PRNGKey(8))
    np.testing.assert_array_equal(a, b)
    assert not (a == c).all()


def test_top_k_top_p_filters():
    logits = jnp.array([[1.0, 2.0, 3.0, 4.0]])
    k2 = apply_top_k(logits, 2)
    assert np.asarray(k2[0, :2] < -1e8).all() and np.isfinite(np.asarray(k2[0, 2:])).all()
    # top_p=0.5: keep smallest set with cumulative prob >= 0.5 (here just token 3)
    p5 = apply_top_p(logits, 0.5)
    kept = np.asarray(p5[0]) > -1e8
    assert kept.tolist() == [False, False, False, True]
    # sampling with top_k=1 is argmax
    tok = sample_token(jax.random.PRNGKey(0), logits, top_k=1)
    assert int(tok[0]) == 3


def test_fused_top_k_top_p_matches_sequential():
    """apply_top_k_top_p (k-subset nucleus cutoff, no full-vocab sort) must keep
    the tokens the sequential top-k -> top-p composition keeps. The two paths
    normalize softmax over different element counts (k vs V), so a token whose
    cumulative mass lands within float eps of p may legitimately flip — accept
    mismatches only at such boundary tokens."""
    from trlx_tpu.ops.sampling import apply_top_k_top_p

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32) * 3)
    for k in (1, 2, 8, 63):
        for p in (0.1, 0.5, 0.9, 1.0):
            fused = np.asarray(apply_top_k_top_p(logits, k, p)) > -1e8
            seq = np.asarray(apply_top_p(apply_top_k(logits, k), p)) > -1e8
            if (fused == seq).all():
                continue
            assert p < 1.0, (k, p)  # p>=1 has no nucleus boundary: must be exact
            # any disagreement must sit AT the nucleus boundary: the mass
            # accumulated *before* the mismatched token itself (its keep
            # condition is cum[rank-1] < p) is within float eps of p
            lg = np.asarray(logits)
            order = np.argsort(-lg, axis=-1)  # descending ranks per row
            vals = np.take_along_axis(lg, order, axis=-1)[:, :k]
            probs = np.exp(vals - vals.max(-1, keepdims=True))
            probs /= probs.sum(-1, keepdims=True)
            cum = probs.cumsum(-1)
            rank_of = np.argsort(order, axis=-1)  # vocab idx -> rank
            for b, v in np.argwhere(fused != seq):
                r = int(rank_of[b, v])
                assert 0 < r < k, (k, p, int(b), int(v), r)
                gap = abs(float(cum[b, r - 1]) - p)
                assert gap < 1e-5, (k, p, int(b), int(v), float(gap))


def test_pad_to_bucket():
    assert pad_to_bucket(5, [8, 16]) == 8
    assert pad_to_bucket(9, [8, 16]) == 16
    assert pad_to_bucket(40, [8, 16]) == 64


@pytest.mark.parametrize(
    "length,cap,padded",
    [(9, 9, 9), (5, 9, 8), (8, 8, 8), (40, 50, 50), (10, 9, 16), (9, None, 16), (1, 0, 8)],
)
def test_pad_to_bucket_stops_at_its_cap(length, cap, padded):
    """A length within the cap pads to the rung or the cap, whichever is smaller; one past the
    cap, or with no cap, pads to the rung."""
    assert pad_to_bucket(length, [8, 16], cap=cap) == padded


@pytest.mark.parametrize("layout", ["list", "stacked", "gqa"])
def test_int8_kv_cache_decode_matches_fp_cache(layout):
    """kv_cache_quant=True: decode over an int8 KV cache (per-row symmetric
    quantization, scales per (b,h,slot)) must track the full-precision cache —
    same logits up to quantization noise and near-identical greedy choices."""
    overrides = dict(TINY)
    if layout == "stacked":
        overrides["scan_layers"] = True
    if layout == "gqa":
        overrides.update(num_heads=4, num_kv_heads=2, hidden_size=32)
    base = PRESETS["gpt2"].replace(**overrides)
    model = TransformerLM(base)
    rng = jax.random.PRNGKey(3)
    ids = jnp.ones((1, 4), jnp.int32)
    params = model.init(rng, ids, jnp.ones_like(ids))["params"]
    qmodel = TransformerLM(base.replace(kv_cache_quant=True))

    prompts = [np.array([5, 9, 11, 2, 30], np.int32), np.array([7, 3], np.int32)]
    pids, pmask = left_pad_batch(prompts, pad_token_id=0, target_len=8)
    outs = {}
    for name, m in (("fp", model), ("int8", qmodel)):
        outs[name] = generate(
            model_step_fn(m), params, lambda b, s, m=m: m.init_cache(b, s),
            jnp.asarray(pids), jnp.asarray(pmask), jax.random.PRNGKey(0),
            max_new_tokens=6, do_sample=False, pad_token_id=0,
        )
    cache = qmodel.init_cache(2, 8)
    assert cache["k"][0].dtype == jnp.int8 if isinstance(cache["k"], list) else cache["k"].dtype == jnp.int8
    # greedy paths agree except where quantization noise flips a near-tie
    fp = np.asarray(outs["fp"]["sequences"])[:, 8:]
    q8 = np.asarray(outs["int8"]["sequences"])[:, 8:]
    agree = (fp == q8).mean()
    assert agree >= 0.75, (fp, q8)

    # teacher-forced single-token decode over a pad-free prompt: logits must
    # stay close to the cache-free forward (drift = accumulated quant noise)
    seq = jnp.asarray(np.array([[5, 9, 11, 2, 30, 7, 3, 22]], np.int32))
    mask = jnp.ones_like(seq)
    ref_logits, *_ = model.apply({"params": params}, seq, mask)
    c = qmodel.init_cache(1, 8)
    logits_steps = []
    for t in range(8):
        lt, _, _, c = qmodel.apply(
            {"params": params}, seq[:, t : t + 1], mask, None, c
        )
        logits_steps.append(lt[:, 0])
    got = jnp.stack(logits_steps, axis=1)
    err = float(jnp.max(jnp.abs(got - ref_logits)))
    assert err < 0.5, err


def test_candidate_space_sampling_distribution_matches_masked_full_vocab():
    """sample_token's k-candidate-space pipeline (top-k select -> nucleus mask
    over the k sorted values -> categorical over k -> gather id) must induce
    the SAME per-token distribution as masking the full-V logits and sampling
    over V: softmax is invariant to NEG_INF entries, so with exact selection
    the two are analytically equal. Compared via probabilities (scattered
    k-space softmax vs full-V softmax of the fused mask), not samples — the
    RNG draw shapes differ by construction."""
    from trlx_tpu.ops.sampling import apply_top_k_top_p

    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(4, 97)).astype(np.float32) * 2.5)
    for k, p in ((1, 1.0), (5, 1.0), (13, 0.9), (50, 0.5)):
        vals, idx = jax.lax.top_k(logits, k)
        if p < 1.0:
            probs_k = jax.nn.softmax(vals, axis=-1)
            cum = jnp.cumsum(probs_k, axis=-1)
            keep = jnp.concatenate(
                [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < p], axis=-1
            )
            vals = jnp.where(keep, vals, -1e9)
        cand_probs = jax.nn.softmax(vals, axis=-1)  # [B, k]
        scattered = np.zeros(logits.shape, np.float64)
        np.put_along_axis(scattered, np.asarray(idx), np.asarray(cand_probs, np.float64), -1)
        ref_probs = np.asarray(jax.nn.softmax(apply_top_k_top_p(logits, k, p), axis=-1))
        np.testing.assert_allclose(scattered, ref_probs, atol=2e-6)


def test_sample_token_candidate_space_impls():
    """Exact selection carries hard guarantees: k=1 is argmax, and every
    sampled token's logit is >= the true k-th value. The approx default only
    promises an *expected* recall (0.95) — no per-element floor exists on TPU's
    binned selection — so for it the test pins just the contract that holds on
    every backend: jits, returns in-range int32 ids, deterministic per key."""
    rng = np.random.default_rng(11)
    logits = jnp.asarray(rng.normal(size=(16, 211)).astype(np.float32) * 3)
    k = 8

    tok1 = jax.jit(lambda r, l: sample_token(r, l, top_k=1, top_k_impl="exact"))(
        jax.random.PRNGKey(0), logits
    )
    np.testing.assert_array_equal(np.asarray(tok1), np.asarray(jnp.argmax(logits, -1)))
    tok = jax.jit(lambda r, l: sample_token(r, l, top_k=k, top_p=0.9, top_k_impl="exact"))(
        jax.random.PRNGKey(1), logits
    )
    floor = np.asarray(jax.lax.top_k(logits, k)[0][:, -1])
    sampled_logit = np.asarray(logits)[np.arange(logits.shape[0]), np.asarray(tok)]
    assert (sampled_logit >= floor - 1e-6).all()

    fn = jax.jit(lambda r, l: sample_token(r, l, top_k=k, top_p=0.9))  # approx default
    ta = fn(jax.random.PRNGKey(2), logits)
    tb = fn(jax.random.PRNGKey(2), logits)
    assert ta.dtype == jnp.int32 and ta.shape == (16,)
    np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))
    assert (np.asarray(ta) >= 0).all() and (np.asarray(ta) < 211).all()


# name: (config overrides, mesh axes or None, cache dtype, whether an eos token ends rows inside the run)
DECODE_KERNEL_CASES = {
    "mha": (dict(), None, jnp.float32, False),
    "mha-eos-inside": (dict(), None, jnp.float32, True),
    "grouped-eos-inside": (dict(num_heads=4, num_kv_heads=2, hidden_size=32), None, jnp.float32, True),
    "bfloat16-cache": (dict(compute_dtype=jnp.bfloat16), None, jnp.bfloat16, False),
    "placed-over-a-mesh": (dict(num_heads=4, hidden_size=32), dict(data=2, fsdp=2, model=2), jnp.float32, True),
    "mesh-the-heads-do-not-divide": (dict(), dict(data=2, model=4), jnp.float32, False),
}


@pytest.mark.parametrize("case", sorted(DECODE_KERNEL_CASES))
def test_generate_is_the_same_with_the_decode_kernel_and_the_einsum(case):
    """`attention_impl="flash"` sends the single-token steps through the decode
    kernel (and the prefill through the flash kernel), `"xla"` keeps the einsum:
    the same tokens and the same `response_mask`, left-padded prompts of
    different lengths, rows ending on an eos at different steps."""
    import contextlib

    from trlx_tpu.ops import attention
    from trlx_tpu.parallel.mesh import make_mesh

    overrides, axes, cache_dtype, with_eos = DECODE_KERNEL_CASES[case]
    base = PRESETS["gpt2"].replace(**{**TINY, **overrides})
    prompts = [np.array(p, np.int32) for p in ([5, 9, 11, 2, 30, 7, 7], [3], [8, 1, 4], [6, 6, 12, 19, 2])]
    ids, mask = left_pad_batch(prompts, pad_token_id=0, target_len=8)
    ids, mask = jnp.asarray(ids), jnp.asarray(mask)
    params = TransformerLM(base).init(jax.random.PRNGKey(0), ids, mask)["params"]
    n_new = 12
    mesh = make_mesh(**axes) if axes else None

    def run(impl, eos):
        model = TransformerLM(base.replace(attention_impl=impl))
        fn = jax.jit(lambda params, ids, mask: generate(
            model_step_fn(model), params, lambda b, s: model.init_cache(b, s, cache_dtype), ids, mask,
            jax.random.PRNGKey(0), max_new_tokens=n_new, do_sample=False, pad_token_id=0, eos_token_id=eos,
        ))
        with mesh or contextlib.nullcontext():
            return jax.tree.map(np.asarray, fn(params, ids, mask))

    eos = None
    if with_eos:  # a token that some row emits inside the run, and not all rows at once
        free = run("xla", None)["sequences"][:, 8:]
        eos = int(free[0, 3])
    want, got = run("xla", eos), run("flash", eos)
    np.testing.assert_array_equal(got["sequences"], want["sequences"])
    np.testing.assert_array_equal(got["response_mask"], want["response_mask"])
    if with_eos:
        lengths = want["response_mask"].sum(axis=1)
        assert lengths.min() < n_new, lengths
    with mesh or contextlib.nullcontext():
        cache = jax.eval_shape(lambda: TransformerLM(base).init_cache(len(prompts), 8 + n_new))
        placed = attention.decode_kernel_placement(
            "flash", base.biased_attention, {"k": cache["k"][0], "v": cache["v"][0]}, base.num_heads, len(prompts))[0]
    assert placed == (case != "mesh-the-heads-do-not-divide")


# name: (config overrides, mesh axes or None, rows, the cache's (rows, kv heads) once folded)
FOLDED_GENERATE_CASES = {
    "64-rows-2-heads": (dict(), None, 64, (128, 1)),
    "16-rows-grouped-the-heads-cap-the-fold": (dict(num_heads=8, num_kv_heads=4, hidden_size=32), None, 16, (64, 1)),
    "placed-over-a-mesh": (dict(num_heads=4, hidden_size=32), dict(data=2, fsdp=2, model=2), 16, (32, 2)),
}


@pytest.mark.parametrize("case", sorted(FOLDED_GENERATE_CASES))
def test_generate_gives_the_same_tokens_with_kv_heads_folded_beside_the_rows(case, monkeypatch):
    """``generate`` end to end through the decode kernel over a cache that holds kv heads beside
    the rows (what a batch under 128 rows gets) against the same kernel over the cache unfolded
    (the chooser made to say 1): the same tokens, the same ``response_mask``, rows ending on an
    eos at different steps, left-padded prompts of different lengths."""
    import contextlib

    from trlx_tpu.ops import attention
    from trlx_tpu.parallel.mesh import make_mesh

    overrides, axes, rows, folded_shape = FOLDED_GENERATE_CASES[case]
    config = PRESETS["gpt2"].replace(**{**TINY, **overrides, "attention_impl": "flash"})
    model = TransformerLM(config)
    rng = np.random.default_rng(rows)
    prompts = [rng.integers(1, 37, size=rng.integers(1, 9)).astype(np.int32) for _ in range(rows)]
    ids, mask = left_pad_batch(prompts, pad_token_id=0, target_len=8)
    ids, mask = jnp.asarray(ids), jnp.asarray(mask)
    params = model.init(jax.random.PRNGKey(0), ids[:2], mask[:2])["params"]
    n_new = 10
    mesh = make_mesh(**axes) if axes else None

    def run(eos):
        fn = jax.jit(lambda params, ids, mask: generate(
            model_step_fn(model), params, lambda b, s: model.init_cache(b, s, jnp.float32), ids, mask,
            jax.random.PRNGKey(0), max_new_tokens=n_new, do_sample=False, pad_token_id=0, eos_token_id=eos,
        ))
        with mesh or contextlib.nullcontext():
            layout = config.cache_layout(rows, 8 + n_new)
            return jax.tree.map(np.asarray, fn(params, ids, mask)), layout["k"][0][:2]

    eos = int(run(None)[0]["sequences"][0, 8 + 3])  # a token that some row emits inside the run
    got, shape = run(eos)
    assert shape == folded_shape
    monkeypatch.setattr(attention, "choose_decode_fold", lambda B, Hkv: 1)
    want, shape = run(eos)
    assert shape == (rows, config.kv_heads)
    np.testing.assert_array_equal(got["sequences"], want["sequences"])
    np.testing.assert_array_equal(got["response_mask"], want["response_mask"])
    lengths = want["response_mask"].sum(axis=1)
    assert lengths.min() < n_new < lengths.max() + 1, lengths


CELL_1 = dict(B=128, prompt_len=64, new_tokens=448, steps=447)  # gpt2.ppo-long-response's rollout


@pytest.mark.parametrize(
    "overrides,axes,low,high",
    [
        (dict(attention_impl="flash"), None, 0.56, 0.58),  # 56 % of the slots hold a token, rounded up to blocks of 8
        (dict(attention_impl="xla"), None, 1.0, 1.0),
        (dict(attention_impl="flash", kv_cache_quant=True), None, 1.0, 1.0),
        (dict(attention_impl="flash", pos_embedding="alibi"), None, 1.0, 1.0),
        (dict(attention_impl="flash", peft_type="prefix", num_virtual_tokens=4), None, 1.0, 1.0),
        (dict(attention_impl="flash", attention_kind="mla"), None, 1.0, 1.0),  # its own absorbed decode
        (dict(attention_impl="flash", peft_type="prompt", num_virtual_tokens=8), None, 0.57, 0.60),  # in the cache too
        # a shard's rows and heads: 32 rows of 6 kv heads, folded to 96 of 2, whose smaller slots make blocks of 32
        (dict(attention_impl="flash"), dict(data=4, model=2), 0.56, 0.60),
        (dict(attention_impl="flash"), dict(data=1, model=8), 1.0, 1.0),  # 12 heads over 8: the einsum
    ],
)
def test_cache_read_share_follows_who_takes_the_decode_kernel(overrides, axes, low, high):
    import contextlib

    from trlx_tpu.ops.attention import decode_cache_read_share
    from trlx_tpu.parallel.mesh import make_mesh

    config = PRESETS["gpt2"].replace(compute_dtype=jnp.bfloat16, **overrides)

    def share(B, prompt_len, new_tokens, steps):  # as MeshRLTrainer.generate asks
        return decode_cache_read_share(
            config.attention_impl, config.biased_attention, config.num_heads,
            config.cache_layout(B, prompt_len + new_tokens), B, new_tokens, steps,
        )

    with make_mesh(**axes) if axes else contextlib.nullcontext():
        assert low <= share(**CELL_1) <= high
    assert share(**{**CELL_1, "steps": 0}) == 1.0
