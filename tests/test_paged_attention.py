"""Paged-KV decode attention parity: XLA gather path vs the fused Pallas
kernel (interpret mode on CPU) vs a dense numpy-style reference, for both the
bf16 and int8 (scale-per-row) pool layouts, including prefix-shared blocks and
mid-batch slot replacement. Plus the paged end-to-end check: token-by-token
``TransformerLM.paged_decode`` must reproduce the contiguous-cache decode."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trlx_tpu.models.presets import PRESETS
from trlx_tpu.models.transformer import TransformerLM
from trlx_tpu.ops.kv_cache import quantize_kv_rows
from trlx_tpu.ops.paged_attention import (
    paged_attention_pallas,
    paged_attention_xla,
    paged_decode_attention,
    paged_pool_layout,
    paged_verify_attention,
    paged_verify_attention_pallas,
    paged_verify_attention_xla,
    write_paged_kv,
    write_paged_kv_multi,
)

pytestmark = pytest.mark.serving

B, HKV, REP, D = 3, 2, 2, 8
NB, BS, MB = 10, 4, 4  # 10 blocks of 4 tokens, up to 16 tokens per slot


def _dense_tokens(pool, tables):
    """Head-major pool [NB, Hkv, BS, ...] through [B, MB] tables -> f64
    [B, MB*BS, Hkv, ...]: each slot's tokens in order, one row per token."""
    g = np.asarray(pool, np.float64)[np.asarray(tables)]  # [B, MB, Hkv, BS, ...]
    g = np.swapaxes(g, 2, 3)
    return g.reshape((g.shape[0], MB * BS) + g.shape[3:])


def _dense_reference(q, k_pool, v_pool, tables, lens, k_scale=None, v_scale=None):
    """Gather into dense [B, S, Hkv, D] f64 arrays and do plain softmax attention."""
    q = np.asarray(q, np.float64)
    kd, vd = _dense_tokens(k_pool, tables), _dense_tokens(v_pool, tables)
    if k_scale is not None:
        ks, vs = _dense_tokens(k_scale, tables), _dense_tokens(v_scale, tables)
    out = np.zeros((B, HKV * REP, D))
    for b in range(B):
        for h in range(HKV * REP):
            kh = h // REP
            L = int(lens[b])
            scores = kd[b, :L, kh] @ q[b, h] / np.sqrt(D)
            if k_scale is not None:
                scores = scores * ks[b, :L, kh]
            p = np.exp(scores - scores.max())
            p /= p.sum()
            if v_scale is not None:
                p = p * vs[b, :L, kh]
            out[b, h] = p @ vd[b, :L, kh]
    return out


def _make_pools(rng, quant):
    """Pools + a block table with a PREFIX-SHARED block (slots 0 and 1 both
    map their first block to physical block 1) and a mid-batch-replaced slot
    (slot 2 got fresh blocks from a later admission wave, short context)."""
    kf = rng.standard_normal((NB, HKV, BS, D)).astype(np.float32)
    vf = rng.standard_normal((NB, HKV, BS, D)).astype(np.float32)
    tables = np.array(
        [[1, 2, 3, 0], [1, 4, 0, 0], [7, 8, 0, 0]], np.int32
    )
    lens = np.array([11, 6, 2], np.int32)
    if not quant:
        return jnp.asarray(kf), jnp.asarray(vf), None, None, tables, lens, kf, vf
    # quantize_kv_rows scales each [D] row on its own, whatever leads it
    k_pool, ks = quantize_kv_rows(jnp.asarray(kf))
    v_pool, vs = quantize_kv_rows(jnp.asarray(vf))
    # the dense reference consumes raw int8 + scales the same way
    return (k_pool, v_pool, ks[..., 0], vs[..., 0], tables, lens,
            np.asarray(k_pool), np.asarray(v_pool))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
def test_xla_matches_pallas_and_dense(quant):
    rng = np.random.default_rng(0)
    k_pool, v_pool, k_scale, v_scale, tables, lens, kraw, vraw = _make_pools(rng, quant)
    q = jnp.asarray(rng.standard_normal((B, HKV * REP, D)).astype(np.float32))

    ref = _dense_reference(q, kraw, vraw, tables, lens, k_scale, v_scale)
    out_xla = paged_attention_xla(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens),
        k_scale=None if k_scale is None else jnp.asarray(k_scale),
        v_scale=None if v_scale is None else jnp.asarray(v_scale),
    )
    out_pl = paged_attention_pallas(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens),
        k_scale=None if k_scale is None else jnp.asarray(k_scale),
        v_scale=None if v_scale is None else jnp.asarray(v_scale),
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out_xla), ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_pl), ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_xla), np.asarray(out_pl), rtol=1e-5, atol=1e-5)


def test_prefix_shared_block_reads_identical_kv():
    """Slots 0 and 1 share physical block 1: attention over the shared region
    must read the same K/V rows for both slots (the whole point of ref-counted
    prefix sharing)."""
    rng = np.random.default_rng(1)
    k_pool, v_pool, _, _, tables, _, _, _ = _make_pools(rng, quant=False)
    q = jnp.asarray(np.repeat(rng.standard_normal((1, HKV * REP, D)), B, 0).astype(np.float32))
    lens = np.array([BS, BS, BS], np.int32)  # all three attend over one block
    out = np.asarray(paged_attention_xla(q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens)))
    # identical query + same physical block -> identical outputs for 0 and 1
    np.testing.assert_array_equal(out[0], out[1])
    # slot 2 reads different blocks -> different output
    assert np.abs(out[0] - out[2]).max() > 1e-3


def test_mid_batch_replacement_changes_only_that_slot():
    """Swapping one slot's table+len (new admission into a freed slot) must
    not perturb the other slots' outputs — the decode step has no cross-slot
    data flow."""
    rng = np.random.default_rng(2)
    k_pool, v_pool, _, _, tables, lens, _, _ = _make_pools(rng, quant=False)
    q = jnp.asarray(rng.standard_normal((B, HKV * REP, D)).astype(np.float32))
    before = np.asarray(paged_attention_xla(q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens)))
    tables2 = tables.copy()
    tables2[1] = [5, 6, 0, 0]  # fresh blocks for a newly admitted sequence
    lens2 = lens.copy()
    lens2[1] = 7
    after = np.asarray(paged_attention_xla(q, k_pool, v_pool, jnp.asarray(tables2), jnp.asarray(lens2)))
    np.testing.assert_array_equal(before[0], after[0])
    np.testing.assert_array_equal(before[2], after[2])
    assert np.abs(before[1] - after[1]).max() > 1e-3


def test_dispatch_impls():
    rng = np.random.default_rng(3)
    k_pool, v_pool, _, _, tables, lens, _, _ = _make_pools(rng, quant=False)
    q = jnp.asarray(rng.standard_normal((B, HKV * REP, D)).astype(np.float32))
    a = paged_decode_attention(q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens), impl="auto")
    x = paged_decode_attention(q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens), impl="xla")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(x))  # auto == xla off-TPU
    with pytest.raises(ValueError):
        paged_decode_attention(q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens), impl="mosaic")


def _zero_pools(quant):
    """One layer's empty pools in the engine's own layout."""
    return {
        key: jnp.zeros(shape, dtype)
        for key, (shape, dtype) in paged_pool_layout(
            NB, BS, HKV, D, jnp.float32, quant
        ).items()
    }


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
def test_write_paged_kv_lands_at_context_len(quant):
    layout = _zero_pools(quant)
    tables = jnp.asarray(np.array([[1, 2, 3, 0], [4, 5, 0, 0], [6, 0, 0, 0]], np.int32))
    lens = jnp.asarray(np.array([5, 0, 3], np.int32))
    cache = {**layout, "block_tables": tables, "context_lens": lens}
    rng = np.random.default_rng(4)
    k_new = jnp.asarray(rng.standard_normal((B, HKV, D)).astype(np.float32))
    out = write_paged_kv(cache, k_new, k_new * 2)
    k = np.asarray(out["k"], np.float32)
    if quant:
        k = k * np.asarray(out["k_scale"])[..., None]
    # slot 0: len 5 -> block tables[0][1]=2, offset 1; slot 1: len 0 -> block 4
    # offset 0; slot 2: len 3 -> block 6 offset 3
    for b, (blk, off) in enumerate([(2, 1), (4, 0), (6, 3)]):
        np.testing.assert_allclose(k[blk, :, off], np.asarray(k_new)[b], rtol=0.02, atol=0.02)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
def test_paged_decode_matches_contiguous_greedy(quant):
    """Token-by-token ``paged_decode`` == the contiguous-cache decode loop."""
    config = PRESETS["gpt2"].replace(
        vocab_size=37, hidden_size=16, num_layers=2, num_heads=2,
        max_position_embeddings=64, compute_dtype=jnp.float32,
        kv_cache_quant=quant,
    )
    model = TransformerLM(config)
    prompt = np.array([5, 9, 11, 2, 30, 7, 1, 3, 22], np.int32)
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32)
    )["params"]
    n_new, total = 6, 16

    # contiguous reference: prefill into a [1, total] cache (the attention
    # mask covers the cache length, not the prompt length), then step
    ids = jnp.asarray(prompt)[None, :]
    pre_mask = (jnp.arange(total)[None, :] < len(prompt)).astype(jnp.int32)
    cache = {**model.init_cache(1, total), "index": 0}
    positions = jnp.arange(len(prompt))[None, :].astype(jnp.int32)
    logits, _, _, cache = model.apply({"params": params}, ids, pre_mask, positions, cache)
    ref = [int(jnp.argmax(logits[0, -1]))]
    for i in range(n_new - 1):
        mask_i = (jnp.arange(total)[None, :] < len(prompt) + i + 1).astype(jnp.int32)
        tok = jnp.asarray([[ref[-1]]], jnp.int32)
        pos = jnp.asarray([[len(prompt) + i]], jnp.int32)
        logits, _, _, cache = model.apply({"params": params}, tok, mask_i, pos, cache)
        ref.append(int(jnp.argmax(logits[0, -1])))

    # paged path: prefill contiguously, scatter rows into the pools by hand,
    # then drive paged_decode one token at a time
    pcache = model.init_paged_cache(num_blocks=8, block_size=4, max_blocks_per_seq=4, batch_size=1)
    blocks = [1, 2, 3, 4]
    cont = {**model.init_cache(1, total), "index": 0}
    _, _, _, cont = model.apply({"params": params}, ids, pre_mask, positions, cont)
    for li in range(config.num_layers):
        for key in ("k", "v"):
            rows = np.asarray(cont[key][li], np.float32)[0]  # [Hkv, total, D]
            if quant:  # contiguous quantized cache: dequantize to re-pack
                rows = rows * np.asarray(cont[key + "_scale"][li], np.float32)[0]
            pool = np.asarray(pcache[key][li], np.float32 if not quant else np.int8).copy()
            scale = (
                np.asarray(pcache[key + "_scale"][li]).copy() if quant else None
            )
            for t in range(len(prompt)):
                blk, off = blocks[t // 4], t % 4
                row = rows[:, t]  # [Hkv, D]
                if quant:
                    qrow, s = quantize_kv_rows(jnp.asarray(row)[None])
                    pool[blk, :, off] = np.asarray(qrow[0])
                    scale[blk, :, off] = np.asarray(s[0, :, 0])
                else:
                    pool[blk, :, off] = row
            pcache[key][li] = jnp.asarray(pool)
            if quant:
                pcache[key + "_scale"][li] = jnp.asarray(scale)
    pcache["block_tables"] = jnp.asarray(np.array([blocks], np.int32))
    pcache["context_lens"] = jnp.asarray(np.array([len(prompt)], np.int32))

    got = [ref[0]]  # first token comes from prefill logits either way
    for i in range(n_new - 1):
        tok = jnp.asarray([got[-1]], jnp.int32)
        logits, _, pcache = model.apply(
            {"params": params}, tok[:, None], pcache, method=model.paged_decode
        )
        got.append(int(jnp.argmax(logits[0, -1])))
    assert got == ref


# ---------------------------------------------------------- verify widening


def _dense_verify_reference(q, k_pool, v_pool, tables, lens, k_scale=None,
                            v_scale=None):
    """[B, Q, H, D] verify attention, one dense softmax per (slot, query,
    head): query j sees positions < lens[b] + j + 1."""
    B, Q, H, D = q.shape
    qf = np.asarray(q, np.float64)
    kd, vd = _dense_tokens(k_pool, tables), _dense_tokens(v_pool, tables)
    if k_scale is not None:
        ks, vs = _dense_tokens(k_scale, tables), _dense_tokens(v_scale, tables)
    out = np.zeros((B, Q, H, D))
    for b in range(B):
        for j in range(Q):
            L = int(lens[b]) + j + 1
            for h in range(H):
                kh = h // REP
                scores = kd[b, :L, kh] @ qf[b, j, h] / np.sqrt(D)
                if k_scale is not None:
                    scores = scores * ks[b, :L, kh]
                p = np.exp(scores - scores.max())
                p /= p.sum()
                if v_scale is not None:
                    p = p * vs[b, :L, kh]
                out[b, j, h] = p @ vd[b, :L, kh]
    return out


@pytest.mark.parametrize("Q", [1, 2, 3, 4])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
def test_verify_xla_matches_pallas_and_dense(quant, Q):
    """The spec_verify contract across q_len 1..K: XLA widening, fused Pallas
    verify kernel (interpret mode), and the dense reference agree for both
    pool layouts."""
    rng = np.random.default_rng(5)
    k_pool, v_pool, k_scale, v_scale, tables, lens, kraw, vraw = _make_pools(rng, quant)
    lens = np.array([9, 5, 2], np.int32)  # room for Q appended positions
    q = jnp.asarray(rng.standard_normal((B, Q, HKV * REP, D)).astype(np.float32))
    kw = dict(
        k_scale=None if k_scale is None else jnp.asarray(k_scale),
        v_scale=None if v_scale is None else jnp.asarray(v_scale),
    )
    ref = _dense_verify_reference(q, kraw, vraw, tables, lens, k_scale, v_scale)
    out_xla = paged_verify_attention_xla(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens), **kw
    )
    out_pl = paged_verify_attention_pallas(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens),
        interpret=True, **kw
    )
    np.testing.assert_allclose(np.asarray(out_xla), ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_pl), ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(out_xla), np.asarray(out_pl), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
def test_verify_q1_bit_identical_to_decode_path(quant):
    """Q=1 verify with pre-append lens must reproduce the single-token decode
    entry BIT-FOR-BIT (decode passes the post-write count lens+1) — the
    spec_k=0-equivalence anchor: both fold queries into the same grouped-head
    einsum with identical reduction order."""
    rng = np.random.default_rng(6)
    k_pool, v_pool, k_scale, v_scale, tables, lens, _, _ = _make_pools(rng, quant)
    q = rng.standard_normal((B, HKV * REP, D)).astype(np.float32)
    kw = dict(
        k_scale=None if k_scale is None else jnp.asarray(k_scale),
        v_scale=None if v_scale is None else jnp.asarray(v_scale),
    )
    dec = paged_attention_xla(
        jnp.asarray(q), k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lens), **kw
    )
    ver = paged_verify_attention_xla(
        jnp.asarray(q)[:, None], k_pool, v_pool,
        jnp.asarray(tables), jnp.asarray(lens - 1), **kw
    )
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(ver)[:, 0])


def test_verify_dispatch_matches_and_rejects_unknown():
    rng = np.random.default_rng(7)
    k_pool, v_pool, _, _, tables, _, _, _ = _make_pools(rng, quant=False)
    lens = jnp.asarray(np.array([8, 4, 1], np.int32))
    q = jnp.asarray(rng.standard_normal((B, 3, HKV * REP, D)).astype(np.float32))
    a = paged_verify_attention(q, k_pool, v_pool, jnp.asarray(tables), lens, impl="auto")
    x = paged_verify_attention(q, k_pool, v_pool, jnp.asarray(tables), lens, impl="xla")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(x))  # auto == xla off-TPU
    with pytest.raises(ValueError):
        paged_verify_attention(q, k_pool, v_pool, jnp.asarray(tables), lens, impl="mosaic")


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
def test_write_paged_kv_multi_equals_sequential_single_writes(quant):
    """Q-token scatter == Q sequential single-token writes, bit-for-bit —
    including the per-row quantization (rows quantize independently in both
    paths)."""
    Q = 3
    layout = _zero_pools(quant)
    tables = jnp.asarray(np.array([[1, 2, 3, 0], [4, 5, 0, 0], [6, 9, 0, 0]], np.int32))
    lens = np.array([3, 0, 6], np.int32)  # slot 0 straddles a block boundary
    rng = np.random.default_rng(8)
    k_new = jnp.asarray(rng.standard_normal((B, Q, HKV, D)).astype(np.float32))
    v_new = jnp.asarray(rng.standard_normal((B, Q, HKV, D)).astype(np.float32))

    multi = write_paged_kv_multi(
        {**layout, "block_tables": tables, "context_lens": jnp.asarray(lens)},
        k_new, v_new,
    )
    seq = {**layout, "block_tables": tables, "context_lens": jnp.asarray(lens)}
    for j in range(Q):
        seq = write_paged_kv(seq, k_new[:, j], v_new[:, j])
        seq["context_lens"] = seq["context_lens"] + 1
    for key in layout:
        np.testing.assert_array_equal(np.asarray(multi[key]), np.asarray(seq[key]))


def test_write_paged_kv_multi_drops_positions_past_the_table():
    """Positions >= max_blocks*block_size must be dropped outright (not wrap,
    not corrupt the null block beyond what padding already does)."""
    layout = _zero_pools(quant=False)
    tables = jnp.asarray(np.array([[1, 0, 0, 0]] * B, np.int32))
    lens = jnp.asarray(np.array([MB * BS - 1, MB * BS - 1, MB * BS - 1], np.int32))
    k_new = jnp.ones((B, 2, HKV, D), jnp.float32)  # position 0 in-range, 1 past
    out = write_paged_kv_multi(
        {**layout, "block_tables": tables, "context_lens": lens}, k_new, k_new
    )
    k = np.asarray(out["k"])
    assert k.sum() > 0  # the in-range position landed...
    written = np.argwhere(np.abs(k).sum(axis=(1, 3)) > 0)  # (block, offset)
    assert {tuple(w) for w in written} <= {(0, BS - 1)}  # ...only at table reach


def test_paged_branch_rejects_multi_token_steps():
    config = PRESETS["gpt2"].replace(
        vocab_size=37, hidden_size=16, num_layers=2, num_heads=2,
        max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    model = TransformerLM(config)
    params = model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32)
    )["params"]
    cache = model.init_paged_cache(num_blocks=4, block_size=4, max_blocks_per_seq=2, batch_size=1)
    with pytest.raises(ValueError, match="single-token"):
        model.apply(
            {"params": params}, jnp.ones((1, 2), jnp.int32), cache,
            method=model.paged_decode,
        )
