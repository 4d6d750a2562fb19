"""Compile the main path's Pallas kernels for a described (not attached) TPU
v5e chip, at the published widths of the models they serve.

Interpret mode, which every other kernel test uses, checks numerics and never
the chip compiler's rules (block shapes against the dtype's tile, VMEM, what
Mosaic can lower). The TPU compiler is installed with jax and compiles for a
topology that is only described, so these cases guard every later PR at no
chip time. Nothing runs: a pass here is a compile, not a chip run.

This is the only file that describes a topology, and it does so inside a
fixture: only one process may load the TPU library, so a call made while any
module is imported would break the other xdist workers' collection.
"""

import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from trlx_tpu.models.presets import PRESETS
from trlx_tpu.models.transformer import TransformerLM
from trlx_tpu.ops import attention
from trlx_tpu.ops.paged_attention import (
    paged_attention_pallas,
    paged_pool_layout,
    paged_verify_attention_pallas,
)

BLOCK_SIZE = 16  # ServingConfig.block_size default
SLOTS, NUM_BLOCKS, MAX_BLOCKS = 32, 1024, 8
FLASH_B, FLASH_T = 8, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _flash(grad, shape=None, kv_heads=None, dtype=jnp.bfloat16, v_width=None):
    c = PRESETS["gpt2"]
    B, H, T, D = shape or (FLASH_B, c.num_heads, FLASH_T, c.dim_per_head)
    kv_shape = (B, T, kv_heads or H, D)  # the kernels' layout: rows, then heads, as a model's projections leave them
    v_shape = kv_shape[:3] + (v_width or D,)

    def fwd(q, k, v, kv_valid):
        return attention.flash_attention(q, k, v, kv_valid, True, None, False)

    def loss(q, k, v, kv_valid):
        return fwd(q, k, v, kv_valid).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    return fn, [((B, T, H, D), dtype), (kv_shape, dtype), (v_shape, dtype), ((B, T), jnp.int32)]


def _grouped_products(grad, tokens, f=1408, k=6):
    """The expert layer's sorted grouped products (``ops/moe.py``) at the
    kimi-vl-a3b cell's shapes: 8 experts of 2048 x 1408 held, 6 assignments a
    token, a row for every assignment; at the lfm2-24b-a2b cell's the experts
    are 1536 wide and a token has 4."""
    from trlx_tpu.ops import moe

    E, d = 8, 2048

    def fwd(x, chosen, weights, gate, up, down):
        return moe.expert_ffn(x, chosen, weights, gate, up, down, expert_offset=0)[0]

    def loss(x, chosen, weights, gate, up, down):
        return fwd(x, chosen, weights, gate, up, down).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 2, 3, 4, 5)) if grad else fwd
    return fn, [
        ((tokens, d), jnp.bfloat16), ((tokens, k), jnp.int32), ((tokens, k), jnp.float32),
        ((E, d, f), jnp.float32), ((E, d, f), jnp.float32), ((E, f, d), jnp.float32),
    ]


def _decode(shape, kv_heads=None, dtype=jnp.bfloat16):
    """The decode kernel as the generator's loop calls it: inside a ``while_loop``, over a cache the
    loop carries in the shape ``ops/kv_cache.py`` lays it out in (``fold`` kv heads beside each row where
    fewer than 128 rows decode: ``[B * fold, Hkv / fold, S, D]``), this step's token just written by
    ``kv_cache``'s own write, the index traced."""
    from trlx_tpu.ops import kv_cache

    B, H, S, D = shape
    Hkv = kv_heads or H
    layout = kv_cache.kv_cache_layout((B, Hkv, S, D), dtype, False, attention.choose_decode_fold(B, Hkv))

    def fn(q, k, v, mask_bias, k_new, v_new, steps):
        def body(carry):
            index, q, cache = carry
            cache = kv_cache.write_kv_cache(cache, k_new, v_new, index)
            return index + 1, attention.decode_attention(q, cache["k"], cache["v"], mask_bias, index), cache

        return jax.lax.while_loop(lambda carry: carry[0] < steps, body, (jnp.int32(1), q, {"k": k, "v": v}))

    return fn, [
        ((B, H, D), dtype), layout["k"], layout["v"], ((B, 1, 1, S), jnp.float32),
        ((B, Hkv, 1, D), dtype), ((B, Hkv, 1, D), dtype), ((), jnp.int32),
    ]


def _while_body(text):
    """The compiled text of the first ``while`` loop's body."""
    name = re.search(r"\bwhile\(.*?body=(%[\w.\-]+)", text).group(1)
    body = text[text.index(f"\n{name} ("):]
    return body[:body.index("\n}\n")]


def _attention_instructions(text):
    """Names of the compiled program's Pallas instructions, as the device trace
    shows them and ``benchmark/metrics/flash_attn_roofline.json`` matches them."""
    return [
        f"{m.group(1)} custom-call"
        for m in re.finditer(r"^\s*(?:ROOT )?(%[\w.\-]+) = .* custom-call\(.*tpu_custom_call", text, re.M)
    ]


def _model_grad():
    """The flash kernels as ``TransformerLM`` calls them: forward, dq and dkv of
    two gpt2-width layers at the learner's length."""
    c = PRESETS["gpt2"].replace(num_layers=2, attention_impl="flash", compute_dtype=jnp.bfloat16)
    model = TransformerLM(c)
    B, T = 2, 513
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((B, T), jnp.int32), jnp.ones((B, T), jnp.int32))["params"]
    )

    def loss(params, ids, mask):
        return model.apply({"params": params}, ids, mask)[0].astype(jnp.float32).sum()

    leaves, treedef = jax.tree_util.tree_flatten(params)

    def fn(ids, mask, *leaves):
        return jax.grad(loss)(jax.tree_util.tree_unflatten(treedef, leaves), ids, mask)

    return fn, [((B, T), jnp.int32)] * 2 + [(x.shape, x.dtype) for x in leaves]


# the four benchmark cells' trunks at their published widths, a layer or two (ouro: one layer, two passes):
# (config, learner [B, T], scorer [B, T]: its buckets stop at the longest prompt and response the cell allows)
def _cell_models():
    gpt2 = PRESETS["gpt2"]
    return {
        "gpt2": (gpt2.replace(num_layers=2), (32, 513), (32, 513)),
        "gpt2-medium": (gpt2.replace(num_layers=1, hidden_size=1024, num_heads=16), (2, 513), (32, 513)),
        # the leading dense layer: latent attention, keys 192 wide and values 128
        "kimi-vl-a3b": (PRESETS["kimi_vl"].replace(num_layers=1, vocab_size=20480), (4, 513), (32, 513)),
        "ouro-2.6b": (PRESETS["ouro"].replace(num_layers=1, loop_steps=2), (8, 257), (32, 257)),
        # a convolution layer and the attention layer (32 / 8 heads of 64, a norm on each head), both over the
        # dense FFN: the experts' grouped products have cases of their own
        "lfm2-24b-a2b": (
            PRESETS["lfm2_moe"].replace(
                num_layers=2, layer_kinds=("conv", "attention"), first_dense_layers=2, vocab_size=8192),
            (8, 513), (32, 513)),
    }


def _cell_trunk(cell, learner):
    """A cell's trunk as the learner (the gradient of its hidden states) or the
    scorer (the forward) runs it: what lies between the projections' products
    and the flash calls is the compiled program's to show."""
    config, learn_shape, score_shape = _cell_models()[cell]
    model = TransformerLM(config.replace(attention_impl="flash", compute_dtype=jnp.bfloat16))
    B, T = learn_shape if learner else score_shape
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32))["params"]
    )
    leaves, treedef = jax.tree_util.tree_flatten(params)

    def hidden(leaves, ids, mask):
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        return model.apply({"params": params}, ids, mask, with_head=False)[1]

    def fn(ids, mask, *leaves):
        if not learner:
            return hidden(leaves, ids, mask)
        return jax.grad(lambda leaves: hidden(leaves, ids, mask).astype(jnp.float32).sum())(list(leaves))

    return fn, [((B, T), jnp.int32)] * 2 + [(x.shape, x.dtype) for x in leaves], model.config


@pytest.mark.parametrize("program", ["learner", "scorer"])
@pytest.mark.parametrize("cell", ["gpt2", "gpt2-medium", "kimi-vl-a3b", "ouro-2.6b", "lfm2-24b-a2b"])
def test_nothing_runs_between_the_projections_and_the_flash_calls(cell, program, one_chip, no_persistent_cache,
                                                                  monkeypatch):
    """At the cells' learner and scorer shapes, compiled for the chip: a layer's
    flash calls keep the names the benchmark finds them by, and XLA runs no op of
    its own on a ``[B, ., T, .]``-sized array around them — no ``pad`` of an
    operand to the tiles' extent, no ``copy`` / ``transpose`` to or from a
    heads-first ``[B, H, T, D]`` array (none exists any more), no reduce forming
    ``delta``. (The decode case's "no copy of the cache" is the pattern.) Where
    the projections' products are the operands (gpt2's family) every operand
    of a flash call comes straight from the fusion that computed it. A model
    that forms q and k heads-split after the projection (rotary pairs in ouro,
    the nope / rope concatenation of latent attention in kimi-vl) still pays
    XLA's relayout from ``[B, T, H, D]`` tiled over (H, D) to the rows the
    kernels read, where it paid the transpose before: PERF.md section 7."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, shapes, config = _cell_trunk(cell, program == "learner")
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    B, T = shapes[0][0]
    Tp = -(-T // 128) * 128
    H, widths = config.num_heads, {config.dim_per_head}
    if config.attention_kind == "mla":
        widths = {config.qk_nope_head_dim + config.qk_rope_head_dim, config.v_head_dim}
    calls = config.attention_layers * config.loop_steps * (3 if program == "learner" else 1)
    names = _attention_instructions(text)
    assert len(names) == calls and all(re.match(r"^%attn[.0-9]* custom-call$", name) for name in names), names

    rows, width = f"(?:{T}|{Tp})", "(?:" + "|".join(map(str, sorted(widths))) + ")"
    heads_first = rf"\[{B},{H},{rows},{width}\]"
    found = [
        line.strip()[:200] for line in text.splitlines()
        # an operand padded to the tiles' extent, heads-first or as the kernels now take it
        if re.search(rf"= (?:bf16|f32)(?:{heads_first}|\[{B},{Tp},\d+\])\S* pad\(", line)
        or re.search(rf"= \w+{heads_first}\S* (?:copy|transpose)\(", line)  # a transpose to or from heads-first
        or re.search(rf"= f32\[{B},{H},(?:1,)?{rows}\]\S* reduce\(", line)  # delta = sum(dO * O) over the value width
    ]
    assert not found, found
    if T != Tp:  # the one array still padded in HBM is the key mask, kilobytes
        assert re.search(rf"= s32\[{B},{Tp}\]\S* pad\(", text)

    if cell.startswith("gpt2"):
        relayouts = []
        for call in re.finditer(r"^\s*%attn[.0-9]* = .*? custom-call\((.*?)\), custom_call_target", text, re.M):
            for operand in re.findall(r"%[\w.\-]+", call.group(1))[1:]:  # but the key mask, kilobytes
                producer = re.search(rf"^\s*{re.escape(operand)} = \S+ ([\w\-]+)\(", text, re.M).group(1)
                if producer in ("copy", "transpose", "reshape", "pad") or re.search(
                        r"copy|transpose|pad", operand.replace("copy-done", "")):  # copy-done: a move between memories
                    relayouts.append(f"{operand} {producer}")
        assert not relayouts, relayouts


def _paged(preset, quant, q_len):
    c = PRESETS[preset]
    layout = paged_pool_layout(
        NUM_BLOCKS, BLOCK_SIZE, c.kv_heads, c.dim_per_head, jnp.bfloat16, quant
    )
    q_shape = (SLOTS, c.num_heads, c.dim_per_head)
    kernel = paged_attention_pallas
    if q_len:
        q_shape = (SLOTS, q_len) + q_shape[1:]
        kernel = paged_verify_attention_pallas
    shapes = [
        (q_shape, jnp.bfloat16), layout["k"], layout["v"],
        ((SLOTS, MAX_BLOCKS), jnp.int32), ((SLOTS,), jnp.int32),
    ]
    if not quant:
        return kernel, shapes

    def quantized(q, k, v, tables, lens, k_scale, v_scale):
        return kernel(q, k, v, tables, lens, k_scale=k_scale, v_scale=v_scale)

    return quantized, shapes + [layout["k_scale"], layout["v_scale"]]


CASES = {
    "flash_fwd-gpt2": functools.partial(_flash, grad=False),
    "flash_grad_pallas_bwd-gpt2": functools.partial(_flash, grad=True),
    "flash_grad-transformer_lm-attn_names": _model_grad,
    "flash_grad-float32-2x12x513x64": functools.partial(_flash, True, (2, 12, 513, 64), None, jnp.float32),
}
# [B, H, T, D] of the benchmark's cells: learner (gpt2, gpt2-medium), scoring at the rungs (a chunk short of
# the caps), prefill; and scoring at the caps
CELL_SHAPES = [
    (32, 12, 513, 64), (2, 16, 513, 64), (32, 12, 576, 64), (32, 16, 640, 64), (128, 12, 64, 64), (64, 16, 512, 64),
    (32, 16, 513, 64),
]
# no cell runs these yet: long contexts at D = 128, multi-head, grouped (rep 4) and multi-query
LONG_SHAPES = [((1, 16, T, 128), kv_heads) for T in (2048, 8192) for kv_heads in (16, 4, 1)]
for _shape, _kv_heads in [(s, None) for s in CELL_SHAPES] + LONG_SHAPES:
    _name = "x".join(map(str, _shape)) + (f"-hkv{_kv_heads}" if _kv_heads else "")
    CASES[f"flash_fwd-{_name}"] = functools.partial(_flash, False, _shape, _kv_heads)
    CASES[f"flash_grad-{_name}"] = functools.partial(_flash, True, _shape, _kv_heads)
# latent attention's expanded form: keys 192 wide, values 128 (kimi-vl-a3b's learner, scoring, padded)
for _T in (513, 576, 640):
    CASES[f"flash_fwd-4x16x{_T}x192-v128"] = functools.partial(_flash, False, (4, 16, _T, 192), None, jnp.bfloat16, 128)
    CASES[f"flash_grad-4x16x{_T}x192-v128"] = functools.partial(_flash, True, (4, 16, _T, 192), None, jnp.bfloat16, 128)
# D = 128 at the ouro-2.6b cell's shapes: a learner microbatch (8 x 257), a scoring chunk at the rungs (64 + 256)
# and at the caps (64 + 193), the prefill of 128 prompts; the (pass, layer) loop calls them 20 times a forward
for _shape in ((8, 16, 257, 128), (32, 16, 320, 128), (128, 16, 64, 128), (32, 16, 257, 128)):
    _name = "x".join(map(str, _shape))
    CASES[f"flash_fwd-{_name}"] = functools.partial(_flash, False, _shape)
    CASES[f"flash_grad-{_name}"] = functools.partial(_flash, True, _shape)
# 32 query heads over 8 key/value heads of 64 at the lfm2-24b-a2b cell's shapes: a learner microbatch
# (8 x 513), a scoring chunk at the rungs (64 + 512) and at the caps (64 + 449), the prefill of 128 prompts
for _shape in ((8, 32, 513, 64), (32, 32, 576, 64), (128, 32, 64, 64), (32, 32, 513, 64)):
    _name = "x".join(map(str, _shape)) + "-hkv8"
    CASES[f"flash_fwd-{_name}"] = functools.partial(_flash, False, _shape, 8)
    CASES[f"flash_grad-{_name}"] = functools.partial(_flash, True, _shape, 8)
# the grouped expert products: a learner microbatch (4 x 513 tokens) and a decode step (128)
for _tokens in (2052, 128):
    CASES[f"grouped_products_fwd-{_tokens}"] = functools.partial(_grouped_products, False, _tokens)
    CASES[f"grouped_products_grad-{_tokens}"] = functools.partial(_grouped_products, True, _tokens)
# and at the lfm2-24b-a2b cell's: experts 1536 wide, 4 a token, a microbatch of 8 x 513 tokens, a decode step
for _tokens in (4104, 128):
    CASES[f"grouped_products_fwd-{_tokens}-f1536-k4"] = functools.partial(_grouped_products, False, _tokens, 1536, 4)
    CASES[f"grouped_products_grad-{_tokens}-f1536-k4"] = functools.partial(_grouped_products, True, _tokens, 1536, 4)
# the decode kernel at the cells' decode steps ([B, H, S, D]; cell 2's cache is 576 slots), and a grouped model's
CASES["decode-128x12x512x64"] = functools.partial(_decode, (128, 12, 512, 64))
CASES["decode-64x16x576x64"] = functools.partial(_decode, (64, 16, 576, 64))
CASES["decode-32x32x1024x128-hkv8"] = functools.partial(_decode, (32, 32, 1024, 128), 8)
# the ouro-2.6b cell's decode step: 16 kv heads of 128, a kv slot of one operand 512 KiB against gpt2's 196 KB
CASES["decode-128x16x256x128"] = functools.partial(_decode, (128, 16, 256, 128))
# the lfm2-24b-a2b cell's: the one attention layer's cache, 8 kv heads of 64 under 32 query heads
CASES["decode-128x32x512x64-hkv8"] = functools.partial(_decode, (128, 32, 512, 64), 8)


def _sparse(kind, B, T):
    """The learned sparse attention's kernels at the keye-vl-2.0-30b-a3b cell's widths (32 / 4 heads of 128, an
    indexer of 16 heads of 64 over one key head, 2,048 kept): the selection, or attention over it with its gradient."""
    from trlx_tpu.ops import sparse_attention as dsa

    if kind == "select":
        def fn(qi, ki, w, valid):
            return dsa.select_keys(qi, ki, w, valid, 2048, False)

        return fn, [((B, T, 16, 64), jnp.bfloat16), ((B, T, 64), jnp.bfloat16), ((B, T, 16), jnp.float32),
                    ((B, T), jnp.int32)]
    Tp, Sp = dsa.padded_lengths(T, T)

    def fn(q, k, v, mask):
        blocks = dsa.block_table(mask)
        attend = lambda q, k, v: dsa.sparse_attention(q, k, v, mask, blocks, 128 ** -0.5, False)
        if kind == "attn_fwd":
            return attend(q, k, v)
        return jax.grad(lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

    return fn, [((B, T, 32, 128), jnp.bfloat16), ((B, T, 4, 128), jnp.bfloat16), ((B, T, 4, 128), jnp.bfloat16),
                ((B, Tp, Sp), jnp.int8)]


# learned sparse attention at the keye-vl-2.0-30b-a3b cell's shapes: a learner microbatch and a scoring chunk
# (one row of 7,680 + 513), the generator's prefill (16 rows of 8,192: 7,680 padded to the ladder's rung)
CASES["sparse_select-1x8193"] = functools.partial(_sparse, "select", 1, 8193)
CASES["sparse_attn_grad-1x8193"] = functools.partial(_sparse, "attn_grad", 1, 8193)
CASES["sparse_select-16x8192"] = functools.partial(_sparse, "select", 16, 8192)
CASES["sparse_attn_fwd-16x8192"] = functools.partial(_sparse, "attn_fwd", 16, 8192)
for _preset in ("gpt2", "gpt_bigcode"):  # Hkv=12 rep=1 D=64; Hkv=1 rep=16 D=128
    for _pool, _quant in (("bf16", False), ("int8", True)):
        CASES[f"paged_decode-{_pool}-{_preset}"] = functools.partial(
            _paged, _preset, _quant, 0
        )
        CASES[f"paged_verify_q4-{_pool}-{_preset}"] = functools.partial(
            _paged, _preset, _quant, 4
        )


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache, monkeypatch):
    # the model asks the backend whether to interpret its kernels; the target here is the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    # jax's grouped matmul multiplies at the process's default precision, which conftest.py sets to
    # float32 and the chip's compiler refuses for bfloat16 operands: on the chip it is the default
    precision = "bfloat16" if case.startswith("grouped_products") else "float32"
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(fn).lower(*args).compile()  # raises what the chip's compiler would
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if case.startswith("grouped_products"):
        # one named family of device ops, as benchmark/metrics/moe_gmm_roofline.json matches it,
        # and none that flash_attn_roofline's pattern would take for its own
        names = _attention_instructions(text)
        products = [n for n in names if re.match(r"^%t?gmm[.0-9]* custom-call$", n)]
        assert len(products) >= (6 if "grad" in case else 3), names
        assert not any(re.match(r"^%attn[.0-9]* custom-call$", n) for n in names), names
    if case.startswith("decode"):
        # a name of its own, which flash_attn_roofline's pattern does not take: 447 x 12 such events
        # an iteration would otherwise be summed into the flash kernels' seconds
        names = _attention_instructions(text)
        assert names and all(re.match(r"^%decode_attn[.0-9]* custom-call$", name) for name in names), names
        # the cache reaches the kernel slots outermost, batch on the lanes, without a copy: the
        # transposes around the call are bitcasts of the layout the loop carries it in
        B, Hkv, S, D = shapes[1][0]  # as the loop carries it: rows x kv heads beside them, kv heads at a row
        cache = f"(?:{B},{Hkv},{S},{D}|{S},{Hkv},{D},{B})"
        body = _while_body(text)
        assert "tpu_custom_call" in body and re.search(rf"= bf16\[{cache}\]\S* bitcast\(", body)
        assert not re.search(rf"= bf16\[{cache}\]\S* (?:copy|transpose)\(", body), "the cache is copied every step"
        rows = shapes[0][0][0]
        if rows < 128:
            # kv heads stand beside the rows: the loop carries the folded cache batch-minor, as it carries a
            # batch of 128, and no operand the kernel is handed leaves lanes empty
            assert B == 128 and B * Hkv == rows * shapes[4][0][1], shapes
            assert re.search(rf"bf16\[{B},{Hkv},{S},{D}\]{{0,3,1,2", body), "the carried cache is not batch-minor"
            call = next(line for line in body.splitlines() if "tpu_custom_call" in line)
            handed = re.search(r"operand_layout_constraints={(.*?)}, \w+=", call).group(1)
            lanes = [int(n) for n in re.findall(r"bf16\[\d+,\d+,\d+,(\d+)\]{3,2,1,0}", handed)]
            assert len(lanes) == 3 and min(lanes) >= 128, handed  # q, k, v
    if case.startswith("sparse_attn"):
        # one named family, as benchmark/metrics/sparse_attn_roofline.json matches it, and none that
        # flash_attn_roofline's pattern would take
        names = _attention_instructions(text)
        ours = [n for n in names if re.match(r"^%(\w*_)?sparse_attn[_.0-9]* custom-call$", n)]
        assert len(ours) == len(names) == (3 if "grad" in case else 1), names
        assert not any(re.match(r"^%attn[.0-9]* custom-call$", n) for n in names), names
    if case.endswith("attn_names"):
        names = _attention_instructions(text)
        assert len(names) == 6, names  # forward, dkv and dq of two layers
        assert all(re.match(r"^%attn[.0-9]* custom-call$", name) for name in names), names


def _sharded_ppo_learner(topo, fsdp=4, layers=2):
    """``PPOTrainer``'s own train step over a ``data=1, fsdp=4`` mesh of the described chips, as
    ``chip_smoke.py --chips 4`` runs it (gpt2's widths and vocabulary, 64 + 65 tokens, batch 32)
    at two layers: the trainer's programs without its state, since a described device holds no
    array. Returns (the jitted step, its abstract arguments, the mesh, (B, P, R))."""
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import chip_smoke
    from trlx_tpu.data.ppo_types import PPORLBatch
    from trlx_tpu.models.hf_loading import load_pretrained
    from trlx_tpu.models.policy import CausalLMWithValueHead
    from trlx_tpu.parallel import mesh as mesh_lib
    from trlx_tpu.parallel.sharding import make_param_shardings, make_state_shardings
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer
    from trlx_tpu.utils import get_optimizer_class, get_scheduler_class

    sizes = dataclasses.replace(chip_smoke.FULL, model_overrides=dict(num_layers=layers))
    config = chip_smoke.ppo_config(sizes, "unused", fsdp=fsdp, trainer="PPOTrainer")
    mesh = Mesh(np.array(topo.devices[:fsdp]).reshape(1, fsdp, 1, 1), mesh_lib.MESH_AXES)

    t = object.__new__(PPOTrainer)  # setup_model and setup_optimizer place arrays; the step needs none
    t.config, t.method, t.mesh, t.health, t._engine, t._train_steps = config, config.method, mesh, None, None, {}
    t.is_seq2seq, t.num_mb = False, 1
    dtypes = dict(param_dtype=jnp.dtype(config.mesh.param_dtype), compute_dtype=jnp.dtype(config.mesh.compute_dtype))
    t.model_config, _, _ = load_pretrained(
        config.model.model_path, {**config.model.model_overrides, **dtypes, "remat": config.mesh.remat}, mesh=None)
    t.module = CausalLMWithValueHead(t.model_config)
    shapes = jax.eval_shape(
        lambda: t.module.init(jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2), jnp.int32))
    )["params"]
    params = jax.tree.map(
        lambda leaf, sharding: jax.ShapeDtypeStruct(leaf.shape, dtypes["param_dtype"], sharding=sharding),
        shapes, make_param_shardings(shapes, mesh))
    kwargs = dict(config.optimizer.kwargs)
    schedule = get_scheduler_class(config.scheduler.name)
    t.lr_schedule = schedule(learning_rate=kwargs.pop("lr"), **config.scheduler.kwargs)
    tx = get_optimizer_class(config.optimizer.name)(learning_rate=t.lr_schedule, **kwargs)
    t.tx = optax.multi_transform({"train": tx, "freeze": optax.set_to_zero()}, t._trainable_labels(params))
    state = jax.eval_shape(t.tx.init, params)
    state = jax.tree.map(
        lambda leaf, sharding: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sharding),
        state, make_state_shardings(state, mesh))

    B, P, R = sizes.batch, sizes.prompt_len, sizes.new_tokens + 1  # the trainer re-appends eos
    rows = NamedSharding(mesh, PartitionSpec(mesh_lib.BATCH_AXES, None))

    def of(width, dtype):
        return jax.ShapeDtypeStruct((B, width), dtype, sharding=rows)

    batch = PPORLBatch(
        query_tensors=of(P, jnp.int32), response_tensors=of(R, jnp.int32), logprobs=of(R, jnp.float32),
        values=of(R, jnp.float32), rewards=of(R, jnp.float32), attention_mask=of(P, jnp.int32),
        response_mask=of(R, jnp.int32),
    )
    return t._get_train_step(B, P, R), (params, state, batch), mesh, (B, P, R)


@pytest.fixture(scope="module")
def sharded_train_step(topo, no_persistent_cache):
    """(the fsdp=4 PPO train step compiled for the described chips, (B, P, R)): one compile, read by two tests."""
    if len(topo.devices) < 4:
        pytest.skip("the described topology has fewer than four chips")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        step, args, mesh, sizes = _sharded_ppo_learner(topo)
        with mesh:
            return step.lower(*args).compile(), sizes  # raises what the chip's compiler would


def test_sharded_ppo_train_step_compiles_with_the_response_window(sharded_train_step):
    """The fsdp=4 PPO train step at gpt2's vocabulary of 50257: the shape on which the chip's
    compiler failed (PR 22: "Bitcast cannot have different shape sizes of output and operand")
    when ``[B, T, V]`` logits were sliced and the backward padded them. The head now runs over
    the response window's rows of the hidden states, and the backward pads ``[B, R, d]``."""
    compiled, (B, P, R) = sharded_train_step
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the flash kernels, placed over the mesh
    assert "all-gather" in text or "all-reduce" in text  # the parameters are sharded over the four chips
    V = 50257
    assert re.search(rf"bf16\[{B // 4},{R},{V}\]", text), "a device's share of the window's logits"
    # no array over every position and the vocabulary, in any dtype, whole or a device's share
    assert not re.search(rf"\[\d+,(?:{P + R}|{P + R - 1}),{V}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 30


def test_the_chips_program_carries_the_programs_scopes(sharded_train_step):
    """The table ``obs/op_scopes.py`` walks out of the TPU program's text: of the instructions
    that can be device events at least 95 % stand under a scope of the vocabulary (the
    compiler's own moves between memory spaces carry no ``op_name`` and take their user's), the
    head's are told from the trunk's, and the flash calls of the two layers (forward, dq, dkv)
    read ``kernel``, forward and backward apart."""
    from trlx_tpu.obs import op_scopes

    rows = op_scopes.walk(sharded_train_step[0].as_text())
    events = [row for row in rows.values() if row["kind"] != "container"]
    scoped = [row for row in events if row["scope"]]
    assert len(events) > 1000 and len(scoped) >= 0.95 * len(events), (len(scoped), len(events))
    assert {tuple(row["scope"]) for row in scoped} >= {("loss",), ("loss", "logprobs"), ("optimizer",)}
    lent = [row for row in events if "via" in row]
    moves = [row for row in lent if row["opcode"].endswith(("-start", "-done")) or row["opcode"] == "custom-call"]
    assert len(moves) >= 0.8 * len(lent) and not [row for row in moves if row["kind"] == "kernel"]
    kernels = {name: row for name, row in rows.items() if row["kind"] == "kernel"}
    assert sorted(row["pass"] for row in kernels.values()) == ["backward"] * 4 + ["forward"] * 2, kernels
    assert all(re.match(r"^%attn[.0-9]*$", name) and row["scope"] == ["loss"] for name, row in kernels.items())
    products = [row for row in events if row["kind"] == "product"]
    assert products and all(row["scope"] for row in products)


@pytest.mark.parametrize("axes", [(1, 4, 1, 1), (2, 1, 1, 2)], ids=["fsdp4", "data2-model2"])
def test_folded_decode_step_places_over_four_chips(axes, topo, no_persistent_cache, monkeypatch):
    """gpt2-medium's decode step (64 rows, 16 heads, 576 slots) through ``attend`` over a mesh of the
    four described chips: the fold is taken from a shard's rows and kv heads (16 rows of 16 heads:
    8 a row; 32 rows of 8: 4 a row), the rows-major fold keeps a shard's rows and heads on its chip,
    so the kernel is placed as it stands, on 128 full lanes a shard, with no collective and no
    cache-sized copy in the loop."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from trlx_tpu.ops import kv_cache
    from trlx_tpu.parallel import mesh as mesh_lib

    if len(topo.devices) < 4:
        pytest.skip("the described topology has fewer than four chips")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices[:4]).reshape(axes), mesh_lib.MESH_AXES)
    B, H, S, D = 64, 16, 576, 64
    n_batch, n_model = axes[0] * axes[1], axes[3]
    with mesh:
        fold = attention.decode_cache_fold("flash", False, B, H, H)
    assert fold == attention.choose_decode_fold(B // n_batch, H // n_model) and fold * B // n_batch == 128
    layout = kv_cache.kv_cache_layout((B, H, S, D), jnp.bfloat16, False, fold)
    attend = attention.attend  # the dispatch a model calls, which chooses the kernel and places it over the mesh

    def fn(q, k, v, mask_bias, k_new, v_new, steps):
        def body(carry):
            index, q, cache = carry
            cache = kv_cache.write_kv_cache(cache, k_new, v_new, index)
            step = k_new.transpose(0, 2, 1, 3)  # this step's rows as the model forms them; the cache has them already
            out = attend(q, step, step, cache, mask_bias, None, index, D ** -0.5, "flash", False, None)
            return index + 1, out.reshape(q.shape), cache

        return jax.lax.while_loop(lambda carry: carry[0] < steps, body, (jnp.int32(1), q, {"k": k, "v": v}))

    def placed(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, PartitionSpec(*spec)))

    rows, heads = mesh_lib.BATCH_AXES, mesh_lib.MODEL_AXIS
    args = [
        placed((B, 1, H, D), jnp.bfloat16, rows, None, heads), placed(*layout["k"], rows, heads),
        placed(*layout["v"], rows, heads), placed((B, 1, 1, S), jnp.float32, rows),
        placed((B, H, 1, D), jnp.bfloat16, rows, heads), placed((B, H, 1, D), jnp.bfloat16, rows, heads),
        placed((), jnp.int32),
    ]
    with mesh:
        compiled = jax.jit(fn).lower(*args).compile()  # raises what the chip's compiler would
    text = compiled.as_text()
    body = _while_body(text)
    held = H // n_model // fold
    assert "tpu_custom_call" in body and re.search(rf"bf16\[{S},{held},{D},128\]\S* bitcast\(", body), "a shard's cache"
    assert not re.search(r"all-gather|all-reduce|all-to-all|collective-permute", body)
    assert not re.search(rf"= bf16\[(?:128,{held},{S},{D}|{S},{held},{D},128)\]\S* (?:copy|transpose)\(", body)
