"""The plain reference of the lfm2 family: LFM2-24B-A2B (``model_type``
``lfm2_moe``), a hybrid whose layers mix tokens by a gated short convolution or
by grouped-query attention, layer by layer, over sigmoid-routed experts with no
shared one, as one chip's share of a deployment, in float32 ``jax.numpy``; the
PPO loss, the microbatched step and AdamW are ``benchmark/reference.py``'s.

No kernels, no cache, no sorting: the convolution is ``conv_L_cache`` shifted
products over the whole sequence, every position attends over the whole
sequence, and every held expert is applied to every token and weighted by what
the router gave it (nothing where it was not chosen). Every matmul is under
``jax.default_matmul_precision("highest")`` (the callers set it). It imports
nothing of the program.

The model, as read from the published description (``modeling_lfm2_moe.py`` of
the transformers library beside the named ``config.json``; nothing was fetched,
so each point is also a sentence under the configuration file's ``assumed``).
No bias anywhere but the value head's; ``RMSNorm(x) = x rsqrt(mean(x^2) +
norm_eps) g``:

- ``h = E[ids]``. For every kept layer ``i``: ``h = h + Mix_i(N_op,i(h))``, then
  ``h = h + FFN_i(N_ffn,i(h))`` (``operator_norm``, ``ffn_norm``; here ``ln_1``,
  ``ln_2``). After the last layer ``h = RMSNorm_f(h)`` (``embedding_norm``, here
  ``ln_f``); ``logits = h E^T`` over the rows held (the head is tied); the value
  head reads the same ``h``.
- ``Mix_i`` on a ``conv`` layer: ``[b, c, x] = z W_in`` split in that order into
  three ``[T, d]``; ``u = b * x``, set to zero at padded positions; ``v_t =
  sum_j w_j * u_{t - (taps - 1) + j}`` with ``u`` zero before the row's first
  real token (``w`` is ``[d, taps]``, one filter a channel: the last tap
  multiplies the token's own ``u``); ``y = (c * v) W_out``.
- ``Mix_i`` on a ``full_attention`` layer: ``q, k, v = z W_q, z W_k, z W_v`` as
  heads of ``head_dim = hidden_size / num_attention_heads``; ``q = RMSNorm_q(q)``,
  ``k = RMSNorm_k(k)`` over each head's dimensions; rotary (rotate-half, every
  dimension, ``rope_theta``) on q and k; query head ``h`` attends over key/value
  head ``h // (heads / kv_heads)``; scores ``q k / sqrt(head_dim)``; causal
  softmax; ``out = (P v) W_o``.
- ``FFN_i`` on the ``num_dense_layers`` leading layers: ``W_2(silu(W_1 z) * W_3
  z)`` of ``intermediate_size`` (here ``gate``, ``up``, ``down``). On every later
  layer: ``s = sigmoid(z W_r)`` over all published experts; the
  ``num_experts_per_tok`` largest of ``s + expert_bias`` are chosen (the bias
  chooses only); weights ``s_e / (sum over the chosen of s + 1e-6)`` times
  ``routed_scaling_factor``; the result is the sum over the chosen experts held
  here of weight times the same gated FFN at ``moe_intermediate_size``.
- the share: ``num_experts`` of the file counts the experts held here,
  ``published.num_experts`` the router's width, ``expert_offset`` (0 where
  absent) the first one held. An assignment to an expert that is not held adds
  nothing. The vocabulary is the file's ``vocab_size`` rows.

Departure from the published model: a two-layer value head reads the final
hidden state, its output layer drawn at ``initializer_range / sqrt(2 hidden)``
as ``reference_kimi_vl.py`` draws it.

Stacked keys start with ``h.``; a key's stack holds the layers that have it, in
their order: the two block norms every layer, ``h.conv.*`` the convolution
layers, ``h.attn.*`` the attention layers, ``h.dense.*`` the leading dense
layers, ``h.moe.*`` the expert layers.
"""

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark import reference as base
from benchmark.reference import init_opt, leaf_norms  # noqa: F401  (what the harness asks of a reference)

Weights = Dict[str, Any]

#: what the published router adds to the sum of the chosen scores before it divides by it
ROUTER_NORM_EPS = 1e-6
KINDS = ("conv", "full_attention")


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, under the published config's own keys."""
    held = int(config["num_experts"])
    published = config.get("published", {})
    kinds = tuple(config["layer_types"])
    layers = int(config["num_hidden_layers"])
    if len(kinds) != layers or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types {kinds} does not name a kind of {KINDS} for each of {layers} layers")
    if config.get("conv_bias") or not config.get("use_expert_bias", True):
        raise ValueError("conv_bias true / use_expert_bias false is not the published model and not computed here")
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return dict(
        d=d, layers=layers, kinds=kinds, dense_layers=int(config["num_dense_layers"]),
        conv_layers=kinds.count("conv"), attn_layers=kinds.count("full_attention"),
        heads=heads, kv_heads=int(config["num_key_value_heads"]), head_dim=d // heads,
        taps=int(config["conv_L_cache"]), theta=float(config["rope_parameters"]["rope_theta"]),
        ffn=int(config["intermediate_size"]), expert_ffn=int(config["moe_intermediate_size"]),
        top_k=int(config["num_experts_per_tok"]), experts=int(published.get("num_experts", held)), held=held,
        offset=int(config.get("expert_offset", 0)), scale=float(config["routed_scaling_factor"]),
        norm_topk=bool(config["norm_topk_prob"]), vocab=int(config["vocab_size"]), eps=float(config["norm_eps"]),
    )


def weight_spec(config: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """name -> (shape, init), as ``reference.weight_spec`` has it."""
    s = dims(config)
    d, L, Lc, La = s["d"], s["layers"], s["conv_layers"], s["attn_layers"]
    Ld, Le = s["dense_layers"], s["layers"] - s["dense_layers"]
    q, kv, f, fe = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"], s["ffn"], s["expert_ffn"]
    std = float(config.get("initializer_range", 0.02))
    res = std / math.sqrt(2 * L)  # the residual projections, scaled as gpt2's own init scales them
    out = std / math.sqrt(2 * d)  # the value head's output layer (reference_kimi_vl.weight_spec: why)
    return {
        "wte": ((s["vocab"], d), std), "ln_f.g": ((d,), "ones"),
        "v.fc_in.w": ((d, 2 * d), std), "v.fc_in.b": ((2 * d,), "zeros"),
        "v.fc_out.w": ((2 * d, 1), out), "v.fc_out.b": ((1,), "zeros"),
        "h.ln_1.g": ((L, d), "ones"), "h.ln_2.g": ((L, d), "ones"),
        "h.conv.in.w": ((Lc, d, 3 * d), std), "h.conv.filter": ((Lc, d, s["taps"]), std),
        "h.conv.out.w": ((Lc, d, d), res),
        "h.attn.q.w": ((La, d, q), std), "h.attn.k.w": ((La, d, kv), std), "h.attn.v.w": ((La, d, kv), std),
        "h.attn.q_norm.g": ((La, s["head_dim"]), "ones"), "h.attn.k_norm.g": ((La, s["head_dim"]), "ones"),
        "h.attn.o.w": ((La, q, d), res),
        "h.dense.gate.w": ((Ld, d, f), std), "h.dense.up.w": ((Ld, d, f), std),
        "h.dense.down.w": ((Ld, f, d), res),
        "h.moe.router.w": ((Le, d, s["experts"]), std),
        # the selection bias: small and not zero, so that it chooses
        "h.moe.router.b": ((Le, s["experts"]), std),
        "h.moe.experts.gate": ((Le, s["held"], d, fe), std),
        "h.moe.experts.up": ((Le, s["held"], d, fe), std),
        "h.moe.experts.down": ((Le, s["held"], fe, d), res),
    }


def init_weights(config: Dict[str, Any], seed: int) -> Weights:
    """Every weight, float32, on the device, in one jitted call from the seed."""
    spec = weight_spec(config)

    def make(key):
        out = {}
        for i, (name, (shape, init)) in enumerate(sorted(spec.items())):
            if init == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif init == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = init * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 63)))


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rotate_half(x, cos, sin):
    """x [..., D], cos/sin broadcastable [..., D / 2]: the pair of dimension i is (i, i + D / 2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def short_conv(h, lw, s, mask):
    """h [B, T, d] (normed), mask [B, T] (1 on real tokens) -> [B, T, d]."""
    T, taps = h.shape[1], s["taps"]
    b, c, x = jnp.split(h @ lw["conv.in.w"], 3, axis=-1)
    u = b * x * mask[..., None].astype(h.dtype)
    before = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))  # nothing before the first token
    v = sum(lw["conv.filter"][:, j] * before[:, j : j + T] for j in range(taps))
    return (c * v) @ lw["conv.out.w"]


def attention(h, lw, s, positions, bias):
    """h [B, T, d] (normed) -> [B, T, d]."""
    B, T, _ = h.shape
    H, Hkv, D = s["heads"], s["kv_heads"], s["head_dim"]
    q = _rms_norm((h @ lw["attn.q.w"]).reshape(B, T, H, D), lw["attn.q_norm.g"], s["eps"])
    k = _rms_norm((h @ lw["attn.k.w"]).reshape(B, T, Hkv, D), lw["attn.k_norm.g"], s["eps"])
    v = (h @ lw["attn.v.w"]).reshape(B, T, Hkv, D)
    inv_freq = 1.0 / (s["theta"] ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, D / 2]
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    q, k = _rotate_half(q, cos, sin), _rotate_half(k, cos, sin)
    k, v = jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)  # query head h reads kv head h // rep
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(D) + bias
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)
    return o.reshape(B, T, H * D) @ lw["attn.o.w"]


def route(h, lw, s):
    """h [..., d] -> the weight of every published expert for every token,
    [..., experts] float32: nothing where the expert was not chosen."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32) @ lw["moe.router.w"])
    _, chosen = jax.lax.top_k(scores + lw["moe.router.b"], s["top_k"])
    picked = jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32).sum(-2)
    weights = scores * picked
    if s["norm_topk"]:
        weights = weights / (weights.sum(-1, keepdims=True) + ROUTER_NORM_EPS)
    return weights * s["scale"]


def experts(h, lw, s):
    """What the experts held here add, [B, T, d]: every held expert on every token."""
    weights = route(h, lw, s)[..., s["offset"] : s["offset"] + s["held"]]  # [B, T, held]
    gate = jnp.einsum("btd,edf->betf", h, lw["moe.experts.gate"])
    up = jnp.einsum("btd,edf->betf", h, lw["moe.experts.up"])
    each = jnp.einsum("betf,efd->betd", jax.nn.silu(gate) * up, lw["moe.experts.down"])
    return jnp.einsum("betd,bte->btd", each, weights)


def layer_weights(w: Weights, s: Dict[str, Any], i: int) -> Weights:
    """Layer ``i``'s weights, each taken from its own stack at the layer's rank in it."""
    kind = s["kinds"][i]
    rank = {
        "ln_1": i, "ln_2": i, "conv": s["kinds"][:i].count("conv"), "attn": s["kinds"][:i].count("full_attention"),
        "dense": i, "moe": i - s["dense_layers"],
    }
    have = ("ln_1", "ln_2", "conv" if kind == "conv" else "attn", "dense" if i < s["dense_layers"] else "moe")
    return {k[2:]: v[rank[k[2:].split(".")[0]]] for k, v in w.items()
            if k.startswith("h.") and k[2:].split(".")[0] in have}


def forward(w: Weights, config: Dict[str, Any], ids, mask):
    """ids, mask [B, T] (mask 1 on real tokens, padding on either side) ->
    (logits [B, T, V], values [B, T]). Positions count real tokens."""
    s = dims(config)
    B, T = ids.shape
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None)
    x = w["wte"][ids]
    allowed = jnp.tril(jnp.ones((T, T), bool))[None, None] & mask[:, None, None, :].astype(bool)
    bias = jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)
    for i in range(s["layers"]):
        lw = layer_weights(w, s, i)
        h = _rms_norm(x, lw["ln_1.g"], s["eps"])
        x = x + (short_conv(h, lw, s, mask) if s["kinds"][i] == "conv" else attention(h, lw, s, positions, bias))
        h = _rms_norm(x, lw["ln_2.g"], s["eps"])
        if i < s["dense_layers"]:
            x = x + _swiglu(h, lw["dense.gate.w"], lw["dense.up.w"], lw["dense.down.w"])
        else:
            x = x + experts(h, lw, s)
    hidden = _rms_norm(x, w["ln_f.g"], s["eps"])
    logits = hidden @ w["wte"].T
    values = jax.nn.relu(hidden @ w["v.fc_in.w"] + w["v.fc_in.b"]) @ w["v.fc_out.w"] + w["v.fc_out.b"]
    return logits, values[..., 0]


def response_window(w, config, seq, mask, P: int, R: int, banned_token=None):
    """``reference.response_window`` over this family's forward."""
    logits, values = forward(w, config, seq, mask)
    logits = logits[:, P - 1 : P - 1 + R]
    tokens = seq[:, P : P + R]
    logprobs = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), tokens[..., None], -1)[..., 0]
    if banned_token is not None:
        logits = logits.at[..., banned_token].set(-jnp.inf)
    gap = logits.max(-1) - jnp.take_along_axis(logits, tokens[..., None], -1)[..., 0]
    return logprobs, values[:, P - 1 : P - 1 + R], gap


def make_train_step(config, hp: Dict[str, float], P: int, R: int, num_mb: int, block_rows: int):
    """``reference.make_train_step`` (microbatches, the PPO loss, AdamW) over
    this family's forward."""
    return base.make_train_step(config, hp, P, R, num_mb, block_rows, response_window=response_window)
