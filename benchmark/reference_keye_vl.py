"""The plain reference of the keye_vl family: the language model of
Keye-VL-2.0-30B-A3B (``model_type`` ``KeyeVL2``; the vision tower is not run),
grouped-query attention over the keys a learned indexer selects and
softmax-routed experts, as one chip's share of a deployment, in float32
``jax.numpy``; the PPO loss and AdamW are ``benchmark/reference.py``'s.

No kernels, no cache, no bisection: the indexer's scores are formed for every
(query, key) pair of a block of query rows, the selection reads the ``topk``-th
largest off the sorted row (ties to the lower index), every query attends over the whole
sequence with everything it did not select masked out, and every held expert is
applied to every token and weighted by what the router gave it (nothing where it
was not chosen). Query rows go through in blocks of ``QUERY_BLOCK`` under
``jax.checkpoint``, so that 8,192 positions fit. Every matmul is under
``jax.default_matmul_precision("highest")`` (the callers set it). It imports
nothing of the program.

The model, as the configuration file writes it down (each point not in the
published ``config.json`` is a sentence under its ``assumed``). No bias anywhere
but the indexer's key norm and the value head; ``RMSNorm(x) = x rsqrt(mean(x^2)
+ eps) g``:

- ``h = E[ids]``; every layer: ``h = h + Attn(N_1(h))``, then ``h = h +
  MoE(N_2(h))``; after the last, ``h = N_f(h)``, ``logits = h W_head`` over the
  rows held; the value head reads the same ``h``.
- ``Attn(x)``: ``q = rope(RMSNorm_q(x W_q))`` as 32 heads of 128, ``k =
  rope(RMSNorm_k(x W_k))``, ``v = x W_v`` as 4 heads, query head ``i`` reads
  kv head ``i // 8``; rotate-half over all 128 dimensions, ``rope_theta``, the
  position the count of real tokens before (``mrope_section`` reduces to this
  for text: the three position ids are equal).
- the indexer: ``qi = rope'(x W_qi)`` as 16 heads of 64, ``ki = rope'(LayerNorm(x
  W_ki))`` one head, ``w = (x W_w) / sqrt(16 * 64)``; ``rope'`` is rotate-half
  over the first 32 of the 64 dimensions. ``I[t, s] = sum_j w[t, j] relu(qi[t,
  j] . ki[s])``; query ``t`` selects the ``min(topk, allowed)`` allowed keys
  (``s <= t``, real) of the largest ``I``, ties to the earlier key; ``o =
  softmax over the selected of q k / sqrt(128)`` times ``v``; ``out = o W_o``.
- ``MoE(x)``: ``p = softmax(x W_r)`` over all published experts; the
  ``num_experts_per_tok`` largest are chosen, weights ``p_e`` over their sum;
  the result is the sum over the chosen experts held here of weight times
  ``W_down(silu(W_gate x) * W_up x)``.
- the share: ``num_experts`` of the file counts the experts held here,
  ``published.num_experts`` the router's width, ``expert_offset`` (0 where
  absent) the first one held. An assignment to an expert that is not held adds
  nothing. The vocabulary is the file's ``vocab_size`` rows.

The indexer is held frozen (the file's ``assumed``): its weights take no
gradient and no update here, as in the program. The value head is
``reference_kimi_vl.py``'s.

Stacked keys start with ``h.``: every layer has every one of them.
"""

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark import reference as base
from benchmark.reference import leaf_norms  # noqa: F401  (what the harness asks of a reference)

Weights = Dict[str, Any]

#: query rows the attention and the selection take at a time
QUERY_BLOCK = 512
#: the weights that take no gradient and no update
FROZEN_PREFIX = "h.idx."


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, under the published config's own keys."""
    held = int(config["num_experts"])
    published = config.get("published", {})
    sa = config["sa_config"]
    if config.get("mlp_only_layers") or config.get("use_sliding_window") or int(config["decoder_sparse_step"]) != 1:
        raise ValueError("dense layers, sliding windows or sparse steps are not the published model")
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexer has one key head")
    return dict(
        d=int(config["hidden_size"]), layers=int(config["num_hidden_layers"]),
        heads=int(config["num_attention_heads"]), kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]), theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        index_heads=int(sa["indexer_num_heads"]), index_dim=int(sa["indexer_head_dim"]),
        index_rope=int(sa["indexer_head_dim"]) // 2, topk=int(sa["topk"]),
        expert_ffn=int(config["moe_intermediate_size"]), top_k=int(config["num_experts_per_tok"]),
        experts=int(published.get("num_experts", held)), held=held, offset=int(config.get("expert_offset", 0)),
        norm_topk=bool(config["norm_topk_prob"]), vocab=int(config["vocab_size"]),
    )


def weight_spec(config: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """name -> (shape, init), as ``reference.weight_spec`` has it."""
    s = dims(config)
    d, L, D = s["d"], s["layers"], s["head_dim"]
    q, kv, fe = s["heads"] * D, s["kv_heads"] * D, s["expert_ffn"]
    Hi, Di = s["index_heads"], s["index_dim"]
    std = float(config.get("initializer_range", 0.02))
    res = std / math.sqrt(2 * L)  # the residual projections, scaled as gpt2's own init scales them
    out = std / math.sqrt(2 * d)  # the value head's output layer (reference_kimi_vl.weight_spec: why)
    embedding = float(config.get("embedding_std", std))  # the file's own, where it gives one (its assumed.weights: why)
    return {
        "wte": ((s["vocab"], d), embedding), "head.w": ((d, s["vocab"]), std), "ln_f.g": ((d,), "ones"),
        "v.fc_in.w": ((d, 2 * d), std), "v.fc_in.b": ((2 * d,), "zeros"),
        "v.fc_out.w": ((2 * d, 1), out), "v.fc_out.b": ((1,), "zeros"),
        "h.ln_1.g": ((L, d), "ones"), "h.ln_2.g": ((L, d), "ones"),
        "h.attn.q.w": ((L, d, q), std), "h.attn.k.w": ((L, d, kv), std), "h.attn.v.w": ((L, d, kv), std),
        "h.attn.q_norm.g": ((L, D), "ones"), "h.attn.k_norm.g": ((L, D), "ones"),
        "h.attn.o.w": ((L, q, d), res),
        "h.idx.q.w": ((L, d, Hi * Di), std), "h.idx.k.w": ((L, d, Di), std),
        "h.idx.k_norm.g": ((L, Di), "ones"), "h.idx.k_norm.b": ((L, Di), "zeros"),
        "h.idx.w.w": ((L, d, Hi), std),
        "h.moe.router.w": ((L, d, s["experts"]), std),
        "h.moe.experts.gate": ((L, s["held"], d, fe), std),
        "h.moe.experts.up": ((L, s["held"], d, fe), std),
        "h.moe.experts.down": ((L, s["held"], fe, d), res),
    }


@functools.lru_cache(maxsize=None)
def _maker(spec: Tuple[Tuple[str, Any], ...]):
    """The jitted init of one sorted weight spec, compiled once a process."""

    def make(key):
        out = {}
        for i, (name, (shape, init)) in enumerate(spec):
            if init == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif init == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = init * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return jax.jit(make)


def init_weights(config: Dict[str, Any], seed: int) -> Weights:
    """Every weight, float32, on the device, in one jitted call from the seed."""
    return _maker(tuple(sorted(weight_spec(config).items())))(jax.random.PRNGKey(int(seed) % (2 ** 63)))


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _rotate_half(x, positions, dims_rotated: int, theta: float):
    """x [B, T, heads, D]: rotate-half over the first ``dims_rotated`` dimensions
    (the pair of dimension i is i + dims_rotated / 2), the rest as they are."""
    half = dims_rotated // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dims_rotated, 2, dtype=jnp.float32) / dims_rotated))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, half]
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    x1, x2, rest = x[..., :half], x[..., half:dims_rotated], x[..., dims_rotated:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def select(scores, allowed, topk: int):
    """scores, allowed [..., S] -> the selection [..., S] bool: the ``min(topk,
    allowed)`` allowed keys of the largest score. The ``topk``-th largest is read
    off the sorted row; the keys above it are kept, and of those equal to it the
    earliest, as many as are left (ties to the lower index)."""
    S = scores.shape[-1]
    k = min(topk, S)
    masked = jnp.where(allowed, scores, -jnp.inf)
    kth = jnp.sort(masked, axis=-1)[..., S - k, None]
    above = masked > kth
    tied = allowed & (masked == kth)
    left = k - above.sum(-1, keepdims=True)
    return allowed & (above | (tied & (jnp.cumsum(tied, axis=-1) <= left)))


def attention(h, lw, s, positions, mask):
    """h [B, T, d] (normed), mask [B, T] -> [B, T, d]."""
    B, T, _ = h.shape
    H, Hkv, D, Hi, Di = s["heads"], s["kv_heads"], s["head_dim"], s["index_heads"], s["index_dim"]
    q = _rms_norm((h @ lw["attn.q.w"]).reshape(B, T, H, D), lw["attn.q_norm.g"], s["eps"])
    k = _rms_norm((h @ lw["attn.k.w"]).reshape(B, T, Hkv, D), lw["attn.k_norm.g"], s["eps"])
    v = (h @ lw["attn.v.w"]).reshape(B, T, Hkv, D)
    q, k = _rotate_half(q, positions, D, s["theta"]), _rotate_half(k, positions, D, s["theta"])
    qi = _rotate_half((h @ lw["idx.q.w"]).reshape(B, T, Hi, Di), positions, s["index_rope"], s["theta"])
    ki = _layer_norm(h @ lw["idx.k.w"], lw["idx.k_norm.g"], lw["idx.k_norm.b"], s["eps"])
    ki = _rotate_half(ki[:, :, None], positions, s["index_rope"], s["theta"])[:, :, 0]
    w = (h @ lw["idx.w.w"]) / math.sqrt(Hi * Di)
    k, v = jnp.repeat(k, H // Hkv, axis=2), jnp.repeat(v, H // Hkv, axis=2)  # query head i reads kv head i // rep

    block = min(QUERY_BLOCK, T)
    Tp = -(-T // block) * block
    pad = lambda x: jnp.pad(x, ((0, 0), (0, Tp - T)) + ((0, 0),) * (x.ndim - 2))
    split = lambda x: pad(x).reshape((B, Tp // block, block) + x.shape[2:]).swapaxes(0, 1)

    @jax.checkpoint
    def rows(args):
        q_b, qi_b, w_b, t_b = args  # a block of query rows; t_b their slots
        scores = (jax.nn.relu(jnp.einsum("bthd,bsd->bths", qi_b, ki)) * w_b[..., None]).sum(2)
        allowed = (jnp.arange(T)[None, None, :] <= t_b[None, :, None]) & (mask[:, None, :] > 0)
        chosen = jax.lax.stop_gradient(select(scores, allowed, s["topk"]))
        logits = jnp.einsum("bthd,bshd->bhts", q_b, k) / math.sqrt(D)
        probs = jax.nn.softmax(jnp.where(chosen[:, None], logits, -1e9), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", probs, v)

    slots = jnp.arange(Tp).reshape(Tp // block, block)
    o = jax.lax.map(rows, (split(q), split(qi), split(w), slots))  # [blocks, B, block, H, D]
    o = o.swapaxes(0, 1).reshape(B, Tp, H * D)[:, :T]
    return o @ lw["attn.o.w"]


def route(h, lw, s):
    """h [..., d] -> the weight of every published expert for every token,
    [..., experts] float32: nothing where the expert was not chosen."""
    probs = jax.nn.softmax(h.astype(jnp.float32) @ lw["moe.router.w"], axis=-1)
    _, chosen = jax.lax.top_k(probs, s["top_k"])
    picked = jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32).sum(-2)
    weights = probs * picked
    if s["norm_topk"]:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights


def experts(h, lw, s):
    """What the experts held here add, [B, T, d]: every held expert on every token, weighted."""
    weights = route(h, lw, s)[..., s["offset"] : s["offset"] + s["held"]]  # [B, T, held]
    gate = jnp.einsum("btd,edf->betf", h, lw["moe.experts.gate"])
    up = jnp.einsum("btd,edf->betf", h, lw["moe.experts.up"])
    weighted = jax.nn.silu(gate) * up * jnp.swapaxes(weights, 1, 2)[..., None]
    return jnp.einsum("betf,efd->btd", weighted, lw["moe.experts.down"])


def forward(w: Weights, config: Dict[str, Any], ids, mask, rows=None):
    """ids, mask [B, T] (mask 1 on real tokens, padding on either side) ->
    (logits [B, T, V], values [B, T]). Positions count real tokens. With
    ``rows`` (a slice), the head and the value head read those positions alone."""
    s = dims(config)
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None)
    layers = {k[2:]: v for k, v in w.items() if k.startswith("h.")}

    @jax.checkpoint
    def block(x, lw):
        x = x + attention(_rms_norm(x, lw["ln_1.g"], s["eps"]), lw, s, positions, mask)
        return x + experts(_rms_norm(x, lw["ln_2.g"], s["eps"]), lw, s), None

    x, _ = jax.lax.scan(block, w["wte"][ids], layers)
    hidden = _rms_norm(x, w["ln_f.g"], s["eps"])
    if rows is not None:
        hidden = hidden[:, rows]
    logits = hidden @ w["head.w"]
    values = jax.nn.relu(hidden @ w["v.fc_in.w"] + w["v.fc_in.b"]) @ w["v.fc_out.w"] + w["v.fc_out.b"]
    return logits, values[..., 0]


def response_window(w, config, seq, mask, P: int, R: int, banned_token=None):
    """``reference.response_window`` over this family's forward; the heads read the window's rows alone."""
    logits, values = forward(w, config, seq, mask, rows=slice(P - 1, P - 1 + R))
    tokens = seq[:, P : P + R]
    logprobs = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), tokens[..., None], -1)[..., 0]
    if banned_token is not None:
        logits = logits.at[..., banned_token].set(-jnp.inf)
    gap = logits.max(-1) - jnp.take_along_axis(logits, tokens[..., None], -1)[..., 0]
    return logprobs, values, gap


def trained(w: Weights) -> Weights:
    """The weights that train: all but the indexer's."""
    return {k: v for k, v in w.items() if not k.startswith(FROZEN_PREFIX)}


def init_opt(w: Weights):
    return base.init_opt(trained(w))


def make_train_step(config, hp: Dict[str, float], P: int, R: int, num_mb: int, block_rows: int):
    """``reference.make_train_step`` (microbatches, the PPO loss, AdamW) over
    this family's forward, over the weights that train: the indexer's are
    handed through as they came, and no gradient of theirs is returned."""

    def step(w, opt, batch):
        frozen = {k: v for k, v in w.items() if k.startswith(FROZEN_PREFIX)}
        window = lambda t, *args, **kwargs: response_window({**t, **frozen}, *args, **kwargs)
        inner = base.make_train_step(config, hp, P, R, num_mb, block_rows, response_window=window)
        new, opt, losses, grads = inner.__wrapped__(trained(w), opt, batch)
        return {**new, **frozen}, opt, losses, grads

    return jax.jit(step, donate_argnums=(0, 1))
