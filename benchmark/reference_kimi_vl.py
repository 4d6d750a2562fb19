"""The plain reference of the kimi_vl family: the language model of
Kimi-VL-A3B (latent attention, sigmoid-routed experts beside shared ones, one
leading dense layer) as one chip's share of a deployment, in float32
``jax.numpy``; the PPO loss, the microbatched step and AdamW are
``benchmark/reference.py``'s.

No kernels, no cache, no sorting: keys and values are expanded from the latent
for every token, and every held expert is applied to every token and weighted
by what the router gave it (nothing where it was not chosen). Every matmul is
under ``jax.default_matmul_precision("highest")`` (the callers set it). It
imports nothing of the program.

The layers, with ``h = RMSNorm(x)`` before each half of a block:

- attention: ``q = h W_q`` as heads of ``nope + rope``; ``[c_raw, k_rope] =
  h W_kva`` (``latent + rope``), ``c = RMSNorm(c_raw)``, ``k_rope`` one head
  shared by all; rotary on ``q_rope`` and ``k_rope``; ``[k_nope, v] = c W_kvb``
  per head; scores ``q . [k_nope, k_rope] / sqrt(nope + rope)``; causal
  softmax; ``o = P v``; ``out = o W_o``. No biases.
- layer 0's FFN is SwiGLU of ``intermediate_size``; every later layer's is
  ``sum_k w_k E_k(h) + S(h)``: ``s = sigmoid(h W_g)`` over all published
  experts, the ``num_experts_per_tok`` largest of ``s + b`` chosen, ``w`` their
  ``s`` divided by its sum over the chosen and scaled by
  ``routed_scaling_factor``; ``E`` SwiGLU of ``moe_intermediate_size``, ``S``
  of ``n_shared_experts`` times that.
- the share: ``n_routed_experts`` of the file counts the experts held here,
  ``published.n_routed_experts`` the router's width, ``expert_offset`` (0 where
  absent) the first one held. An assignment to an expert that is not held adds
  nothing. The vocabulary is the file's ``vocab_size`` rows.

Departures from the published model, all under the file's ``assumed``: the
rotary pairs are the two halves of the 64 (rotate-half; the published
checkpoint interleaves them and the program's loader permutes), and a
two-layer value head reads the final hidden state, its output layer drawn at
``initializer_range / sqrt(fan-in)``.

Stacked keys start with ``h.``; a key's stack holds the layers that have it:
the attention and norm keys all of them, ``h.dense.*`` the leading dense
layers, ``h.moe.*`` the expert layers.
"""

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from benchmark import reference as base
from benchmark.reference import init_opt, leaf_norms  # noqa: F401  (what the harness asks of a reference)

Weights = Dict[str, Any]


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, under the published config's own keys."""
    held = int(config["n_routed_experts"])
    published = config.get("published", {})
    return dict(
        d=int(config["hidden_size"]), layers=int(config["num_hidden_layers"]),
        dense_layers=int(config["first_k_dense_replace"]),
        heads=int(config["num_attention_heads"]), nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]), vdim=int(config["v_head_dim"]),
        latent=int(config["kv_lora_rank"]), theta=float(config["rope_theta"]),
        ffn=int(config["intermediate_size"]), expert_ffn=int(config["moe_intermediate_size"]),
        shared=int(config["n_shared_experts"]), top_k=int(config["num_experts_per_tok"]),
        experts=int(published.get("n_routed_experts", held)), held=held,
        offset=int(config.get("expert_offset", 0)),
        scale=float(config["routed_scaling_factor"]), norm_topk=bool(config["norm_topk_prob"]),
        vocab=int(config["vocab_size"]), eps=float(config["rms_norm_eps"]),
    )


def weight_spec(config: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """name -> (shape, init), as ``reference.weight_spec`` has it."""
    s = dims(config)
    d, L, H = s["d"], s["layers"], s["heads"]
    Ld, Le = s["dense_layers"], s["layers"] - s["dense_layers"]
    f, fe, fs = s["ffn"], s["expert_ffn"], s["shared"] * s["expert_ffn"]
    std = float(config.get("initializer_range", 0.02))
    res = std / math.sqrt(2 * L)  # the residual projections, scaled as gpt2's own init scales them
    # the value head's output layer, scaled by its fan-in: at ``std`` Adam's first steps of ``lr`` a
    # weight, all one way, move every value by 1.6 a step at this width, eight times
    # ``cliprange_value``, and what step 3 lands on is then decided by rounding (PERF.md, PR 29)
    out = std / math.sqrt(2 * d)
    return {
        "wte": ((s["vocab"], d), std), "head.w": ((d, s["vocab"]), std), "ln_f.g": ((d,), "ones"),
        "v.fc_in.w": ((d, 2 * d), std), "v.fc_in.b": ((2 * d,), "zeros"),
        "v.fc_out.w": ((2 * d, 1), out), "v.fc_out.b": ((1,), "zeros"),
        "h.ln_1.g": ((L, d), "ones"), "h.ln_2.g": ((L, d), "ones"),
        "h.q.w": ((L, d, H * (s["nope"] + s["rope"])), std),
        "h.kva.w": ((L, d, s["latent"] + s["rope"]), std),
        "h.kva_norm.g": ((L, s["latent"]), "ones"),
        "h.kvb.w": ((L, s["latent"], H * (s["nope"] + s["vdim"])), std),
        "h.o.w": ((L, H * s["vdim"], d), res),
        "h.dense.gate.w": ((Ld, d, f), std), "h.dense.up.w": ((Ld, d, f), std),
        "h.dense.down.w": ((Ld, f, d), res),
        "h.moe.router.w": ((Le, d, s["experts"]), std),
        # the selection bias: small and not zero, so that it chooses
        "h.moe.router.b": ((Le, s["experts"]), std),
        "h.moe.experts.gate": ((Le, s["held"], d, fe), std),
        "h.moe.experts.up": ((Le, s["held"], d, fe), std),
        "h.moe.experts.down": ((Le, s["held"], fe, d), res),
        "h.moe.shared.gate.w": ((Le, d, fs), std), "h.moe.shared.up.w": ((Le, d, fs), std),
        "h.moe.shared.down.w": ((Le, fs, d), res),
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
    """x [..., rope], cos/sin broadcastable [..., rope / 2]: the pair of
    dimension i is (i, i + rope / 2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def attention(h, lw, s, positions, bias):
    """h [B, T, d] (normed) -> [B, T, d]."""
    B, T, _ = h.shape
    H, nope, rope, vdim, latent = s["heads"], s["nope"], s["rope"], s["vdim"], s["latent"]
    q = (h @ lw["q.w"]).reshape(B, T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    kva = h @ lw["kva.w"]
    c = _rms_norm(kva[..., :latent], lw["kva_norm.g"], s["eps"])
    k_rope = kva[..., latent:]  # [B, T, rope], one head
    inv_freq = 1.0 / (s["theta"] ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, rope / 2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    q_rope = _rotate_half(q_rope, cos[:, :, None], sin[:, :, None])
    k_rope = _rotate_half(k_rope, cos, sin)
    kv = (c @ lw["kvb.w"]).reshape(B, T, H, nope + vdim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = jnp.einsum("bthd,bshd->bhts", q_nope, k_nope) + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope)
    probs = jax.nn.softmax(scores / math.sqrt(nope + rope) + bias, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", probs, v)
    return o.reshape(B, T, H * vdim) @ lw["o.w"]


def route(h, lw, s):
    """h [..., d] -> the weight of every published expert for every token,
    [..., experts] float32: nothing where the expert was not chosen."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32) @ lw["moe.router.w"])
    _, chosen = jax.lax.top_k(scores + lw["moe.router.b"], s["top_k"])
    picked = jax.nn.one_hot(chosen, s["experts"], dtype=jnp.float32).sum(-2)
    weights = scores * picked
    if s["norm_topk"]:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights * s["scale"]


def moe_parts(h, lw, s):
    """(what the experts held here add, what the shared experts add), each
    [B, T, d]. The share's routed part: every held expert on every token."""
    weights = route(h, lw, s)[..., s["offset"] : s["offset"] + s["held"]]  # [B, T, held]
    gate = jnp.einsum("btd,edf->betf", h, lw["moe.experts.gate"])
    up = jnp.einsum("btd,edf->betf", h, lw["moe.experts.up"])
    each = jnp.einsum("betf,efd->betd", jax.nn.silu(gate) * up, lw["moe.experts.down"])
    routed = jnp.einsum("betd,bte->btd", each, weights)
    shared = _swiglu(h, lw["moe.shared.gate.w"], lw["moe.shared.up.w"], lw["moe.shared.down.w"])
    return routed, shared


def _stacks(w: Weights, kind: str, start: int, stop: int) -> Weights:
    """What one scan over layers [start, stop) walks: the attention and norm
    stacks cut to those layers, and the ``kind`` ("dense" or "moe") stacks whole."""
    cut = {k[2:]: v[start:stop] for k, v in w.items() if k.startswith("h.") and k[2:].split(".")[0] not in ("dense", "moe")}
    return {**cut, **{k[2:]: v for k, v in w.items() if k.startswith(f"h.{kind}.")}}


def forward(w: Weights, config: Dict[str, Any], ids, mask):
    """ids, mask [B, T] (mask 1 on real tokens, padding on either side) ->
    (logits [B, T, V], values [B, T]). Positions count real tokens."""
    s = dims(config)
    B, T = ids.shape
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None)
    x = w["wte"][ids]
    allowed = jnp.tril(jnp.ones((T, T), bool))[None, None] & mask[:, None, None, :].astype(bool)
    bias = jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)

    def block(ffn):
        def apply(x, lw):
            x = x + attention(_rms_norm(x, lw["ln_1.g"], s["eps"]), lw, s, positions, bias)
            return x + ffn(_rms_norm(x, lw["ln_2.g"], s["eps"]), lw), None
        return apply

    dense = lambda h, lw: _swiglu(h, lw["dense.gate.w"], lw["dense.up.w"], lw["dense.down.w"])
    experts = lambda h, lw: sum(moe_parts(h, lw, s))
    x, _ = jax.lax.scan(block(dense), x, _stacks(w, "dense", 0, s["dense_layers"]))
    x, _ = jax.lax.scan(block(experts), x, _stacks(w, "moe", s["dense_layers"], s["layers"]))
    hidden = _rms_norm(x, w["ln_f.g"], s["eps"])
    logits = hidden @ w["head.w"]
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
