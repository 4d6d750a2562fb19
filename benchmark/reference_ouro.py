"""The plain reference of the ouro family: Ouro-2.6B, a looped language model
(one stack of layers applied ``total_ut_steps`` times over the same weights),
in float32 ``jax.numpy``; the PPO loss, the microbatched step and AdamW are
``benchmark/reference.py``'s.

No kernels, no cache: every position attends over the whole sequence in every
pass. Every matmul is under ``jax.default_matmul_precision("highest")`` (the
callers set it). It imports nothing of the program.

The model, as read from the published description (``modeling_ouro.py`` beside
the named ``config.json``, and arXiv:2510.25741; nothing was fetched, so each
point is also a sentence under the configuration file's ``assumed``). No
biases anywhere but the gate's and the value head's:

- ``h = E[ids]``. For ``t = 1 .. total_ut_steps``: for every layer ``i``:
  ``h = Block_i(h)``; then ``h = RMSNorm_f(h)``; that normed ``h_t`` is the
  input of pass ``t + 1``. ``logits = h_last W_head``; the value head reads
  ``h_last``. Blocks, ``RMSNorm_f`` and the rotary positions are the same in
  every pass.
- ``Block(x)``: ``x = x + N2(Attn(N1(x)))``, ``x = x + N4(FFN(N3(x)))``: sandwich
  norms, four RMSNorms a block with a scale each (``N1`` ``input_layernorm``,
  ``N2`` ``input_layernorm_2``, ``N3`` ``post_attention_layernorm``, ``N4``
  ``post_attention_layernorm_2``; here ``ln_1``, ``ln_1_post``, ``ln_2``,
  ``ln_2_post``).
- ``Attn``: heads of ``head_dim``; rotary (rotate-half, every dimension,
  ``rope_theta``) on q and k; scores ``q k / sqrt(head_dim)``; causal softmax.
  ``FFN(h) = W_down(silu(W_gate h) * W_up h)``.
- the exit gate: ``lambda_t = sigmoid(h_t w_g + b_g)``; the exit distribution
  is ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t`` before the last
  pass and the remaining mass at the last (:func:`exit_distribution`). At the
  published ``early_exit_threshold`` of 1 no token leaves early, so the gate
  is on no path to the logits and takes no gradient from the PPO loss; AdamW
  decays it.

Departures: each pass is wrapped in ``jax.checkpoint`` (the backward computes
a pass's forward again instead of keeping the activations of all
``passes x layers`` block applications; the numbers are the same), and a
two-layer value head reads the final hidden state, its output layer drawn at
``initializer_range / sqrt(2 hidden)`` as ``reference_kimi_vl.py`` draws it.

Stacked keys start with ``h.`` and hold every layer.
"""

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark import reference as base
from benchmark.reference import init_opt, leaf_norms  # noqa: F401  (what the harness asks of a reference)

Weights = Dict[str, Any]


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, under the published config's own keys."""
    if float(config["early_exit_threshold"]) < 1.0:
        raise ValueError("an early_exit_threshold under 1 lets tokens leave at different passes; not computed here")
    return dict(
        d=int(config["hidden_size"]), layers=int(config["num_hidden_layers"]),
        passes=int(config["total_ut_steps"]), heads=int(config["num_attention_heads"]),
        head_dim=int(config["head_dim"]), ffn=int(config["intermediate_size"]),
        vocab=int(config["vocab_size"]), eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
    )


def weight_spec(config: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """name -> (shape, init), as ``reference.weight_spec`` has it."""
    s = dims(config)
    d, L, f, hd = s["d"], s["layers"], s["ffn"], s["heads"] * s["head_dim"]
    std = float(config.get("initializer_range", 0.02))
    res = std / math.sqrt(2 * L)  # the residual projections, scaled as gpt2's own init scales them
    out = std / math.sqrt(2 * d)  # the value head's output layer (reference_kimi_vl.weight_spec: why)
    spec = {
        "wte": ((s["vocab"], d), std), "head.w": ((d, s["vocab"]), std), "ln_f.g": ((d,), "ones"),
        "exit.w": ((d, 1), std), "exit.b": ((1,), "zeros"),
        "v.fc_in.w": ((d, 2 * d), std), "v.fc_in.b": ((2 * d,), "zeros"),
        "v.fc_out.w": ((2 * d, 1), out), "v.fc_out.b": ((1,), "zeros"),
        "h.q.w": ((L, d, hd), std), "h.k.w": ((L, d, hd), std), "h.v.w": ((L, d, hd), std),
        "h.o.w": ((L, hd, d), res),
        "h.gate.w": ((L, d, f), std), "h.up.w": ((L, d, f), std), "h.down.w": ((L, f, d), res),
    }
    for norm in ("ln_1", "ln_1_post", "ln_2", "ln_2_post"):
        spec[f"h.{norm}.g"] = ((L, d), "ones")
    return spec


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
    """x [B, T, H, D], cos/sin [B, T, 1, D / 2]: dimension i pairs with i + D / 2."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def block(x, lw, s, cos, sin, bias):
    """One layer on x [B, T, d], with its four norms."""
    B, T, _ = x.shape
    H, D = s["heads"], s["head_dim"]
    h = _rms_norm(x, lw["ln_1.g"], s["eps"])
    q, k, v = ((h @ lw[f"{n}.w"]).reshape(B, T, H, D) for n in ("q", "k", "v"))
    q, k = _rotate_half(q, cos, sin), _rotate_half(k, cos, sin)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(D) + bias
    attn = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v).reshape(B, T, H * D)
    x = x + _rms_norm(attn @ lw["o.w"], lw["ln_1_post.g"], s["eps"])
    h = _rms_norm(x, lw["ln_2.g"], s["eps"])
    ffn = (jax.nn.silu(h @ lw["gate.w"]) * (h @ lw["up.w"])) @ lw["down.w"]
    return x + _rms_norm(ffn, lw["ln_2_post.g"], s["eps"])


def hidden_states(w: Weights, config: Dict[str, Any], ids, mask, passes: Optional[int] = None) -> List[Any]:
    """ids, mask [B, T] (mask 1 on real tokens, padding on either side) -> the
    normed state after each pass, ``passes`` (the configuration's, where not
    given) arrays [B, T, d]. Positions count real tokens."""
    s = dims(config)
    B, T = ids.shape
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None)
    inv_freq = 1.0 / (s["theta"] ** (jnp.arange(0, s["head_dim"], 2, dtype=jnp.float32) / s["head_dim"]))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, D / 2]
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    allowed = jnp.tril(jnp.ones((T, T), bool))[None, None] & mask[:, None, None, :].astype(bool)
    bias = jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)
    layers = {k[2:]: v for k, v in w.items() if k.startswith("h.")}

    @jax.checkpoint  # a departure (the docstring): the backward walks a pass again
    def one_pass(x, layers, final):
        x, _ = jax.lax.scan(lambda x, lw: (block(x, lw, s, cos, sin, bias), None), x, layers)
        return _rms_norm(x, final, s["eps"])

    x, states = w["wte"][ids], []
    for _ in range(s["passes"] if passes is None else passes):
        x = one_pass(x, layers, w["ln_f.g"])
        states.append(x)
    return states


def exit_distribution(w: Weights, states: List[Any]):
    """The gate's exit distribution over the passes, [passes, B, T]: what the
    published early-exit rule accumulates against ``early_exit_threshold``."""
    stay, out = 1.0, []
    for t, h in enumerate(states):
        leave = jax.nn.sigmoid(h @ w["exit.w"] + w["exit.b"])[..., 0]
        out.append(stay if t == len(states) - 1 else leave * stay)
        stay = stay * (1.0 - leave)
    return jnp.stack(out)


def forward(w: Weights, config: Dict[str, Any], ids, mask, passes: Optional[int] = None):
    """-> (logits [B, T, V], values [B, T]) from the last pass's state."""
    hidden = hidden_states(w, config, ids, mask, passes)[-1]
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
