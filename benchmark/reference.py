"""The plain reference: a float32 gpt2-family forward, the PPO loss of trlX and
AdamW, in straightforward ``jax.numpy``.

No kernels, no cache, no batching tricks; every matmul under
``jax.default_matmul_precision("highest")`` (the callers set it). It imports
nothing of the program and takes nothing the program made: the weights come
from :func:`init_weights` and the seed, the hyper-parameters from the cell's
files. The layers are stacked ``[L, ...]`` and walked with ``lax.scan`` so
that the reference compiles in seconds at any depth.

Departures from the published gpt2: q, k and v are three ``[d, d]`` matrices
(the published checkpoint fuses them into ``c_attn``; the mathematics is the
same), and a two-layer value head (``d -> 2d -> relu -> 1``, trlX's
``make_head``) reads the final hidden state.
"""

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Weights = Dict[str, Any]


def dims(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes the reference needs, under the published config's own keys."""
    d = int(config["n_embd"])
    return dict(
        d=d, layers=int(config["n_layer"]), heads=int(config["n_head"]),
        vocab=int(config["vocab_size"]), positions=int(config["n_positions"]),
        ffn=int(config.get("n_inner") or 4 * d),
    )


def weight_spec(config: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """name -> (shape, init): init is a normal's std, or 1.0 / 0.0 written as
    the strings "ones" / "zeros". Keys under ``h.`` are stacked over layers."""
    s = dims(config)
    d, L, f = s["d"], s["layers"], s["ffn"]
    std = float(config["initializer_range"])
    res = std / math.sqrt(2 * L)  # gpt2's scaled init of the residual projections
    spec = {
        "wte": ((s["vocab"], d), std), "wpe": ((s["positions"], d), std),
        "ln_f.g": ((d,), "ones"), "ln_f.b": ((d,), "zeros"),
        "v.fc_in.w": ((d, 2 * d), std), "v.fc_in.b": ((2 * d,), "zeros"),
        "v.fc_out.w": ((2 * d, 1), std), "v.fc_out.b": ((1,), "zeros"),
    }
    for ln in ("ln_1", "ln_2"):
        spec[f"h.{ln}.g"] = ((L, d), "ones")
        spec[f"h.{ln}.b"] = ((L, d), "zeros")
    for name, shape, init in (
        ("q", (d, d), std), ("k", (d, d), std), ("v", (d, d), std), ("o", (d, d), res),
        ("up", (d, f), std), ("down", (f, d), res),
    ):
        spec[f"h.{name}.w"] = ((L,) + shape, init)
        spec[f"h.{name}.b"] = ((L, shape[1]), "zeros")
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


def _layer_norm(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(w: Weights, config: Dict[str, Any], ids, mask):
    """ids, mask [B, T] (mask 1 on real tokens, padding on either side) ->
    (logits [B, T, V], values [B, T]). Positions count real tokens."""
    s = dims(config)
    eps = float(config["layer_norm_epsilon"])
    B, T = ids.shape
    H, D = s["heads"], s["d"] // s["heads"]
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None)
    x = w["wte"][ids] + w["wpe"][positions]
    allowed = jnp.tril(jnp.ones((T, T), bool))[None, None] & mask[:, None, None, :].astype(bool)
    bias = jnp.where(allowed, 0.0, -1e9).astype(jnp.float32)

    def block(x, lw):
        h = _layer_norm(x, lw["ln_1.g"], lw["ln_1.b"], eps)
        q, k, v = (
            (h @ lw[f"{n}.w"] + lw[f"{n}.b"]).reshape(B, T, H, D) for n in ("q", "k", "v")
        )
        scores = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(D) + bias
        attn = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)
        x = x + attn.reshape(B, T, H * D) @ lw["o.w"] + lw["o.b"]
        h = _layer_norm(x, lw["ln_2.g"], lw["ln_2.b"], eps)
        x = x + _gelu_new(h @ lw["up.w"] + lw["up.b"]) @ lw["down.w"] + lw["down.b"]
        return x, None

    layers = {k[2:]: v for k, v in w.items() if k.startswith("h.")}
    x, _ = jax.lax.scan(block, x, layers)
    hidden = _layer_norm(x, w["ln_f.g"], w["ln_f.b"], eps)
    logits = hidden @ w["wte"].T
    values = jax.nn.relu(hidden @ w["v.fc_in.w"] + w["v.fc_in.b"]) @ w["v.fc_out.w"] + w["v.fc_out.b"]
    return logits, values[..., 0]


def response_window(w, config, seq, mask, P: int, R: int, banned_token=None):
    """What PPO reads of a forward over prompt+response: for each of the R
    response tokens its log-probability and the value before it, and how far
    the token's logit lies below the best one (``banned_token`` may not be the
    best: the generator masks eos while ``min_new_tokens`` holds)."""
    logits, values = forward(w, config, seq, mask)
    logits = logits[:, P - 1 : P - 1 + R]
    tokens = seq[:, P : P + R]
    logprobs = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), tokens[..., None], -1)[..., 0]
    if banned_token is not None:
        logits = logits.at[..., banned_token].set(-jnp.inf)
    gap = logits.max(-1) - jnp.take_along_axis(logits, tokens[..., None], -1)[..., 0]
    return logprobs, values[:, P - 1 : P - 1 + R], gap


# ------------------------------------------------------------------- PPO


def whiten(x, mask):
    n = jnp.maximum(mask.sum(), 1e-8)
    mean = (x * mask).sum() / n
    var = (((x - mean) ** 2) * mask).sum() / n
    return (x - mean) * jax.lax.rsqrt(var + 1e-8)


def gae(values, rewards, mask, gamma: float, lam: float):
    """Generalised advantage estimation over the response window, then
    whitened advantages (trlX ``get_advantages_and_returns``)."""
    values, rewards = values * mask, rewards * mask
    next_values = jnp.concatenate([values[:, 1:], jnp.zeros_like(values[:, :1])], axis=1)
    next_mask = jnp.concatenate([mask[:, 1:], jnp.zeros_like(mask[:, :1])], axis=1)
    deltas = rewards + gamma * next_values * next_mask - values

    def back(last, xs):  # from the last response token to the first
        delta, nmask = xs
        last = delta + gamma * lam * nmask * last
        return last, last

    _, rev = jax.lax.scan(back, jnp.zeros_like(deltas[:, 0]), (deltas.T[::-1], next_mask.T[::-1]))
    advantages = rev[::-1].T * mask
    returns = advantages + values
    return whiten(advantages, mask) * mask, returns


def ppo_token_losses(logprobs, values, old_logprobs, old_values, advantages, returns, mask, hp):
    """The clipped policy term and the weighted value term of every response
    token, masked."""
    clipped = jnp.clip(values, old_values - hp["cliprange_value"], old_values + hp["cliprange_value"])
    vf = 0.5 * jnp.maximum((values - returns) ** 2, (clipped - returns) ** 2)
    ratio = jnp.exp((logprobs - old_logprobs) * mask)
    pg = jnp.maximum(
        -advantages * ratio,
        -advantages * jnp.clip(ratio, 1.0 - hp["cliprange"], 1.0 + hp["cliprange"]),
    )
    return pg * mask, hp["vf_coef"] * vf * mask


def make_train_step(config, hp: Dict[str, float], P: int, R: int, num_mb: int, block_rows: int,
                    response_window=response_window):
    """One optimizer step as the configuration states it: the batch is cut
    into ``num_mb`` microbatches, each whitens its own advantages and takes
    the mean over its own tokens, the gradients are averaged, AdamW follows.
    Rows go through in blocks of ``block_rows`` so that float32 fits. Returns
    the new weights and state, the loss as (policy term, value term), and the
    gradient. Another family's reference passes its own ``response_window``."""

    def block_loss(w, seq, mask, old_lp, old_v, adv, ret, row_scale):
        logprobs, values, _ = response_window(w, config, seq, mask, P, R)
        rmask = mask[:, P:].astype(jnp.float32)
        pg, vf = ppo_token_losses(logprobs, values, old_lp, old_v, adv, ret, rmask, hp)
        pg, vf = (pg.sum(axis=1) * row_scale).sum(), (vf.sum(axis=1) * row_scale).sum()
        return pg + vf, (pg, vf)

    def step(w, opt, batch):
        seq, mask, old_lp, old_v, rewards = batch
        B = seq.shape[0]
        rmask = mask[:, P:].astype(jnp.float32)
        mb = lambda x: x.reshape((num_mb, B // num_mb) + x.shape[1:])
        adv, ret = jax.vmap(lambda v, r, m: gae(v, r, m, hp["gamma"], hp["lam"]))(
            mb(old_v), mb(rewards), mb(rmask)
        )
        adv, ret = adv.reshape(B, R), ret.reshape(B, R)
        tokens_per_mb = jnp.maximum(mb(rmask).sum(axis=(1, 2)), 1.0)
        row_scale = jnp.repeat(1.0 / (tokens_per_mb * num_mb), B // num_mb)
        blocks = jax.tree.map(
            lambda x: x.reshape((B // block_rows, block_rows) + x.shape[1:]),
            (seq, mask, old_lp, old_v, adv, ret, row_scale),
        )

        def body(carry, blk):
            (_, (pg, vf)), grads = jax.value_and_grad(block_loss, has_aux=True)(w, *blk)
            return (carry[0] + pg, carry[1] + vf, jax.tree.map(jnp.add, carry[2], grads)), None

        zero = (jnp.float32(0.0), jnp.float32(0.0), jax.tree.map(jnp.zeros_like, w))
        (pg, vf, grads), _ = jax.lax.scan(body, zero, blocks)

        count = opt["count"] + 1
        mu = jax.tree.map(lambda m, g: hp["b1"] * m + (1 - hp["b1"]) * g, opt["mu"], grads)
        nu = jax.tree.map(lambda n, g: hp["b2"] * n + (1 - hp["b2"]) * g * g, opt["nu"], grads)
        c1 = 1 - hp["b1"] ** count.astype(jnp.float32)
        c2 = 1 - hp["b2"] ** count.astype(jnp.float32)
        new_w = jax.tree.map(
            lambda p, m, n: p - hp["lr"] * ((m / c1) / (jnp.sqrt(n / c2) + hp["eps"]) + hp["weight_decay"] * p),
            w, mu, nu,
        )
        return new_w, dict(count=count, mu=mu, nu=nu), (pg, vf), grads

    return jax.jit(step, donate_argnums=(0, 1))


def init_opt(w: Weights):
    zeros = lambda: jax.tree.map(jnp.zeros_like, w)
    return dict(count=jnp.zeros((), jnp.int32), mu=zeros(), nu=zeros())


def leaf_norms(tree: Weights) -> Dict[str, Any]:
    """The norm of every leaf; of a stacked ``h.`` leaf, one norm per layer."""
    def norm(name, x):
        if name.startswith("h."):
            return jnp.sqrt((x.reshape(x.shape[0], -1) ** 2).sum(axis=1))
        return jnp.sqrt((x ** 2).sum())

    return {name: norm(name, x) for name, x in tree.items()}
