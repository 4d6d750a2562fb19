"""Jitted KV-cache autoregressive generation under SPMD.

This replaces the reference's reliance on HF ``generate`` / NeMo ``text_generation``
(SURVEY.md §2.4.8 — "the rollout hot loop"): prefill builds the cache in one forward,
then a ``lax.while_loop`` decodes one token per step with early exit when every
sequence has finished (under SPMD the ``finished`` reduction is global, giving the
pod-wide eos short-circuit the reference gets from ``synced_gpus``). All shapes are
static: prompts are left-padded to a bucketed length, the cache is preallocated at
``prompt_len + max_new_tokens``, and the sequence buffer is donated across steps.

ILQL's advantage-shaped decoding (reference ``modeling_ilql.py:325-412``) plugs in as
a ``logits_processor(params, hidden, logits, prev_token) -> logits`` hook evaluated on the decode
hidden state each step.
"""

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from trlx_tpu.analysis.ir.entrypoints import EntryArtifacts, register_entrypoint
from trlx_tpu.ops.sampling import sample_token

# step_fn(params, ids[B,T], mask[B,S], positions[B,T], cache) -> (logits[B,T,V],
# hidden[B,T,H], cache). `hidden` feeds the ILQL logit processor; pass None-free.
StepFn = Callable[..., Tuple[jnp.ndarray, jnp.ndarray, Any]]


#: the padding ladder (8 .. 8192): prompt, response and sequence lengths are padded up to the next
#: of these, so that a run compiles one program a bucket and not one a length
LENGTH_BUCKETS = tuple(2 ** i for i in range(3, 14))


def pad_to_bucket(length: int, buckets: Sequence[int], cap: Optional[int] = None) -> int:
    """Smallest bucket >= length (limits recompilation across prompt lengths;
    parity concern: reference pads to multiples of 8, SURVEY.md §7 hard-part 3).

    ``cap`` is the longest length the caller's configuration allows on this
    axis: a rung above it adds no shape that could be needed, so a length
    within the cap pads to ``min(rung, cap)``. A length past the cap (a caller
    that did not hold to it) pads to the rung, never below itself."""
    rung = next((b for b in sorted(buckets) if b >= length), int(np.ceil(length / 64) * 64))
    return min(rung, cap) if cap is not None and length <= cap else rung


def left_pad_batch(
    ids_list: List[np.ndarray], pad_token_id: int, target_len: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side: left-pad a ragged list of prompt id arrays to [B, target_len]
    (C++ data plane when available, numpy otherwise)."""
    from trlx_tpu.native import pad_collate_i32

    return pad_collate_i32(ids_list, target_len, pad_token_id, pad_left=True)


def generate(
    step_fn: StepFn,
    params: Any,
    init_cache_fn: Callable[[int, int], Any],
    input_ids: jnp.ndarray,
    attention_mask: jnp.ndarray,
    rng: jax.Array,
    max_new_tokens: int,
    eos_token_id: Optional[int] = None,
    pad_token_id: int = 0,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    do_sample: bool = True,
    top_k_impl: str = "approx",
    min_new_tokens: int = 0,
    logits_processor: Optional[Callable[[Any, jnp.ndarray, jnp.ndarray], jnp.ndarray]] = None,
) -> Dict[str, jnp.ndarray]:
    """Generate continuations for left-padded prompts.

    Returns dict with ``sequences`` [B, P+N] (prompt + generation, ``pad_token_id``
    after eos) and ``response_mask`` [B, N] (1 on generated tokens up to & incl. eos).
    Fully traceable: wrap in jit with static max_new_tokens via the trainer.
    """
    B, P = input_ids.shape
    N = int(max_new_tokens)
    total = P + N
    prompt_lens = attention_mask.sum(axis=1).astype(jnp.int32)

    cache = init_cache_fn(B, total)
    # pytree structure is static under trace — `in` probes dict keys, never
    # array values
    if isinstance(cache, dict) and "index" in cache:  # graftcheck: noqa[JX004]
        # static Python 0: marks prefill-from-zero at TRACE time, so the model's
        # prefill-only paths (flash kernel, prompt-tuning prepend) engage even
        # when this whole function is wrapped in an outer jit (where a
        # jnp.array(0) constant would already be a tracer)
        cache = {**cache, "index": 0}
    # mask over all cache slots; generated slots get enabled as they are written
    full_mask = jnp.concatenate([attention_mask.astype(jnp.int32), jnp.zeros((B, N), jnp.int32)], axis=1)

    positions = jnp.clip(jnp.cumsum(attention_mask, axis=1) - 1, 0, None).astype(jnp.int32)
    with jax.named_scope("prefill"):
        logits, hidden, cache = step_fn(params, input_ids, full_mask, positions, cache)
    last_logits = logits[:, -1, :]
    if logits_processor is not None:
        last_logits = logits_processor(params, hidden[:, -1, :], last_logits, input_ids[:, -1])

    seqs = jnp.concatenate([input_ids, jnp.full((B, N), pad_token_id, jnp.int32)], axis=1)

    def sample_step(rng, step, logits, finished):
        rng, sub = jax.random.split(rng)
        if eos_token_id is not None and min_new_tokens > 0:
            logits = jnp.where(
                (step < min_new_tokens) & (jnp.arange(logits.shape[-1]) == eos_token_id)[None, :],
                -1e9,
                logits,
            )
        tok = sample_token(sub, logits, temperature, top_k, top_p, do_sample, top_k_impl)
        tok = jnp.where(finished, pad_token_id, tok)
        return rng, tok

    rng, tok = sample_step(rng, jnp.array(0), last_logits, jnp.zeros((B,), bool))
    finished = jnp.zeros((B,), bool)
    if eos_token_id is not None:
        finished = tok == eos_token_id

    def write(state_seqs, state_mask, tok, step):
        new_seqs = jax.lax.dynamic_update_slice(state_seqs, tok[:, None], (0, P + step))
        new_mask = jax.lax.dynamic_update_slice(
            state_mask, jnp.ones((B, 1), jnp.int32), (0, P + step)
        )
        return new_seqs, new_mask

    seqs, full_mask = write(seqs, full_mask, tok, 0)

    def cond(state):
        step, _, _, finished, _, _, _ = state
        return jnp.logical_and(step < N, jnp.logical_not(jnp.all(finished)))

    def body(state):
        step, seqs, full_mask, finished, cache, rng, tok = state
        # `tok` was sampled at iteration step-1 and sits at sequence slot P+step-1,
        # i.e. per-sample position prompt_len + step - 1
        pos = (prompt_lens + step - 1)[:, None]
        logits, hidden, cache = step_fn(params, tok[:, None], full_mask, pos, cache)
        step_logits = logits[:, -1, :]
        if logits_processor is not None:
            step_logits = logits_processor(params, hidden[:, -1, :], step_logits, tok)
        rng, new_tok = sample_step(rng, step, step_logits, finished)
        new_finished = finished
        if eos_token_id is not None:
            new_finished = jnp.logical_or(finished, new_tok == eos_token_id)
        seqs, full_mask = write(seqs, full_mask, new_tok, step)
        return step + 1, seqs, full_mask, new_finished, cache, rng, new_tok

    state = (jnp.array(1, jnp.int32), seqs, full_mask, finished, cache, rng, tok)
    with jax.named_scope("decode"):
        step, seqs, full_mask, finished, cache, rng, tok = jax.lax.while_loop(cond, body, state)

    response_mask = full_mask[:, P:]
    # zero out mask past each sample's eos is already handled: finished samples write
    # pad tokens but their mask slots were set; rebuild mask from tokens instead:
    if eos_token_id is not None:
        resp = seqs[:, P:]
        is_eos = resp == eos_token_id
        after_eos = jnp.cumsum(jnp.pad(is_eos[:, :-1], ((0, 0), (1, 0))), axis=1) > 0
        response_mask = response_mask * (1 - after_eos.astype(jnp.int32))
        # never count trailing never-written slots (loop exited early)
        written = jnp.arange(N)[None, :] < step
        response_mask = response_mask * written.astype(jnp.int32)
        seqs = jnp.concatenate(
            [seqs[:, :P], jnp.where(response_mask > 0, resp, pad_token_id)], axis=1
        )
    return {"sequences": seqs, "response_mask": response_mask}


def generate_seq2seq(
    encode_fn,
    cross_kv_fn,
    decode_fn,
    init_cache_fn,
    params: Any,
    input_ids: jnp.ndarray,
    attention_mask: jnp.ndarray,
    rng: jax.Array,
    max_new_tokens: int,
    decoder_start_token_id: int = 0,
    eos_token_id: Optional[int] = None,
    pad_token_id: int = 0,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    do_sample: bool = True,
    top_k_impl: str = "approx",
    min_new_tokens: int = 0,
    logits_processor=None,
) -> Dict[str, jnp.ndarray]:
    """Seq2seq generation: encode once, precompute cross-attention K/V, then a
    ``lax.while_loop`` decoder with a preallocated self-attention cache (replaces the
    reference's HF seq2seq ``generate``; cf. modeling_ppo.py:1242-1350 usage).

    ``decode_fn(params, tok[B,1], enc, enc_mask, dec_mask, positions, cache,
    cross_kvs) -> (logits, hidden, cache)``. Returns ``sequences`` [B, 1+N] (leading
    decoder_start token) and ``response_mask`` [B, N].
    """
    B = input_ids.shape[0]
    N = int(max_new_tokens)

    with jax.named_scope("prefill"):
        enc = encode_fn(params, input_ids, attention_mask)
        cross_kvs = cross_kv_fn(params, enc)
    cache = init_cache_fn(params, B, N + 1)

    seqs = jnp.full((B, N + 1), pad_token_id, jnp.int32)
    seqs = seqs.at[:, 0].set(decoder_start_token_id)
    dec_mask = jnp.zeros((B, N + 1), jnp.int32).at[:, 0].set(1)

    def sample_step(rng, step, logits, finished):
        rng, sub = jax.random.split(rng)
        if eos_token_id is not None and min_new_tokens > 0:
            logits = jnp.where(
                (step < min_new_tokens)
                & (jnp.arange(logits.shape[-1]) == eos_token_id)[None, :],
                -1e9,
                logits,
            )
        tok = sample_token(sub, logits, temperature, top_k, top_p, do_sample, top_k_impl)
        return rng, jnp.where(finished, pad_token_id, tok)

    def cond(state):
        step, _, _, finished, _, _, _ = state
        return jnp.logical_and(step < N, jnp.logical_not(jnp.all(finished)))

    def body(state):
        step, seqs, dec_mask, finished, cache, rng, tok = state
        logits, hidden, cache = decode_fn(
            params, tok[:, None], enc, attention_mask, dec_mask, None, cache, cross_kvs
        )
        step_logits = logits[:, -1, :]
        if logits_processor is not None:
            step_logits = logits_processor(params, hidden[:, -1, :], step_logits, tok)
        rng, new_tok = sample_step(rng, step, step_logits, finished)
        new_finished = finished
        if eos_token_id is not None:
            new_finished = jnp.logical_or(finished, new_tok == eos_token_id)
        seqs = jax.lax.dynamic_update_slice(seqs, new_tok[:, None], (0, step + 1))
        dec_mask = jax.lax.dynamic_update_slice(
            dec_mask, jnp.ones((B, 1), jnp.int32), (0, step + 1)
        )
        return step + 1, seqs, dec_mask, new_finished, cache, rng, new_tok

    tok0 = jnp.full((B,), decoder_start_token_id, jnp.int32)
    state = (
        jnp.array(0, jnp.int32), seqs, dec_mask, jnp.zeros((B,), bool), cache, rng, tok0
    )
    with jax.named_scope("decode"):
        step, seqs, dec_mask, finished, cache, rng, tok = jax.lax.while_loop(cond, body, state)

    response_mask = dec_mask[:, 1:]
    if eos_token_id is not None:
        resp = seqs[:, 1:]
        is_eos = resp == eos_token_id
        after_eos = jnp.cumsum(jnp.pad(is_eos[:, :-1], ((0, 0), (1, 0))), axis=1) > 0
        response_mask = response_mask * (1 - after_eos.astype(jnp.int32))
        written = jnp.arange(N)[None, :] < step
        response_mask = response_mask * written.astype(jnp.int32)
        seqs = jnp.concatenate(
            [seqs[:, :1], jnp.where(response_mask > 0, resp, pad_token_id)], axis=1
        )
    return {"sequences": seqs, "response_mask": response_mask}


# -- AOT audit surface (graftcheck-ir) ----------------------------------------


@register_entrypoint("decode_step", specs=("small", "xl"))
def build_decode_step(spec: str, mesh) -> EntryArtifacts:
    """The rollout decode loop as graftcheck-ir audits it: :func:`generate`
    over a ``TransformerLM`` cached decode — the same jitted callable
    ``MeshRLTrainer.generate`` builds — with replicated outputs and the
    sampling pipeline pinned by :data:`trlx_tpu.ops.sampling.AUDIT_GEN_KWARGS`.

    The ``xl`` spec is the 1.5B blueprint from the round-5 scale proof
    (GPT-2-XL dims, scanned layers): it exists to be *lowered*, deviceless,
    proving the audit scales past gpt2-small without hardware; CI compiles
    only ``small``.
    """
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.models.transformer import TransformerLM
    from trlx_tpu.ops.sampling import AUDIT_GEN_KWARGS
    from trlx_tpu.parallel.mesh import BATCH_AXES

    from jax.sharding import NamedSharding, PartitionSpec

    dims = {
        "small": dict(hidden=64, layers=2, heads=4, vocab=256, B=8, P=16, N=8,
                      scan_layers=False),
        # GPT-2-XL shapes (~1.5B params): hidden 1600 x 48 layers, 25 heads
        "xl": dict(hidden=1600, layers=48, heads=25, vocab=50257, B=8, P=128,
                   N=16, scan_layers=True),
    }[spec]
    model_config = PRESETS["gpt2"].replace(
        vocab_size=dims["vocab"], hidden_size=dims["hidden"],
        num_layers=dims["layers"], num_heads=dims["heads"],
        intermediate_size=4 * dims["hidden"],
        max_position_embeddings=max(1024, dims["P"] + dims["N"]),
        param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
        scan_layers=dims["scan_layers"],
    )
    trunk = TransformerLM(model_config)

    params_shape = jax.eval_shape(
        lambda: trunk.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2), jnp.int32)
        )
    )["params"]
    from trlx_tpu.parallel.sharding import make_param_shardings

    abs_params = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        params_shape, make_param_shardings(params_shape, mesh),
    )

    B, P, N = dims["B"], dims["P"], dims["N"]
    bsh = NamedSharding(mesh, PartitionSpec(BATCH_AXES, None))
    abs_ids = jax.ShapeDtypeStruct((B, P), jnp.int32, sharding=bsh)
    abs_rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def step_fn(params, ids, mask, positions, cache):
        logits, hidden, _, cache = trunk.apply({"params": params}, ids, mask, positions, cache)
        return logits, hidden, cache

    def decode_fn(params, ids, mask, rng):
        return generate(
            step_fn, params, lambda b, s: trunk.init_cache(b, s), ids, mask, rng,
            max_new_tokens=N, eos_token_id=0, pad_token_id=0, **AUDIT_GEN_KWARGS,
        )

    return EntryArtifacts(
        fn=decode_fn,
        args=(abs_params, abs_ids, abs_ids, abs_rng),
        donate_argnums=(),
        out_shardings=NamedSharding(mesh, PartitionSpec()),
        compute_dtype="bfloat16",
        meta=dict(batch=B, prompt=P, max_new_tokens=N,
                  hidden_size=dims["hidden"], num_layers=dims["layers"]),
    )
