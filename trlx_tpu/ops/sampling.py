"""Token sampling ops: temperature / top-k / top-p, fully jittable.

The reference delegates sampling to HF ``generate`` (CUDA) — SURVEY.md §2.4.8 calls
the KV-cache generation loop "the single most performance-critical piece to build".
These are its logit-space pieces; the loop lives in :mod:`trlx_tpu.ops.generation`.
"""


import jax
import jax.numpy as jnp

NEG_INF = -1e9


def apply_temperature(logits: jnp.ndarray, temperature: float) -> jnp.ndarray:
    return logits / jnp.maximum(temperature, 1e-6)


def exact_top_k(logits: jnp.ndarray, k: int, num_groups: int = 16):
    """Exact top-k via two-stage grouped selection: ``(values, indices)``,
    bit-identical to ``jax.lax.top_k`` (same values, same indices, same
    smallest-index tie-breaks).

    ``lax.top_k`` lowers to a full-vocab variadic sort on TPU — O(V log V)
    work per decode step for k tokens of output. Stage 1 splits the vocab
    into ``num_groups`` contiguous groups and selects each group's top-k
    (sorting runs over V/G elements); stage 2 selects the global top-k over
    the G*k survivors. Exactness: every true top-k element is in its own
    group's top-k. Tie-order: the candidate list is group-major with groups
    in index order and within-group ties already index-ascending, so the
    stage-2 positional tie-break reproduces the global smallest-index rule.
    (Bench: gpt2 decode with exact top-k 50 went 37.9k -> ~approx-path
    throughput once the full-vocab sort left the step.)
    """
    V = logits.shape[-1]
    if k >= V:  # graftcheck: noqa[JX004] — static shape/int, not traced
        return jax.lax.top_k(logits, k)
    # keep groups comfortably larger than k so stage 2 stays tiny; degenerate
    # vocabs fall back to the single-stage primitive
    G = min(num_groups, max(1, V // max(1, 2 * k)))
    if G <= 1:  # graftcheck: noqa[JX004] — static shape/int, not traced
        return jax.lax.top_k(logits, k)
    g = -(-V // G)  # ceil(V / G)
    pad = G * g - V
    if pad:  # graftcheck: noqa[JX004] — static shape/int, not traced
        # -inf pads sit at the highest indices of the last group, so any
        # genuine value (even a NEG_INF-masked one) outranks them on ties
        logits = jnp.pad(
            logits, [(0, 0)] * (logits.ndim - 1) + [(0, pad)],
            constant_values=-jnp.inf,
        )
    grouped = logits.reshape(*logits.shape[:-1], G, g)
    gv, gi = jax.lax.top_k(grouped, k)  # [..., G, k]
    gi = gi + (jnp.arange(G, dtype=gi.dtype) * g)[:, None]  # group -> vocab index
    cand_v = gv.reshape(*gv.shape[:-2], G * k)
    cand_i = gi.reshape(*gi.shape[:-2], G * k)
    vals, pos = jax.lax.top_k(cand_v, k)
    return vals, jnp.take_along_axis(cand_i, pos, axis=-1)


def apply_top_k(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Mask everything below the k-th largest logit. k<=0 disables."""
    if k <= 0 or k >= logits.shape[-1]:
        return logits
    kth = exact_top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, NEG_INF, logits)


def apply_top_p(logits: jnp.ndarray, p: float) -> jnp.ndarray:
    """Nucleus filtering: keep the smallest set of tokens with cumulative prob >= p.

    Implemented sort-free-gather style: sort descending, find cutoff, map back.
    p>=1 disables.
    """
    # p is a static Python float (bound via partial before jit), not a tracer
    if p >= 1.0:  # graftcheck: noqa[JX004]
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep tokens whose *previous* cumulative mass is < p (always keep the top-1)
    keep_sorted = jnp.concatenate(
        [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < p], axis=-1
    )
    # threshold logit = smallest kept logit
    cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(logits < cutoff, NEG_INF, logits)


def _nucleus_keep(sorted_vals: jnp.ndarray, p: float) -> jnp.ndarray:
    """Boolean keep-mask over descending-sorted logits: the smallest prefix
    whose cumulative softmax mass reaches ``p`` (top-1 always kept). The ONE
    definition of the nucleus boundary — sample_token and apply_top_k_top_p
    must share it or their distributions silently diverge."""
    probs = jax.nn.softmax(sorted_vals, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    return jnp.concatenate(
        [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < p], axis=-1
    )


def apply_top_k_top_p(logits: jnp.ndarray, k: int, p: float) -> jnp.ndarray:
    """Fused top-k -> top-p: the nucleus cutoff is computed on the k already-
    sorted top-k values instead of a full-vocab sort (``lax.top_k`` is O(V)
    selection; the sort shrinks from V to k elements — V/k less sort work per
    decode step, e.g. 50257 -> 50 for gpt2 sampling defaults).

    Equivalent to ``apply_top_p(apply_top_k(logits, k), p)`` up to float
    rounding at the cumulative-mass boundary: absent ties at the k-th value
    the two paths keep the same nucleus *mathematically*, but they normalize
    softmax over different element counts (k here vs V after masking), so a
    boundary token whose cumulative mass lands within float eps of ``p`` can
    flip between the two (observed at |cum - p| ~ 1e-6 with k=256, p=0.999).
    With ties at the k-th value this cutoff normalizes over k values instead
    of k+ties, so it can be at most one probability bin stricter — a
    measure-zero event for real-valued model logits."""
    vals = exact_top_k(logits, k)[0]  # [.., k], sorted descending
    kth = vals[..., -1:]
    kept = jnp.where(logits < kth, NEG_INF, logits)
    if p >= 1.0:
        return kept
    keep_sorted = _nucleus_keep(vals, p)
    cutoff = jnp.min(jnp.where(keep_sorted, vals, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(kept < cutoff, NEG_INF, kept)


def sample_token(
    rng: jax.Array,
    logits: jnp.ndarray,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    do_sample: bool = True,
    top_k_impl: str = "approx",
) -> jnp.ndarray:
    """Sample (or argmax) next tokens from [B, V] logits -> [B] int32.

    When ``0 < top_k < V`` the whole top-k/top-p/categorical pipeline runs in
    the k-candidate space: select (vals, indices), nucleus-mask the k sorted
    values, draw categorical over k, gather the token id. With exact selection
    this is distribution-identical to masking the full-V logits and sampling
    (softmax is invariant to the NEG_INF entries) but removes every full-vocab
    pass after the selection itself (no benchmark cell samples with top-k, so
    what the full-V path costs on the chip is not measured: ROADMAP S5).

    ``top_k_impl``: "approx" (default) selects candidates with
    ``jax.lax.approx_max_k`` — the TPU-native binned selection (per-candidate
    recall 0.95, then an exact top-k over the candidate bins); a true-top-k
    tail member is occasionally replaced by a near-tied neighbor, the same
    kind of truncation noise top-k sampling itself introduces (rollout
    logprobs are computed from the full softmax either way, exactly as the
    reference's HF top-k sampling does). "exact" uses :func:`exact_top_k`,
    the two-stage grouped selection bit-identical to ``jax.lax.top_k``.
    """
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = apply_temperature(logits.astype(jnp.float32), temperature)
    if 0 < top_k < logits.shape[-1]:
        if top_k_impl == "approx":
            vals, idx = jax.lax.approx_max_k(
                logits, top_k, recall_target=0.95, aggregate_to_topk=True
            )
        else:
            vals, idx = exact_top_k(logits, top_k)
        if top_p < 1.0:
            vals = jnp.where(_nucleus_keep(vals, top_p), vals, NEG_INF)
        choice = jax.random.categorical(rng, vals, axis=-1)
        return jnp.take_along_axis(idx, choice[..., None], axis=-1)[..., 0].astype(jnp.int32)
    logits = apply_top_p(logits, top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def count_accepted_drafts(
    sampled: jnp.ndarray, proposed: jnp.ndarray
) -> jnp.ndarray:
    """Leading-match accept count for speculative verify.

    ``proposed`` ``[B, K+1]`` is what the verify pass scored: the pending
    token followed by K draft tokens. ``sampled`` ``[B, K+1]`` is the
    per-position model output (position j is the model's choice *after*
    ``proposed[:, :j+1]``). Draft j+1 is accepted iff it equals what the
    model would have emitted at position j AND every earlier draft was
    accepted — the count is the length of the leading run of
    ``proposed[:, 1:] == sampled[:, :K]``, in ``[0, K]``. Greedy decode then
    emits ``sampled[:, :accepted+1]``, which is by construction the exact
    token sequence non-speculative decode produces one step at a time.
    """
    K = proposed.shape[1] - 1
    if K == 0:
        return jnp.zeros((proposed.shape[0],), jnp.int32)
    match = (proposed[:, 1:] == sampled[:, :K]).astype(jnp.int32)
    return jnp.sum(jnp.cumprod(match, axis=1), axis=1)


#: The sampling configuration graftcheck-ir's decode audit locks down: the
#: full temperature -> top-k -> top-p -> categorical pipeline, with the exact
#: top-k implementation so the compiled HLO is identical across backends
#: (``approx_max_k`` lowers to a TPU-specific custom call that would fork the
#: deviceless-CPU budget from the TPU artifact). Changing these changes the
#: audited decode graph — regenerate graftcheck-ir-budget.json alongside.
AUDIT_GEN_KWARGS = dict(temperature=0.7, top_k=50, top_p=0.95, top_k_impl="exact")
