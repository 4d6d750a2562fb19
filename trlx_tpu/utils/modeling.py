"""Model/numeric helpers: logprobs, whitening, distributed statistics, dict flattening.

Capability parity with `/root/reference/trlx/utils/modeling.py` (logprobs_of_labels :213,
whiten/get_global_statistics :169-207, RunningMoments :264-307, flatten_dict :220). Under
single-program SPMD (jit over a Mesh with global-view arrays) batch statistics computed with
plain ``jnp.mean``/``var`` are already *global* — XLA inserts the collectives — so the
reference's ``torch.distributed.all_reduce`` plumbing disappears. Explicit named-axis
variants are provided for use inside ``shard_map`` regions.
"""

import functools
from typing import Any, Dict, MutableMapping, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


def make_head_init(scale: float = 0.02):
    """Initializer for value/Q heads (normal, like HF head init)."""
    return jax.nn.initializers.normal(stddev=scale)


def logprobs_of_labels(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Log-probabilities of ``labels`` under ``logits``: log softmax + gather.

    Shapes: logits [..., T, V], labels [..., T] -> [..., T].
    Parity: reference utils/modeling.py:213-218 (which shifts externally; callers here
    pass already-aligned slices). Logits arrive in the model's compute dtype (bf16 on
    TPU); the logsumexp inside log_softmax must not accumulate a 32k-vocab sum in a
    7-bit mantissa, so upcast first — KL penalties are differences of these logprobs
    and bf16 rounding there directly biases the reward.
    """
    logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logprobs, labels[..., None], axis=-1)[..., 0]


def response_logprobs(
    hidden: jnp.ndarray, head, tokens: jnp.ndarray, start: int, length: int
) -> jnp.ndarray:
    """Log-probabilities of the response tokens from the final hidden states:
    ``hidden`` [B, T, d] post-norm, ``head`` the vocabulary head (rows
    [B, R, d] -> logits [B, R, V] in the compute dtype: ``TransformerLM.head``
    applied with the head's weights), ``tokens`` [B, T] -> [B, R], the
    log-probability of tokens ``start+1 … start+length`` given what precedes
    each (a PPO caller passes ``start = P - 1``, ``length = R``).

    The head is taken over the window's rows alone: the *hidden states* are
    sliced, never ``[B, T, V]`` logits. Besides the rows nobody reads, a slice
    of the logits puts a vocab-sized ``pad`` into the backward, and the TPU
    compiler failed on exactly that in the sharded (fsdp=4) PPO step at gpt2's
    vocab of 50257 ("INTERNAL: ... Bitcast cannot have different shape sizes
    of output and operand", PR 22); the backward here pads ``[B, R, d]`` to
    ``[B, T, d]``. The values are those of
    ``logprobs_of_labels(head(hidden)[:, :-1], tokens[:, 1:])[:, start:start+length]``
    row for row: same operands, same dtypes, the same float32 reduction.
    """
    rows = jax.lax.slice_in_dim(hidden, start, start + length, axis=1)
    labels = jax.lax.slice_in_dim(tokens, start + 1, start + 1 + length, axis=1)
    logits = head(rows)
    _log_head_rows(hidden.shape, length, logits.shape[-1], str(logits.dtype))
    return _logprobs_lean(logits, labels)


@functools.lru_cache(maxsize=None)
def _log_head_rows(hidden_shape, rows, vocab, dtype):
    """The window taken, once per traced shape."""
    B, T, d = hidden_shape
    row_mib = B * vocab * jnp.dtype(dtype).itemsize / 2**20  # one position's logits over the batch
    logger.info(  # graftcheck: noqa[JX003] — once per traced shape is the point
        f"vocabulary head h[{B},{T},{d}] → rows {rows} of {T}, V {vocab} {dtype}:"
        f" logits {rows * row_mib:.1f} MiB where every row would take {T * row_mib:.1f}"
    )


@jax.custom_vjp
def _logprobs_lean(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """``logprobs_of_labels`` that keeps no float32 ``[rows, V]`` tensor between
    forward and backward: the residuals are the logits as they arrive (bf16 on
    TPU) and one float32 log-sum-exp a row."""
    return _logprobs_lean_fwd(logits, labels)[0]


def _at_label(labels, shape):
    """[..., V] mask of each row's label."""
    return jax.lax.broadcasted_iota(labels.dtype, shape, len(shape) - 1) == labels[..., None]


def _logprobs_lean_fwd(logits, labels):
    x = logits.astype(jnp.float32)
    top = x.max(axis=-1, keepdims=True)
    shifted = x - top
    log_sum = jnp.log(jnp.exp(shifted).sum(axis=-1, keepdims=True))
    # the label's logit by a masked sum (one term is not zero, so it is exact),
    # which fuses into the pass that sums the exponentials; a gather does not
    picked = jnp.where(_at_label(labels, x.shape), shifted, 0.0).sum(axis=-1)
    return picked - log_sum[..., 0], (logits, top + log_sum, labels)


def _logprobs_lean_bwd(residuals, g):
    logits, lse, labels = residuals
    x = logits.astype(jnp.float32)
    # g · (onehot − softmax) in float32, cast where it enters the head's products
    d_logits = g[..., None] * (_at_label(labels, x.shape).astype(jnp.float32) - jnp.exp(x - lse))
    return d_logits.astype(logits.dtype), None


_logprobs_lean.defvjp(_logprobs_lean_fwd, _logprobs_lean_bwd)


def masked_mean(x: jnp.ndarray, mask: jnp.ndarray, axis=None) -> jnp.ndarray:
    """Mean of ``x`` over positions where ``mask`` is 1."""
    mask = mask.astype(x.dtype)
    return (x * mask).sum(axis=axis) / jnp.maximum(mask.sum(axis=axis), 1e-8)


def masked_var(x: jnp.ndarray, mask: jnp.ndarray, mean: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    if mean is None:
        mean = masked_mean(x, mask)
    return masked_mean((x - mean) ** 2, mask)


def whiten(xs: jnp.ndarray, shift_mean: bool = True, mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Whiten values to zero mean / unit variance over the *global* batch.

    Under jit-over-Mesh the reductions are global across all devices, matching the
    reference's distributed whitening (utils/modeling.py:169-185) without explicit
    collectives.
    """
    if mask is not None:
        mean = masked_mean(xs, mask)
        var = masked_var(xs, mask, mean)
    else:
        mean, var = jnp.mean(xs), jnp.var(xs)
    whitened = (xs - mean) * jax.lax.rsqrt(var + 1e-8)
    if not shift_mean:
        whitened = whitened + mean
    return whitened


def get_global_statistics(
    xs: jnp.ndarray, axis_name: Optional[str] = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(mean, var, count) of ``xs``. With ``axis_name`` set, reduces across that named
    mesh axis too (for use inside ``shard_map``); otherwise relies on global-view SPMD."""
    if axis_name is None:
        count = jnp.array(xs.size, dtype=jnp.float32)
        mean = jnp.mean(xs)
        var = jnp.var(xs)
        return mean, var, count
    # accumulate in f32 regardless of xs.dtype: a bf16 sum over a shard is
    # already wrong before the psum ever sees it (JX007 discipline)
    s = jax.lax.psum(jnp.array([xs.sum(dtype=jnp.float32), xs.size], dtype=jnp.float32), axis_name)
    global_sum, count = s[0], s[1]
    mean = global_sum / count
    sum_var = jax.lax.psum(((xs - mean) ** 2).sum(dtype=jnp.float32), axis_name)
    return mean, sum_var / count, count


class RunningMoments:
    """Streaming mean/std of reward batches with Welford-style merging.

    Parity: reference ``RunningMoments`` (utils/modeling.py:264-307). Operates on
    *global* (already gathered) arrays on the host; under a multi-controller setup
    callers gather per-host scores first (see trainer.gather_scores).
    """

    def __init__(self):
        self.mean = 0.0
        self.std = 1.0
        self.var = 1.0
        self.count = 1e-24

    def update(self, xs: np.ndarray) -> Tuple[float, float]:
        """Update from a batch; returns (batch mean, batch std)."""
        xs = np.asarray(jax.device_get(xs), dtype=np.float64).reshape(-1)
        xs_count = xs.size
        xs_mean = float(xs.mean())
        xs_var = float(xs.var())

        delta = xs_mean - self.mean
        tot_count = self.count + xs_count
        new_sum = xs_var * xs_count
        old_sum = self.var * self.count + delta**2 * self.count * xs_count / tot_count
        tot_sum = old_sum + new_sum

        self.mean += delta * xs_count / tot_count
        self.var = tot_sum / tot_count
        self.std = float(np.sqrt(self.var * tot_count / max(tot_count - 1, 1)))
        self.count = tot_count
        return xs_mean, float(np.sqrt(xs_var * xs_count / max(xs_count - 1, 1)))


def flatten_dict(d: MutableMapping, parent_key: str = "", sep: str = "/") -> Dict[str, Any]:
    """Flatten a nested dict with ``/``-joined keys (parity: utils/modeling.py:220-230)."""
    items = []
    for k, v in d.items():
        new_key = parent_key + sep + str(k) if parent_key else str(k)
        if isinstance(v, MutableMapping):
            items.extend(flatten_dict(v, new_key, sep).items())
        else:
            items.append((new_key, v))
    return dict(items)


def gather_dict(obj: Dict, grad_state=None) -> Dict:
    """Gather a metadata dict of lists from every process (parity:
    utils/modeling.py:238-259). Single-process: identity. Multi-host: uses
    ``jax.experimental.multihost_utils`` process allgather on pickled objects."""
    if jax.process_count() == 1:
        return obj
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(obj, tiled=False)
    # process_allgather returns stacked arrays per leaf; convert back to lists
    out = {}
    for k, v in gathered.items():
        out[k] = list(np.concatenate([np.atleast_1d(x) for x in v]))
    return out


def param_path_leaves(params) -> Dict[str, Any]:
    """Flatten a nested param dict to {"a/b/c": leaf} for path-predicate surgery."""
    flat = flatten_dict(params)
    return flat
