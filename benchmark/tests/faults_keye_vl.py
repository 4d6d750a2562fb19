"""The planted faults of learned sparse attention, beside ``faults.py``'s: the
selection replaced, in the program's own module, for every multi-token forward
(the scorer, the learner and the generator's prefill), each as a context
manager. ``readings_keye_vl.py`` adds them to the faults ``readings.py`` reads."""

import contextlib
from unittest import mock


def _replaced(keep):
    """The selection, by the kernel or by XLA, gives ``keep(query slot, key slot,
    topk)`` among the allowed keys in place of the indexer's top-k."""
    import jax.numpy as jnp

    from trlx_tpu.ops import sparse_attention as dsa

    def select(qi, ki, w, kv_valid, topk, *, kernels, interpret):
        T, S = qi.shape[1], ki.shape[1]
        Tp, Sp = dsa.padded_lengths(T, S) if kernels else (T, S)
        t, s = jnp.arange(Tp)[:, None], jnp.arange(Sp)[None, :]
        valid = jnp.pad(kv_valid, ((0, 0), (0, Sp - S)))[:, None, :] > 0
        mask = (s <= t) & (t < T) & keep(t, s, topk) & valid
        return mask.astype(jnp.int8) if kernels else mask

    return mock.patch.object(dsa, "select", select)


@contextlib.contextmanager
def selection_last_positions():
    """Each query keeps the ``topk`` latest keys, whatever the indexer scores."""
    with _replaced(lambda t, s, topk: s > t - topk):
        yield


@contextlib.contextmanager
def selection_ignored():
    """Each query attends over every causal key: dense attention."""
    with _replaced(lambda t, s, topk: s >= 0):
        yield


FAULTS = {"selection_last_positions": selection_last_positions, "selection_ignored": selection_ignored}
