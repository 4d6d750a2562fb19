"""The faults a cell's timed path can have, planted underneath the harness
(in the program's own classes, below the subclass that records), each as a
context manager. ``test_harness.py`` sees ``correct`` come out false under each
at a tiny size; ``readings.py`` reads them on the chip at the cells' sizes."""

import contextlib
from unittest import mock

import numpy as np


@contextlib.contextmanager
def state_unchanged():
    """The optimizer step returns its state as it got it."""
    import jax

    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    original = PPOTrainer.train_step

    def train_step(self, batch):
        keep = jax.tree.map(lambda x: x.copy(), (self.params, self.opt_state))
        stats = original(self, batch)
        self.params, self.opt_state = keep
        return stats

    with mock.patch.object(PPOTrainer, "train_step", train_step):
        yield


@contextlib.contextmanager
def half_batch():
    """Half of the batch left out, the mean taken over the rest: the second
    half of every batch is the first again, so no shape moves."""
    import jax

    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    original = PPOTrainer.train_step

    def train_step(self, batch):
        half = batch.query_tensors.shape[0] // 2
        batch = jax.tree.map(lambda x: np.concatenate([x[:half], x[:half]]), batch)
        return original(self, batch)

    with mock.patch.object(PPOTrainer, "train_step", train_step):
        yield


@contextlib.contextmanager
def token_altered():
    """One generated token of every row altered where it is produced."""
    from trlx_tpu.trainer.mesh_trainer import MeshRLTrainer

    original = MeshRLTrainer.generate

    def generate(self, prompts, *args, **kwargs):
        sequences, mask, pad_len = original(self, prompts, *args, **kwargs)
        sequences = np.array(sequences)
        at = pad_len + 3  # the fourth generated token
        sequences[:, at] = np.where(sequences[:, at] == 7, 8, 7)
        return sequences, mask, pad_len

    with mock.patch.object(MeshRLTrainer, "generate", generate):
        yield


def _score_outputs_altered(alter):
    """The scoring forward's three answers (policy log-probabilities, values,
    reference log-probabilities) altered where the compiled program returns them."""
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    original = PPOTrainer._get_score_fn

    def _get_score_fn(self, *args, **kwargs):
        score = original(self, *args, **kwargs)
        return lambda *inputs: alter(*score(*inputs))

    return mock.patch.object(PPOTrainer, "_get_score_fn", _get_score_fn)


@contextlib.contextmanager
def score_policy_shifted():
    """The policy's log-probabilities and values read one position late."""
    import jax.numpy as jnp

    with _score_outputs_altered(lambda lp, v, ref: (jnp.roll(lp, 1, axis=1), jnp.roll(v, 1, axis=1), ref)):
        yield


@contextlib.contextmanager
def score_reference_shifted():
    """The reference model's log-probabilities read one position late."""
    import jax.numpy as jnp

    with _score_outputs_altered(lambda lp, v, ref: (lp, v, jnp.roll(ref, 1, axis=1))):
        yield


FAULTS = {
    "state_unchanged": state_unchanged, "half_batch": half_batch, "token_altered": token_altered,
    "score_policy_shifted": score_policy_shifted, "score_reference_shifted": score_reference_shifted,
}
