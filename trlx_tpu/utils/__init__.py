"""General utilities: seeding, timers, pytree helpers, optimizer/scheduler registries.

Capability parity with `/root/reference/trlx/utils/__init__.py` (seeding :44-52,
optimizer/scheduler registries :83-146, Clock :149-187, tree_map/to_device :190-208,
infinite_dataloader :240), re-expressed for JAX: optimizers/schedules resolve to optax,
device placement is handled by shardings so ``to_device`` has no analogue, and RNG is
explicit (`jax.random.PRNGKey`) with a numpy fallback for host-side shuffling.
"""

import math
import random
import subprocess
import time
from enum import Enum
from numbers import Number
from typing import Any, Dict, Iterable, Iterator, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import optax


def set_seed(seed: int) -> np.random.Generator:
    """Seed python/numpy RNGs and return a numpy Generator for host-side sampling.

    Deliberately NO per-process offset, unlike the reference's ``seed + rank``
    (utils/__init__.py:44-52): under single-controller SPMD every process must
    run the identical program on identical data — per-host divergence (in data
    order, sampled tokens, anything feeding a jit input) is undefined behavior.
    Per-sample generation diversity comes from the batched device RNG, not from
    rank offsets. JAX device RNG is explicit — trainers derive
    ``jax.random.PRNGKey(seed)`` themselves.
    """
    seed = int(seed)
    random.seed(seed)
    np.random.seed(seed % (2**32))
    return np.random.default_rng(seed)


def significant(x: Any, ndigits: int = 3) -> Any:
    """Round a number to ``ndigits`` significant figures (for stat logging)."""
    if isinstance(x, (jnp.ndarray, np.ndarray)):
        x = float(x)
    if not isinstance(x, Number) or x == 0 or not math.isfinite(x):
        return x
    return round(x, ndigits - int(math.floor(math.log10(abs(x)))) - 1)


class Clock:
    """Wall-clock timer tracking time/samples deltas between ``tick`` calls
    (parity: reference ``Clock``, utils/__init__.py:149-187)."""

    def __init__(self):
        self.start = time.time()
        self.total_time = 0.0
        self.total_samples = 0

    def tick(self, samples: int = 0) -> float:
        """Returns time (s) since last tick; accumulates samples for throughput."""
        end = time.time()
        delta = end - self.start
        self.start = end
        if samples != 0:
            self.total_time += delta
            self.total_samples += samples
        return delta

    def get_stat(self, n_samp: int = 1000, reset: bool = False) -> float:
        """Seconds per ``n_samp`` samples over the accumulated window."""
        stat = self.total_time * n_samp / max(self.total_samples, 1)
        if reset:
            self.total_time = 0.0
            self.total_samples = 0
        return stat


def tree_map_number(fn, tree: Any) -> Any:
    """Apply ``fn`` to every leaf of a nested dict/list structure (host-side)."""
    if isinstance(tree, dict):
        return {k: tree_map_number(fn, v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(tree_map_number(fn, v) for v in tree)
    return fn(tree)


def filter_non_scalars(xs: Dict) -> Dict:
    """Keep only numeric leaves of a flat stats dict (for tracker logging)."""
    ys = {}
    for k, v in xs.items():
        try:
            ys[k] = float(v)
        except (TypeError, ValueError):
            continue
    return ys


def get_git_tag() -> Tuple[str, str]:
    """(commit hash, branch) of the current repo, or placeholders outside git
    (a copy that is no repository) and where there is no ``git`` to ask."""
    try:
        output = subprocess.check_output(
            "git log --format='%h/%as' -n1".split(), stderr=subprocess.DEVNULL
        )
        branch = subprocess.check_output(
            "git rev-parse --abbrev-ref HEAD".split(), stderr=subprocess.DEVNULL
        )
        return output.decode()[1:-2], branch.decode()[:-1]
    except (subprocess.CalledProcessError, OSError):
        return "unknown", "unknown"


def infinite_loader(loader: Iterable) -> Iterator:
    """Cycle a (re-iterable) dataloader forever (parity: ``infinite_dataloader``)."""
    while True:
        yield from loader


# ----------------------------- optimizers ------------------------------------


class OptimizerName(str, Enum):
    """Supported optimizer names. The 8-bit variants use true int8
    blockwise-quantized moment states (:mod:`trlx_tpu.ops.quantized_adam`),
    the TPU-native counterpart of the reference's bitsandbytes optimizers
    (utils/__init__.py:104-123)."""

    ADAM = "adam"
    ADAMW = "adamw"
    ADAM_8BIT = "adam_8bit_bnb"
    ADAMW_8BIT = "adamw_8bit_bnb"
    SGD = "sgd"
    LION = "lion"
    ADAFACTOR = "adafactor"
    RMSPROP = "rmsprop"


def get_optimizer_class(name) -> Any:
    """Resolve an optimizer registry name to an optax constructor.

    Constructors accept ``learning_rate`` plus the usual kwargs (``betas`` is
    translated to optax's ``b1``/``b2``).
    """
    name = OptimizerName(name.lower() if isinstance(name, str) else name)

    def _adamlike(ctor):
        def make(learning_rate, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0, **kw):
            return ctor(
                learning_rate=learning_rate,
                b1=betas[0],
                b2=betas[1],
                eps=eps,
                weight_decay=weight_decay,
                **kw,
            )

        return make

    if name == OptimizerName.ADAMW:
        return _adamlike(optax.adamw)
    if name == OptimizerName.ADAMW_8BIT:
        from trlx_tpu.ops.quantized_adam import adamw_8bit

        return _adamlike(adamw_8bit)
    if name == OptimizerName.ADAM_8BIT:
        from trlx_tpu.ops.quantized_adam import adam_8bit

        def make_adam8(learning_rate, betas=(0.9, 0.999), eps=1e-8, **kw):
            kw.pop("weight_decay", None)
            return adam_8bit(learning_rate, b1=betas[0], b2=betas[1], eps=eps, **kw)

        return make_adam8
    if name == OptimizerName.ADAM:

        def make_adam(learning_rate, betas=(0.9, 0.999), eps=1e-8, **kw):
            kw.pop("weight_decay", None)
            return optax.adam(learning_rate, b1=betas[0], b2=betas[1], eps=eps, **kw)

        return make_adam
    if name == OptimizerName.SGD:

        def make_sgd(learning_rate, momentum=0.0, weight_decay=0.0, **kw):
            tx = optax.sgd(learning_rate, momentum=momentum or None, **kw)
            if weight_decay:
                tx = optax.chain(optax.add_decayed_weights(weight_decay), tx)
            return tx

        return make_sgd
    if name == OptimizerName.LION:

        def make_lion(learning_rate, betas=(0.9, 0.99), weight_decay=0.0, **kw):
            return optax.lion(learning_rate, b1=betas[0], b2=betas[1], weight_decay=weight_decay, **kw)

        return make_lion
    if name == OptimizerName.ADAFACTOR:
        return lambda learning_rate, **kw: optax.adafactor(learning_rate, **kw)
    if name == OptimizerName.RMSPROP:
        return lambda learning_rate, **kw: optax.rmsprop(learning_rate, **kw)
    raise ValueError(f"Unknown optimizer {name}")


# ----------------------------- schedulers ------------------------------------


class SchedulerName(str, Enum):
    COSINE_ANNEALING = "cosine_annealing"
    LINEAR = "linear"
    CONSTANT = "constant"
    COSINE_WARMUP = "cosine_warmup"


def get_scheduler_class(name) -> Any:
    """Resolve a scheduler registry name to an optax schedule constructor.

    Returned constructors take the same hyperparameters as the reference's torch
    schedulers (``T_max``/``eta_min`` for cosine) and produce ``optax.Schedule``s.
    """
    name = SchedulerName(name.lower() if isinstance(name, str) else name)
    if name == SchedulerName.COSINE_ANNEALING:

        def make_cosine(learning_rate, T_max, eta_min=0.0, **_):
            return optax.cosine_decay_schedule(
                init_value=learning_rate,
                decay_steps=max(int(T_max), 1),
                alpha=eta_min / learning_rate if learning_rate else 0.0,
            )

        return make_cosine
    if name == SchedulerName.LINEAR:

        def make_linear(learning_rate, total_steps, end_value=0.0, **_):
            return optax.linear_schedule(learning_rate, end_value, max(int(total_steps), 1))

        return make_linear
    if name == SchedulerName.CONSTANT:
        return lambda learning_rate, **_: optax.constant_schedule(learning_rate)
    if name == SchedulerName.COSINE_WARMUP:

        def make_warmup(learning_rate, warmup_steps, total_steps, eta_min=0.0, **_):
            return optax.warmup_cosine_decay_schedule(
                init_value=0.0,
                peak_value=learning_rate,
                warmup_steps=int(warmup_steps),
                decay_steps=max(int(total_steps), 1),
                end_value=eta_min,
            )

        return make_warmup
    raise ValueError(f"Unknown scheduler {name}")
