"""The harness: one cell through ``trlx_tpu.train()``, a window of whole PPO
iterations, and what the comparison and the readers need of it.

The program is driven unchanged. A registered subclass of ``PPOTrainer``
installs the benchmark's weights, puts host spans (mirrored as
``TraceAnnotation``) around the calls into each layer, keeps what the timed
path produced in its first three optimizer steps, and marks the end of every
iteration at ``post_epoch_callback``. The window opens at the first mark and
closes at the first later mark at which ``seconds`` have passed; the subclass
then raises :class:`WindowClosed`, which :func:`run_cell` catches around
``trlx_tpu.train()``. A traced run profiles the window's first iteration, mark
to mark, and closes there.

Functions take their sizes as arguments, so ``benchmark/tests`` rehearses them
on the CPU at tiny widths; only ``run.py`` refuses a device that is no TPU.
"""

import contextlib
import gc
import importlib
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "out")  # benchmark/.gitignore ignores it

WORKLOAD_KEYS = {
    "config", "chips", "who", "why", "prompt_len", "new_tokens", "num_rollouts", "decode_batch_size",
    "chunk_size", "batch_size", "minibatch_size", "ppo_epochs", "ppo", "limits",
}
PPO_KEYS = {
    "lr", "b1", "b2", "eps", "weight_decay", "gamma", "lam", "cliprange", "cliprange_value",
    "vf_coef", "init_kl_coef", "cliprange_reward",
}


#: the traffic samples through the sampling path at this temperature: the same
#: program and work as at 1, and every token the greedy one but for ties, which
#: is what lets ``rollout_gap`` hold the generator against a full forward
GREEDY_TEMPERATURE = 1e-4

#: the host spans the subclass opens, mirrored as ``TraceAnnotation``
ANNOTATIONS = ("rollout", "reward", "score", "learn")


class WindowClosed(Exception):
    """Raised from ``post_epoch_callback`` to end ``learn()`` at a mark."""


# ------------------------------------------------------------------ files


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(workload, configuration) of a cell, by name."""
    cell = load_json("workloads", f"{name}.json")
    unknown = (set(cell) - WORKLOAD_KEYS) | (set(cell.get("ppo", {})) - PPO_KEYS)
    missing = (WORKLOAD_KEYS - set(cell)) | (PPO_KEYS - set(cell.get("ppo", {})))
    if unknown or missing:
        raise ValueError(f"workload {name}: unknown keys {sorted(unknown)}, missing keys {sorted(missing)}")
    config = load_json("configs", f"{cell['config']}.json")
    return cell, config


def family_of(config: Dict[str, Any]):
    """The module of the configuration's family, ``benchmark/families/<family>.py``:
    its plain reference, its operation counts, the program's names."""
    return importlib.import_module(f"benchmark.families.{config['family']}")


def load_peaks(device_kind: str) -> Dict[str, float]:
    peaks = load_json("peaks.json")
    if device_kind not in peaks:
        raise KeyError(f"device_kind {device_kind!r} is not in benchmark/peaks.json: {sorted(peaks)}")
    return peaks[device_kind]


# ----------------------------------------------------------------- inputs


def make_prompts(seed: int, count: int, prompt_len: int) -> List[str]:
    """``count`` ASCII prompts of exactly ``prompt_len`` bytes from the seed."""
    rng = np.random.default_rng(seed)
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)
    return [bytes(rng.choice(letters, prompt_len)).decode("ascii") for _ in range(count)]


def vowel_share(samples: List[str]) -> List[float]:
    """Cheap and deterministic: the share of vowels in each whole sample."""
    return [sum(ch in "aeiou" for ch in s) / max(1, len(s)) for s in samples]


def build_config(cell: Dict[str, Any], config: Dict[str, Any], seed: int, out_dir: str,
                 trainer: str = "BenchPPOTrainer"):
    from trlx_tpu.data.configs import (
        MeshConfig, ModelConfig, OptimizerConfig, SchedulerConfig, TokenizerConfig,
        TrainConfig, TRLConfig,
    )
    from trlx_tpu.methods.ppo import PPOConfig

    never = 10 ** 9
    ppo, precision, family = cell["ppo"], config["precision"], family_of(config)
    return TRLConfig(
        train=TrainConfig(
            seq_length=cell["prompt_len"] + cell["new_tokens"],
            epochs=never, total_steps=never, batch_size=cell["batch_size"],
            minibatch_size=cell["minibatch_size"],
            checkpoint_interval=never, eval_interval=never,
            checkpoint_dir=os.path.join(out_dir, "ckpts"), logging_dir=os.path.join(out_dir, "logs"),
            pipeline="PromptPipeline", trainer=trainer, tracker=None,
            seed=int(seed) % (2 ** 31),
        ),
        model=ModelConfig(
            model_path=family.MODEL_PATH,
            num_layers_unfrozen=-1,  # full reference copy
            model_overrides={**family.program_overrides(config), "attention_impl": "flash"},
        ),
        tokenizer=TokenizerConfig(tokenizer_path="bytes"),
        optimizer=OptimizerConfig(name="adamw", kwargs=dict(
            lr=ppo["lr"], betas=(ppo["b1"], ppo["b2"]), eps=ppo["eps"], weight_decay=ppo["weight_decay"])),
        # eta_min == lr: the rate is constant
        scheduler=SchedulerConfig(name="cosine_annealing", kwargs=dict(T_max=10000, eta_min=ppo["lr"])),
        method=PPOConfig(
            num_rollouts=cell["num_rollouts"], chunk_size=cell["chunk_size"],
            decode_batch_size=cell["decode_batch_size"],
            ppo_epochs=cell["ppo_epochs"], init_kl_coef=ppo["init_kl_coef"], target=None,
            gamma=ppo["gamma"], lam=ppo["lam"], cliprange=ppo["cliprange"],
            cliprange_value=ppo["cliprange_value"], vf_coef=ppo["vf_coef"],
            scale_reward=None, cliprange_reward=ppo["cliprange_reward"],
            # min == max: every response has new_tokens tokens, so no shape moves
            gen_kwargs=dict(max_new_tokens=cell["new_tokens"], min_new_tokens=cell["new_tokens"],
                            do_sample=True, top_k=0, top_p=1.0, temperature=GREEDY_TEMPERATURE),
        ),
        mesh=MeshConfig(data=1, fsdp=cell["chips"], model=1, compute_dtype=precision["compute_dtype"],
                        param_dtype=precision["param_dtype"]),
    )


# ------------------------------------------------- weights into the program


def flat_name(key: str, layer: Optional[int]) -> str:
    return key if layer is None else f"h{layer}.{key[2:]}"


def _paths(tree) -> List[Tuple[Tuple[str, ...], Any]]:
    import jax

    return [
        (tuple(str(getattr(k, "key", k)) for k in path), leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    ]


def to_program_tree(family, weights: Dict[str, Any], like, dtype):
    """The reference's weights in the program's parameter tree (``like``
    gives its structure and shapes only). Every program leaf must be covered."""
    import jax

    leaves = []
    for path, leaf in _paths(like):
        key, layer = family.leaf_name(path)
        value = weights[key] if layer is None else weights[key][layer]
        if value.shape != leaf.shape:
            raise ValueError(f"{'/'.join(path)}: program has {leaf.shape}, reference {value.shape}")
        leaves.append(value.astype(dtype))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(like), leaves)


def program_leaf_norms(family, tree) -> Dict[str, float]:
    """The norm of every program leaf, under the reference's flat names."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: jax.tree.map(lambda x: jnp.sqrt((x.astype(jnp.float32) ** 2).sum()), t))(tree)
    return {flat_name(*family.leaf_name(path)): float(v) for path, v in _paths(jax.device_get(norms))}


def _adam_mu(opt_state):
    """The first-moment tree inside an optax state, wherever it is nested."""
    if hasattr(opt_state, "mu") and hasattr(opt_state, "nu"):
        return opt_state.mu
    children = []
    if isinstance(opt_state, dict):
        children = list(opt_state.values())
    elif isinstance(opt_state, (tuple, list)):
        children = list(opt_state)
    elif hasattr(opt_state, "__dict__"):
        children = list(vars(opt_state).values())
    for child in children:
        found = _adam_mu(child)
        if found is not None:
            return found
    return None


# ------------------------------------------------------------ the session


class Session:
    """What one run keeps: marks, spans, compiles, and the first three steps."""

    CHECKED_STEPS = 3

    def __init__(self, cell, config, seed: int, seconds: float, trace: bool, t0: float, trace_dir: str):
        self.cell, self.config, self.seed = cell, config, seed
        self.family = family_of(config)
        self.seconds, self.trace, self.t0, self.trace_dir = seconds, trace, t0, trace_dir
        self.marks: List[float] = []
        self.spans: List[Tuple[str, float, float]] = []
        self.compiles: List[float] = []  # host time of every backend compile
        self.traced: Optional[Tuple[float, float]] = None
        self.batches: List[Dict[str, np.ndarray]] = []
        self.losses: List[float] = []
        self.grad_norms: Optional[Dict[str, float]] = None
        self.update_norms: Optional[Dict[str, float]] = None
        self.scores: Dict[str, float] = {}  # prompt -> score, first experience only
        self.first_experience_done = False
        self.trainer = None

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        start = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.spans.append((name, start, time.monotonic()))

    def mark(self) -> None:
        import jax

        now = time.monotonic()
        self.marks.append(now)
        if self.trace and len(self.marks) == 1:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.traced = (now, float("inf"))  # mark to mark: starting the trace is inside
        elif self.trace and len(self.marks) == 2:
            self.traced = (self.traced[0], now)
            jax.profiler.stop_trace()
            # every per-layer metric is read over the traced iteration, and
            # writing the trace has taken a minute or two of this run's time
            raise WindowClosed()
        if len(self.marks) > 1 and now - self.marks[0] >= self.seconds:
            raise WindowClosed()

    def after_checked_steps(self) -> None:
        """Called once the steps the comparison follows are recorded. A run
        goes on; ``tests/readings.py`` ends its runs here."""

    @property
    def iterations(self) -> int:
        return max(0, len(self.marks) - 1)

    @property
    def window(self) -> Tuple[float, float]:
        return self.marks[0], self.marks[-1]


_compile_sink: List[Optional[Session]] = [None]
_listener_installed = [False]


def _install_compile_listener() -> None:
    """jax has no per-listener unregister: one dispatcher, installed once,
    forwards every backend compile to the session in force."""
    if _listener_installed[0]:
        return
    import jax.monitoring

    def on_event(event: str, duration: float, **_):
        session = _compile_sink[0]
        if session is not None and event == "/jax/core/compile/backend_compile_duration":
            session.compiles.append(time.monotonic())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    _listener_installed[0] = True


def register_bench_trainer():
    """``PPOTrainer`` plus observation at its own hooks; what it trains, and
    how, is untouched. Registered by name so that ``trlx_tpu.train()`` builds
    it as it builds any trainer."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.parallel.sharding import make_param_shardings
    from trlx_tpu.trainer import _TRAINERS, register_trainer
    from trlx_tpu.trainer.ppo_trainer import PPOTrainer

    if "benchppotrainer" in _TRAINERS:
        return _TRAINERS["benchppotrainer"]

    @register_trainer
    class BenchPPOTrainer(PPOTrainer):
        session: Optional[Session] = None  # set by run_cell before train()

        def setup_model(self):
            super().setup_model()
            s = self.session
            s.trainer = self
            # the benchmark's weights in place of the program's own init
            weights = s.family.reference.init_weights(s.config, s.seed)
            shardings = make_param_shardings(self.params, self.mesh)
            install = jax.jit(
                lambda w: to_program_tree(s.family, w, self.params, self.param_dtype), out_shardings=shardings
            )
            with self.mesh:
                self.params = install(weights)
                if self.ref_params is not None:
                    self.ref_params = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(
                        self.params["transformer"]
                    )
            del weights

        def prepare_learning(self):
            super().prepare_learning()
            self.session.first_experience_done = True

        def generate(self, *args, **kwargs):
            with self.session.span("rollout"):
                return super().generate(*args, **kwargs)

        def call_reward_fn(self, **kwargs):
            with self.session.span("reward"):
                return super().call_reward_fn(**kwargs)

        def _score_and_store(self, *args, **kwargs):
            with self.session.span("score"):
                return super()._score_and_store(*args, **kwargs)

        def train_step(self, batch):
            s = self.session
            keep = len(s.batches) < s.CHECKED_STEPS
            if keep:
                s.batches.append({
                    k: np.array(getattr(batch, k)) for k in (
                        "query_tensors", "response_tensors", "logprobs", "values", "rewards",
                        "attention_mask", "response_mask")
                })
            with s.span("learn"):
                stats = super().train_step(batch)
            if keep:
                s.losses.append(float(stats["losses/total_loss"]))
            return stats

        def post_backward_callback(self):
            super().post_backward_callback()
            s = self.session
            if self.iter_count == 1:
                # the first gradient as the optimizer got it: Adam's first
                # moment after one step is (1 - b1) times it
                b1 = s.cell["ppo"]["b1"]
                mu = _adam_mu(self.opt_state)
                s.grad_norms = {k: v / (1.0 - b1) for k, v in program_leaf_norms(s.family, mu).items()}
            elif self.iter_count == s.CHECKED_STEPS:
                # the parameters' change over the first steps, before the next
                # step donates these buffers
                weights = s.family.reference.init_weights(s.config, s.seed)
                with self.mesh:
                    delta = jax.jit(
                        lambda p, w: jax.tree.map(
                            lambda a, b: a.astype(jnp.float32) - b,
                            p, to_program_tree(s.family, w, p, jnp.float32))
                    )(self.params, weights)
                s.update_norms = program_leaf_norms(s.family, delta)
                del weights, delta
                s.after_checked_steps()

        def post_epoch_callback(self, epoch):
            super().post_epoch_callback(epoch)
            self.session.mark()

    return BenchPPOTrainer


def free_program_state(session: Session) -> None:
    """Delete what the trainer holds on the device, so that the reference has
    the chip to itself."""
    import jax

    trainer, session.trainer = session.trainer, None
    if trainer is None:
        return
    for name in ("params", "opt_state", "ref_params", "_rollout_params", "frozen_branch_params"):
        for leaf in jax.tree.leaves(getattr(trainer, name, None)):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
        setattr(trainer, name, None)
    for name in ("_compiled_generate", "_score_fns", "_train_steps"):
        getattr(trainer, name, {}).clear()
    del trainer
    gc.collect()


def run_cell(cell: Dict[str, Any], config: Dict[str, Any], seed: int, seconds: float, trace: bool,
             t0: float, out_dir: str = OUT_DIR) -> Session:
    """Drive ``trlx_tpu.train()`` through set-up and the window. The returned
    session still holds the trainer: read memory, then :func:`free_program_state`."""
    import trlx_tpu

    trainer_cls = register_bench_trainer()
    _install_compile_listener()
    os.makedirs(out_dir, exist_ok=True)
    session = Session(cell, config, seed, seconds, trace, t0, os.path.join(out_dir, "trace"))
    trl_config = build_config(cell, config, seed, out_dir)
    prompts = make_prompts(seed, 2 * cell["decode_batch_size"], cell["prompt_len"])

    def reward_fn(samples, prompts, outputs, **kwargs):
        scores = vowel_share(samples)
        if not session.first_experience_done:
            session.scores.update(zip(prompts, scores))
        return scores

    trainer_cls.session = session
    _compile_sink[0] = session
    try:
        trlx_tpu.train(reward_fn=reward_fn, prompts=prompts, eval_prompts=prompts[:8], config=trl_config)
        raise RuntimeError("trlx_tpu.train() returned before the window closed")
    except WindowClosed:
        pass
    finally:
        trainer_cls.session = None
        _compile_sink[0] = None
    gc.collect()  # the exception's frames held the loop's locals
    return session


def peak_bytes() -> int:
    """``peak_bytes_in_use`` on the fullest local device; 0 where the backend
    reports none (the CPU)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.local_devices())
