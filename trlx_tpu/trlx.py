"""Public ``train()`` entry (parity: `/root/reference/trlx/trlx.py:15-143`): one
function dispatching every training mode, building the trainer and pipelines and
running ``learn()``.

Dispatch table (first matching row wins; ``config`` overrides the inferred
default when given explicitly):

==========================  =========================  ======================
 given                       mode                       default config
==========================  =========================  ======================
 ``reward_fn``               online RL (PPO/GRPO/RFT)   ``default_ppo_config``
 ``environment``             environment RL (GRPO)      ``default_grpo_config``
 ``samples`` + ``rewards``   offline RL (ILQL)          ``default_ilql_config``
 ``samples``                 supervised (SFT)           ``default_sft_config``
==========================  =========================  ======================

``environment`` is an :class:`~trlx_tpu.online.environment.Environment`
whose reward is an interaction loop (observe → generate → act → reward); a
stateless-scorable environment is adapted into a reward_fn here and flows
through the prompt-pipeline path. Fleet-harvested online training
(``train.online``; docs/online.md) also enters through the reward_fn row —
the collector feeds the trainer's experience buffer while ``learn()`` runs.
"""

from typing import Callable, Dict, Iterable, List, Optional, Tuple, Union

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.default_configs import (
    default_grpo_config,
    default_ilql_config,
    default_ppo_config,
    default_sft_config,
)
from trlx_tpu.utils import logging, set_seed
from trlx_tpu.utils.loading import get_pipeline, get_trainer

logger = logging.get_logger(__name__)


def train(
    model_path: Optional[str] = None,
    reward_fn: Optional[Callable] = None,
    dataset: Optional[Iterable[Tuple[str, float]]] = None,
    samples: Optional[List[str]] = None,
    rewards: Optional[List[float]] = None,
    prompts: Optional[List[Union[str, Dict]]] = None,
    eval_prompts: Optional[List[Union[str, Dict]]] = None,
    metric_fn: Optional[Callable] = None,
    config: Optional[TRLConfig] = None,
    stop_sequences: Optional[List[str]] = None,
    environment=None,
):
    """Dispatch & fit (see the module docstring's dispatch table). The
    reference surface is identical (model_path, reward_fn, samples, rewards,
    prompts, eval_prompts, metric_fn, config, stop_sequences) plus
    ``environment``: an :class:`~trlx_tpu.online.environment.Environment`
    scored through its stateless ``evaluate`` and trained with GRPO by
    default."""
    if reward_fn is not None and environment is not None:
        raise ValueError(
            "`reward_fn` and `environment` are mutually exclusive: an "
            "environment IS the reward source"
        )
    if config is None:
        logger.warning(
            "Passing the `config` argument implicitly is depreciated, use or adapt one of the default configs instead"
        )
        if reward_fn:
            config = default_ppo_config()
        elif environment is not None:
            config = default_grpo_config()
        elif rewards:
            config = default_ilql_config()
        else:
            config = default_sft_config()
    if environment is not None:
        # adapt the environment into the reward_fn row of the dispatch table
        from trlx_tpu.online.environment import environment_reward_fn

        reward_fn = environment_reward_fn(environment)
    if model_path:
        config.model.model_path = model_path

    # multi-process init must precede any backend-initializing jax call
    # (set_seed queries jax.process_index)
    from trlx_tpu.parallel.mesh import initialize_distributed

    initialize_distributed()
    set_seed(config.train.seed)

    if dataset is not None:
        logger.warning("the `dataset` argument is being depreciated, split it into `samples` and `rewards` instead")
        samples, rewards = dataset

    trainer_cls = get_trainer(config.train.trainer)
    trainer = trainer_cls(
        config=config,
        reward_fn=reward_fn,
        metric_fn=metric_fn,
        stop_sequences=stop_sequences,
        **config.train.trainer_kwargs,
    )

    batch_size = config.train.batch_size
    max_prompt_length = config.max_prompt_length

    # online RL (PPO / GRPO / RFT): prompts + reward_fn (an environment was
    # adapted into reward_fn above)
    if reward_fn:
        prompts = prompts or [trainer.tokenizer.bos_token] * batch_size
        if eval_prompts is None:
            eval_prompts = prompts[:batch_size]
        pipeline = get_pipeline(config.train.pipeline)(
            prompts, max_prompt_length, trainer.tokenizer
        )
        trainer.add_prompt_pipeline(pipeline)

    # offline RL (ILQL): samples + rewards
    elif samples is not None and rewards is not None:
        if len(samples) != len(rewards):
            raise ValueError(f"Number of samples {len(samples)} should match the number of rewards {len(rewards)}")
        if eval_prompts is None:
            eval_prompts = [trainer.tokenizer.bos_token] * batch_size
        trainer.make_experience(samples, rewards, config.train.seq_length)

    # supervised fine-tuning (SFT): samples only
    elif samples is not None:
        if eval_prompts is None:
            eval_prompts = [trainer.tokenizer.bos_token] * batch_size
        trainer.make_experience(samples, config.train.seq_length)

    else:
        raise ValueError(
            "One of `samples` (SFT / +`rewards` for ILQL), `reward_fn` "
            "(PPO/GRPO/RFT) or `environment` (GRPO over interaction "
            "rollouts) should be given for training"
        )

    eval_pipeline = get_pipeline(config.train.pipeline)(
        eval_prompts, max_prompt_length, trainer.tokenizer
    )
    trainer.add_eval_pipeline(eval_pipeline)

    trainer.learn()
    return trainer
