"""ILQL sentiments (parity: `/root/reference/examples/ilql_sentiments.py`): offline RL
on reward-labeled reviews.

Offline-degradation caveat: with the tiny byte-level stand-in model, the mean
eval sentiment hovers near 0 — the corpus is 50/50 positive/negative, so a
well-fit LM generates balanced text (mean 0 is the LM optimum), and the
advantage-shaped decode can only tilt toward positive WORDS once the base is
fluent enough to emit them, which a 4-layer byte model barely reaches. The
learning dynamics themselves are verified on randomwalks; with a real pretrained checkpoint
(reference: gpt2 + its tokenizer) this script runs the real task unchanged."""

import sys

sys.path.insert(0, ".")

import trlx_tpu
from examples.sentiment_task import (
    PROMPT_STUBS,
    TINY_MODEL_OVERRIDES,
    apply_offline_warm_start,
    build_corpus,
    ensure_offline_base,
    hf_task_available,
    lexicon_sentiment,
)
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.default_configs import default_ilql_config


def build_config() -> TRLConfig:
    config = default_ilql_config()
    config = config.evolve(
        train={
            "seq_length": 64, "batch_size": 32, "total_steps": 1000,
            "checkpoint_dir": "ckpts/ilql_sentiments", "tracker": "jsonl",
        },
    )
    if hf_task_available("gpt2"):  # a real local gpt2 checkpoint: the real task
        config.model.model_path = "gpt2"
        config.tokenizer.tokenizer_path = "gpt2"
    else:
        config.model.model_path = "gpt2"
        config.model.model_overrides = dict(TINY_MODEL_OVERRIDES)
        config.tokenizer.tokenizer_path = "bytes"
    return config


def main(hparams=None):
    hparams = hparams if hparams is not None else {}
    config = TRLConfig.update(build_config().to_dict(), hparams)
    # offline stand-in for starting from pretrained gpt2 (the reference's base):
    # byte-level fluency takes far longer than the RL signal does
    apply_offline_warm_start(config, hparams, ensure_offline_base)
    samples = build_corpus(512)
    rewards = lexicon_sentiment(samples)
    trlx_tpu.train(
        samples=samples,
        rewards=rewards,
        eval_prompts=PROMPT_STUBS,
        metric_fn=lambda samples, **kw: {"sentiment": lexicon_sentiment(samples)},
        config=config,
    )


if __name__ == "__main__":
    import json

    main(json.loads(sys.argv[1]) if len(sys.argv) > 1 else {})
