"""SFT on the chosen responses of helpful/harmless dialogues (parity:
`/root/reference/examples/hh/sft_hh.py`): supervised fine-tuning on
prompt+chosen, with the reward model (or its lexicon stand-in) as the eval
metric. The usual first stage before ppo_hh/ilql_hh."""

import os
import sys

sys.path.insert(0, ".")

import trlx_tpu
from examples.hh.ppo_hh import CHOSEN, PROMPTS
from examples.sentiment_task import TINY_MODEL_OVERRIDES, lexicon_sentiment
from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.default_configs import default_sft_config


def build_config() -> TRLConfig:
    config = default_sft_config()
    config = config.evolve(
        train={
            "seq_length": 96, "batch_size": 16, "total_steps": 600,
            "eval_interval": 100, "checkpoint_interval": 100000,
            "checkpoint_dir": "ckpts/sft_hh", "tracker": "jsonl",
        },
        method={"gen_kwargs": {"max_new_tokens": 32, "top_k": 20, "top_p": 1.0,
                               "do_sample": True}},
    )
    model_path = os.environ.get("HH_MODEL", "gpt2")
    config.model.model_path = model_path
    if not os.path.isdir(model_path):
        config.model.model_overrides = dict(TINY_MODEL_OVERRIDES)
        config.tokenizer.tokenizer_path = "bytes"
    else:
        config.tokenizer.tokenizer_path = model_path
    return config


def hh_base_corpus(n_synth: int = 480, seed: int = 0):
    """SFT corpus for the offline hh base policy: prompt+chosen, prompt+rejected,
    and synthetic assistant replies mixing filler with BOTH sentiment polarities.
    The base must speak the full vocabulary (positive AND negative words) so the
    PPO stage's reward can steer it — an SFT base that only parrots the 4 chosen
    replies gives exploration nothing to vary (round-4 flat-curve lesson)."""
    import numpy as np

    from examples.hh.ppo_hh import REJECTED
    from examples.sentiment_task import NEGATIVE, POSITIVE

    rng = np.random.default_rng(seed)
    filler = ["try", "with", "and", "then", "also", "maybe", "the", "a", "more",
              "less", "daily", "simple", "plan", "rest", "focus", "start", "keep"]
    vocab = list(POSITIVE) + list(NEGATIVE) + filler * 2
    base = [p + c for p, c in zip(PROMPTS, CHOSEN)]
    base += [p + r for p, r in zip(PROMPTS, REJECTED)]
    synth = []
    for _ in range(n_synth):
        prompt = PROMPTS[int(rng.integers(len(PROMPTS)))]
        words = list(rng.choice(vocab, size=int(rng.integers(4, 9))))
        synth.append(prompt + " " + " ".join(words) + ".")
    return base * 8 + synth


# Policy/base sizes for the hh chain. "tiny" is the round-4 byte-level
# recipe; the BPE sizes move the chain off char-level: the
# tokenizer is a from-scratch byte-level BPE trained on the hh corpus
# (trlx_tpu/pipeline/bpe.py), "small" is what one CPU core converges inside a
# round, "125m" is gpt2-124M-shaped (12x768) for the TPU-queue variant.
HH_SIZES = {
    "tiny": dict(overrides=dict(TINY_MODEL_OVERRIDES), bpe=None, seq_length=96),
    "small": dict(
        overrides=dict(hidden_size=256, num_layers=6, num_heads=4,
                       intermediate_size=1024, max_position_embeddings=128),
        bpe=1024, seq_length=48,
    ),
    "125m": dict(
        overrides=dict(hidden_size=768, num_layers=12, num_heads=12,
                       intermediate_size=3072, max_position_embeddings=256),
        bpe=2048, seq_length=64,
    ),
}


def ensure_hh_bpe(vocab_size: int, seed: int = 0) -> str:
    """Train (once) and cache the hh-corpus BPE tokenizer; returns bpe://path.
    The cache key carries the corpus seed: merges from a different corpus draw
    are different token ids."""
    import json as _json

    path = f"ckpts/hh_bpe_{vocab_size}_s{seed}.json"
    if os.path.exists(path):
        try:
            with open(path) as f:
                if _json.load(f).get("vocab_size"):
                    return f"bpe://{path}"
        except (OSError, _json.JSONDecodeError):
            pass
    from trlx_tpu.pipeline.bpe import train_and_save

    train_and_save(hh_base_corpus(seed=seed), vocab_size, path)
    return f"bpe://{path}"


def ensure_hh_base(base_dir: str = "ckpts/hh_base_r4", steps: int = 400,
                   seed: int = 0, size: str = "tiny") -> str:
    """Cached offline SFT base for the hh recipe (fingerprinted like the
    sentiment warm starts); returns an HF-export dir for HH_MODEL."""
    from examples.sentiment_task import _sft_offline_base

    spec = HH_SIZES[size]
    tokenizer_path = "bytes"
    fingerprint_extra = ""
    overrides = dict(spec["overrides"])
    if spec["bpe"]:
        import json as _json

        # key the SFT cache on the MERGE CONTENT, not just the path string: a
        # retrained tokenizer file means different token ids for the same text.
        # One hash rule shared with the RM cache (train_tiny_rm) so the SFT and
        # RM staleness keys can never desynchronize.
        from examples.hh.train_tiny_rm import resolve_bpe_file, tokenizer_content_sha

        tokenizer_path = ensure_hh_bpe(spec["bpe"], seed=seed)
        base_dir = f"{base_dir}_{size}"
        fingerprint_extra = tokenizer_content_sha(tokenizer_path) or ""
        with open(resolve_bpe_file(tokenizer_path)) as f:
            overrides["vocab_size"] = _json.load(f)["vocab_size"]
    return _sft_offline_base(
        base_dir, "gpt2", "causal", overrides,
        hh_base_corpus(seed=seed), steps, seed, seq_length=spec["seq_length"],
        tokenizer_path=tokenizer_path, fingerprint_extra=fingerprint_extra,
    )


def main(hparams=None):
    hparams = hparams if hparams is not None else {}
    config = TRLConfig.update(build_config().to_dict(), hparams)
    samples = [p + c for p, c in zip(PROMPTS, CHOSEN)] * 32
    trlx_tpu.train(
        samples=samples,
        eval_prompts=PROMPTS,
        metric_fn=lambda samples, **kw: {"reward": lexicon_sentiment(samples)},
        config=config,
        stop_sequences=["Human:", "human:", "Assistant:", "assistant:"],
    )


if __name__ == "__main__":
    import json

    main(json.loads(sys.argv[1]) if len(sys.argv) > 1 else {})
