"""Bitrot guard over the example surface (parity: the reference ships 20+ example
scripts as its integration contract, SURVEY.md §2.2): every example module must
import cleanly and, where it exposes a config builder, produce a valid TRLConfig.
Full runs are covered by the slow trainer tests."""

import importlib
import os
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

MODULES = [
    "examples.architext",
    "examples.ilql_sentiments",
    "examples.ilql_sentiments_t5",
    "examples.inference",
    "examples.ppo_dense_sentiments",
    "examples.ppo_sentiments",
    "examples.ppo_sentiments_llama",
    "examples.ppo_sentiments_peft",
    "examples.ppo_sentiments_t5",
    "examples.ppo_translation_t5",
    "examples.rft_sentiments",
    "examples.sft_sentiments",
    "examples.simulacra",
    "examples.sentiment_task",
    "examples.hh.ppo_hh",
    "examples.hh.ilql_hh",
    "examples.hh.sft_hh",
    "examples.hh.reward_client",
    "examples.hh.train_tiny_rm",
    "examples.randomwalks.ppo_randomwalks",
    "examples.randomwalks.ilql_randomwalks",
    "examples.randomwalks.rft_randomwalks",
    "examples.summarize_daily_cnn.t5_summarize_daily_cnn",
    "examples.summarize_rlhf.reward_model",
    "examples.summarize_rlhf.trlx_gptj_text_summarization",
    "examples.alpaca.sft_alpaca",
    "examples.grounded_program_synthesis.train_trlx",
]


@pytest.mark.parametrize("name", MODULES)
def test_example_imports_and_builds_config(name):
    mod = importlib.import_module(name)
    builder = getattr(mod, "build_config", None) or getattr(mod, "default_config", None)
    if builder is not None:
        try:
            config = builder()
        except TypeError:
            return  # builder needs task-specific args; import is the contract here
        from trlx_tpu.data.configs import TRLConfig

        assert isinstance(config, TRLConfig)
        # round-trips through the dict form used by the argv hparams path
        assert TRLConfig.from_dict(config.to_dict()).train.seq_length == config.train.seq_length
