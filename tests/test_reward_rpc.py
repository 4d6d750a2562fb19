"""Served reward-model path: HTTP server + Triton-shape client roundtrip
(parity: the reference's Triton-served reward, examples/hh/ppo_hh.py:119-139)."""

import os
import socket
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_reward_server_client_roundtrip():
    from examples.hh.reward_client import RemoteRewardClient

    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_ROOT, "examples/hh/serve_reward.py"), "--port", str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=REPO_ROOT,
    )
    try:
        assert "listening" in proc.stdout.readline()
        client = RemoteRewardClient(f"http://127.0.0.1:{port}/v2/models/reward/infer")
        outputs = [" this is a good and helpful answer", " bad terrible nothing"]
        scores = client(
            samples=["p1" + outputs[0], "p2" + outputs[1]],
            prompts=["p1", "p2"], outputs=outputs,
        )
        assert len(scores) == 2
        assert scores[0] > scores[1]  # lexicon stand-in favors helpful words

        # delta-vs-chosen: identical chosen text zeroes the reward
        delta = client(samples=outputs, outputs=outputs, chosen=outputs)
        assert delta == [0.0, 0.0]
    finally:
        proc.terminate()  # plain python http server — safe to signal (no jax/TPU)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def _save_tiny_classifier(tmp_path) -> str:
    """Save a tiny random-init HF sequence-classification checkpoint locally."""
    from transformers import DistilBertConfig, DistilBertForSequenceClassification, DistilBertTokenizer

    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]", "good", "bad", "movie", "the", "a"]
    vocab_file = tmp_path / "vocab.txt"
    vocab_file.write_text("\n".join(vocab))
    model_dir = str(tmp_path / "tiny_sentiment")
    tok = DistilBertTokenizer(str(vocab_file))
    cfg = DistilBertConfig(
        vocab_size=len(vocab), dim=32, n_layers=1, n_heads=2, hidden_dim=64,
        num_labels=2, id2label={0: "NEGATIVE", 1: "POSITIVE"},
        label2id={"NEGATIVE": 0, "POSITIVE": 1},
    )
    model = DistilBertForSequenceClassification(cfg)
    model.save_pretrained(model_dir)
    tok.save_pretrained(model_dir)
    return model_dir


def test_real_sentiment_scorer_local_checkpoint(tmp_path):
    """The real reward path (parity: reference examples/ppo_sentiments.py:21-52
    sentiment pipeline + get_positive_score) loads a *local* checkpoint and
    returns P(POSITIVE) per sample."""
    from examples.sentiment_task import load_sentiment_scorer

    model_dir = _save_tiny_classifier(tmp_path)
    score = load_sentiment_scorer(model_dir, batch_size=2)
    texts = ["the movie good", "bad bad movie", "a the movie"]
    out = score(texts)
    assert len(out) == 3 and all(0.0 <= s <= 1.0 for s in out)
    # Deterministic model: same text -> same score
    assert score(["the movie good"])[0] == out[0]

    import pytest

    with pytest.raises(FileNotFoundError):
        load_sentiment_scorer(str(tmp_path / "missing"))


def test_reward_server_serves_real_checkpoint(tmp_path):
    from examples.hh.reward_client import RemoteRewardClient

    model_dir = _save_tiny_classifier(tmp_path)
    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_ROOT, "examples/hh/serve_reward.py"),
         "--port", str(port), "--model-dir", model_dir],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=REPO_ROOT,
    )
    try:
        seen = []
        saw_checkpoint = False
        for _ in range(50):  # skip import-time log noise
            line = proc.stdout.readline()
            seen.append(line)
            saw_checkpoint |= "serving checkpoint" in line
            if "listening" in line:
                break
        else:
            raise AssertionError(f"server never came up: {seen}")
        assert saw_checkpoint, seen
        client = RemoteRewardClient(f"http://127.0.0.1:{port}/v2/models/reward/infer")
        scores = client(samples=["good movie", "bad movie"],
                        outputs=["good movie", "bad movie"])
        assert len(scores) == 2 and all(0.0 <= s <= 1.0 for s in scores)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_reward_server_serves_ranking_rm(tmp_path):
    """Round-4 path: the JAX pairwise-ranking RM (train_tiny_rm.py default mode)
    saved + detected + served; scalar rewards with the chosen-delta contract."""
    from examples.hh.reward_client import RemoteRewardClient
    from examples.hh.train_tiny_rm import is_ranking_rm, load_ranking_rm, train_ranking_rm

    rm_dir = str(tmp_path / "rank_rm")
    train_ranking_rm(rm_dir, steps=8)  # wiring test, not convergence
    assert is_ranking_rm(rm_dir) and not is_ranking_rm(str(tmp_path / "missing"))

    # in-process load path: deterministic scalar scores
    score_fn = load_ranking_rm(rm_dir)
    s1 = score_fn(["good movie", "zq mw"])
    s2 = score_fn(["good movie", "zq mw"])
    assert len(s1) == 2 and s1 == s2

    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_ROOT, "examples/hh/serve_reward.py"),
         "--port", str(port), "--model-dir", rm_dir],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, cwd=REPO_ROOT,
        # the ranking RM imports jax in the server: force CPU
        env={**os.environ, "PYTHONPATH": REPO_ROOT, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": ""},
    )
    try:
        seen = []
        saw_rm = False
        for _ in range(80):
            line = proc.stdout.readline()
            seen.append(line)
            saw_rm |= "serving ranking RM" in line
            if "listening" in line:
                break
        else:
            raise AssertionError(f"server never came up: {seen}")
        assert saw_rm, seen
        client = RemoteRewardClient(f"http://127.0.0.1:{port}/v2/models/reward/infer")
        scores = client(samples=["good movie", "zq mw"], outputs=["good movie", "zq mw"])
        assert len(scores) == 2
        assert scores == s1  # served scores match the in-process load path
        # delta-vs-chosen: identical chosen text zeroes the reward exactly
        delta = client(samples=["good movie"], outputs=["good movie"], chosen=["good movie"])
        assert delta == [0.0]
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
