"""Train the served reward model for the hh recipe.

Default mode trains the repo's JAX pairwise-ranking reward model
(`examples/summarize_rlhf/reward_model.py` — scalar head, -log sigmoid(r_c - r_r)
loss; parity: `/root/reference/examples/summarize_rlhf/reward_model/`) on graded
sentiment pairs that are NOT trivially separable: both sides mix positive and
negative words into random noise and differ only in net counts, often by a
margin of 1, and byte-truncation at seq_len hides words past the window. The
held-out pairwise accuracy therefore lands strictly inside (0.7, 0.95) — a
reward surface with real slack, so PPO against the served scalar shows
*sustained* growth instead of snapping to a saturated classifier's ceiling
(round-3 weakness: char-level DistilBERT stand-in hit held-out acc 1.0).

The scalar head is roughly monotone in net positive-word count, so the policy
can keep climbing by densifying positive words — the graded analogue of the
reference RM's "more helpful than the chosen response" headroom.

`--classifier` keeps the round-3 torch DistilBERT classifier path (used by the
serve_reward --model-dir HF-checkpoint route).

Usage: python examples/hh/train_tiny_rm.py [--out ckpts/tiny_rm_rank]
           [--steps 500] [--classifier]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, ".")

import numpy as np

from examples.sentiment_task import NEGATIVE, POSITIVE, build_corpus, lexicon_sentiment

RM_META = "rm_meta.json"
RM_PARAMS = "rm_params.msgpack"
# architecture of the tiny ranking RM (byte-level; must see the same bytes the
# byte-tokenized policy emits — a word-level vocab would map novel strings to
# UNK and the served reward would go flat)
RM_ARCH = dict(
    vocab_size=259, hidden_size=96, num_layers=3, num_heads=3,
    intermediate_size=384, max_position_embeddings=96,
)
RM_SEQ_LEN = 64

CHARSET = list("abcdefghijklmnopqrstuvwxyz0123456789")


def graded_text(rng, k_pos=None, noise=None, k_neg=None) -> "tuple[str, int]":
    """Noise words with k_pos positive and k_neg negative words shuffled in;
    returns (text, net_count). Length can exceed RM_SEQ_LEN bytes, so words can
    fall outside the model's window — irreducible ambiguity by design."""
    if noise is None:
        noise = ["".join(rng.choice(CHARSET, size=rng.integers(2, 7)))
                 for _ in range(rng.integers(1, 5))]
    if k_pos is None:
        k_pos = int(rng.integers(0, 6))
    if k_neg is None:
        k_neg = int(rng.integers(0, 5))
    words = list(noise)
    words += list(rng.choice(POSITIVE, size=k_pos)) + list(rng.choice(NEGATIVE, size=k_neg))
    rng.shuffle(words)
    return " ".join(words), k_pos - k_neg


def graded_pairs(n: int, seed: int):
    """(higher, lower, margin) pairs; margins concentrate at 1-2 (hard).

    Half the pairs share their noise words and negative count and differ ONLY
    in how many positive words they carry — these isolate count-sensitivity
    (the slope PPO climbs); the rest are independent draws (ranking across
    unrelated contexts). Shuffled word order + byte truncation keep margin-1
    pairs genuinely hard."""
    rng = np.random.default_rng(seed)
    pairs = []
    while len(pairs) < n:
        if rng.random() < 0.5:
            # matched-context pair: same noise + k_neg, different k_pos
            noise = ["".join(rng.choice(CHARSET, size=rng.integers(2, 7)))
                     for _ in range(rng.integers(1, 5))]
            k_neg = int(rng.integers(0, 3))
            ka, kb = rng.choice(6, size=2, replace=False)
            a, sa = graded_text(rng, k_pos=int(max(ka, kb)), noise=noise, k_neg=k_neg)
            b, sb = graded_text(rng, k_pos=int(min(ka, kb)), noise=noise, k_neg=k_neg)
        else:
            a, sa = graded_text(rng)
            b, sb = graded_text(rng)
            if sa == sb:
                continue
            if sa < sb:
                (a, sa), (b, sb) = (b, sb), (a, sa)
        margin = sa - sb
        # keep all margin-1/2 pairs, subsample easy wide-margin ones
        if margin > 2 and rng.random() > 0.3:
            continue
        pairs.append((a, b, margin))
    return pairs


def pairwise_accuracy(score_fn, pairs, batch: int = 64) -> float:
    correct = 0
    for i in range(0, len(pairs), batch):
        chunk = pairs[i : i + batch]
        ra = score_fn([a for a, _, _ in chunk])
        rb = score_fn([b for _, b, _ in chunk])
        correct += int(np.sum(np.asarray(ra) > np.asarray(rb)))
    return correct / len(pairs)


def _resolve_rm_tokenizer(tokenizer_path: str):
    from trlx_tpu.data.configs import TokenizerConfig
    from trlx_tpu.pipeline.tokenization import load_tokenizer

    return load_tokenizer(TokenizerConfig(tokenizer_path=tokenizer_path))


def resolve_bpe_file(tokenizer_path: str) -> str:
    """Filesystem path of a bpe:// tokenizer. Relative paths are repo-relative
    by convention (the training subprocesses run with cwd=REPO); resolving
    against the repo root keeps every consumer — content hashing, vocab-size
    reads — agreeing regardless of the caller's cwd."""
    path = tokenizer_path[len("bpe://"):]
    if not os.path.isabs(path):
        repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        path = os.path.join(repo, path)
    return path


def tokenizer_content_sha(tokenizer_path: str):
    """Content hash of a file-backed tokenizer (bpe://...), or None for
    built-ins. Cache keys must include this: the same bpe:// PATH can hold a
    retrained merge table, and an RM keyed only on the path string would pair
    stale token ids with a new policy vocabulary."""
    if not tokenizer_path.startswith("bpe://"):
        return None
    import hashlib

    try:
        with open(resolve_bpe_file(tokenizer_path), "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None


def train_ranking_rm(out_dir: str, steps: int, seed: int = 0,
                     tokenizer_path: str = "bytes") -> float:
    """Train + save the JAX ranking RM; returns held-out pairwise accuracy.

    ``tokenizer_path`` must match the policy's tokenizer family (a bpe://
    tokenizer for the BPE hh sizes): the RM has to read exactly the strings
    the policy emits."""
    from flax import serialization

    from examples.summarize_rlhf.reward_model import train_reward_model
    from trlx_tpu.models.transformer import TransformerConfig

    import jax.numpy as jnp

    tokenizer = _resolve_rm_tokenizer(tokenizer_path)
    arch = dict(RM_ARCH, vocab_size=max(RM_ARCH["vocab_size"], tokenizer.vocab_size))
    config = TransformerConfig(**arch, compute_dtype=jnp.float32, param_dtype=jnp.float32)
    train_pairs = [(a, b) for a, b, _ in graded_pairs(4000, seed=seed)]
    _, params, score_fn = train_reward_model(
        train_pairs, tokenizer, config,
        steps=steps, batch_size=32, seq_len=RM_SEQ_LEN, lr=3e-4, seed=seed,
    )

    held_out = graded_pairs(600, seed=seed + 1)
    acc = pairwise_accuracy(score_fn, held_out)
    by_margin = {}
    for m in (1, 2, 3):
        sub = [p for p in held_out if p[2] == m] if m < 3 else [p for p in held_out if p[2] >= m]
        if sub:
            by_margin[f"margin_{m}{'+' if m == 3 else ''}"] = round(
                pairwise_accuracy(score_fn, sub), 3
            )
    # sanity anchor for the PPO leg: the scalar must be monotone-ish in net
    # positive count so the policy has a slope to climb
    probe = [" ".join(["good"] * k) for k in range(0, 7)]
    probe_scores = [round(float(s), 3) for s in np.asarray(score_fn(probe))]

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, RM_PARAMS), "wb") as f:
        f.write(serialization.to_bytes(params))
    meta = {
        "kind": "ranking_rm",
        "arch": arch,
        "tokenizer": tokenizer_path,
        "tokenizer_content_sha": tokenizer_content_sha(tokenizer_path),
        "seed": seed,
        "seq_len": RM_SEQ_LEN,
        "train_steps": steps,
        "heldout_pairwise_acc": round(acc, 4),
        "heldout_acc_by_margin": by_margin,
        "positive_density_probe": probe_scores,
    }
    with open(os.path.join(out_dir, RM_META), "w") as f:
        json.dump(meta, f, indent=1)
    print(f"[rm] held-out pairwise acc {acc:.3f} by-margin {by_margin}")
    print(f"[rm] positive-density probe {probe_scores}")
    print(f"[rm] saved ranking RM to {out_dir}")
    return acc


def load_ranking_rm(model_dir: str):
    """score_fn for a saved ranking RM (used by serve_reward.py)."""
    from flax import serialization

    import jax
    import jax.numpy as jnp

    from examples.summarize_rlhf.reward_model import RewardModel
    from trlx_tpu.models.transformer import TransformerConfig
    from trlx_tpu.ops.generation import left_pad_batch

    with open(os.path.join(model_dir, RM_META)) as f:
        meta = json.load(f)
    config = TransformerConfig(**meta["arch"], compute_dtype=jnp.float32, param_dtype=jnp.float32)
    model = RewardModel(config)
    template = model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32)
    )["params"]
    with open(os.path.join(model_dir, RM_PARAMS), "rb") as f:
        params = serialization.from_bytes(template, f.read())
    tokenizer = _resolve_rm_tokenizer(meta.get("tokenizer", "bytes"))
    seq_len = int(meta["seq_len"])
    apply = jax.jit(lambda ids, mask: model.apply({"params": params}, ids, mask))

    def score_fn(texts):
        ids, mask = left_pad_batch(
            [np.asarray(tokenizer(t).input_ids[:seq_len]) for t in texts],
            tokenizer.pad_token_id, seq_len,
        )
        return [float(x) for x in np.asarray(apply(jnp.asarray(ids), jnp.asarray(mask)))]

    return score_fn


def is_ranking_rm(model_dir: str) -> bool:
    return bool(model_dir) and os.path.exists(os.path.join(model_dir, RM_META))


def build_tokenizer(tmp_vocab_path):
    """Character-level WordPiece vocab for the legacy torch classifier mode
    (every ascii letter as both a start piece and a ## continuation piece)."""
    from transformers import DistilBertTokenizer

    chars = list("abcdefghijklmnopqrstuvwxyz0123456789.,!?'")
    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]
    vocab += chars + [f"##{c}" for c in chars]
    with open(tmp_vocab_path, "w") as f:
        f.write("\n".join(vocab))
    # model_max_length must ride with the checkpoint: the serving pipeline's
    # truncation=True is a no-op without it, and char-level token counts easily
    # exceed the model's 64 position embeddings
    return DistilBertTokenizer(tmp_vocab_path, model_max_length=64)


def train_classifier_rm(out_dir: str, steps: int, batch_size: int = 32) -> float:
    """Round-3 torch DistilBERT classifier path (kept for the HF-checkpoint
    serve route); trivially separable by construction — prefer the default
    ranking mode for optimization-pressure experiments."""
    import torch
    from transformers import DistilBertConfig, DistilBertForSequenceClassification

    rng0 = np.random.default_rng(7)

    def noise_words(k):
        return ["".join(rng0.choice(CHARSET, size=rng0.integers(2, 8))) for _ in range(k)]

    def synth(positive):
        words = noise_words(int(rng0.integers(2, 6)))
        if positive:
            inserts = list(rng0.choice(POSITIVE, size=int(rng0.integers(1, 3))))
        elif rng0.random() < 0.5:
            inserts = list(rng0.choice(NEGATIVE, size=int(rng0.integers(1, 3))))
        else:
            inserts = []
        for w in inserts:
            words.insert(int(rng0.integers(len(words) + 1)), w)
        return " ".join(words)

    corpus = build_corpus(n=1000, seed=0)
    corpus += [synth(positive=i % 2 == 0) for i in range(2000)]
    labels = [1 if lexicon_sentiment([t])[0] > 0 else 0 for t in corpus]

    import tempfile

    with tempfile.TemporaryDirectory() as td:
        tok = build_tokenizer(os.path.join(td, "vocab.txt"))
    cfg = DistilBertConfig(
        vocab_size=tok.vocab_size, dim=64, n_layers=2, n_heads=2, hidden_dim=128,
        max_position_embeddings=64, num_labels=2,
        id2label={0: "NEGATIVE", 1: "POSITIVE"}, label2id={"NEGATIVE": 0, "POSITIVE": 1},
    )
    torch.manual_seed(0)
    model = DistilBertForSequenceClassification(cfg)
    opt = torch.optim.AdamW(model.parameters(), lr=5e-4)
    rng = np.random.default_rng(0)

    model.train()
    for step in range(steps):
        idx = rng.integers(len(corpus), size=batch_size)
        enc = tok([corpus[i] for i in idx], return_tensors="pt", padding=True,
                  truncation=True, max_length=64)
        y = torch.tensor([labels[i] for i in idx])
        out = model(**enc, labels=y)
        opt.zero_grad()
        out.loss.backward()
        opt.step()
        if step % 50 == 0:
            acc = (out.logits.argmax(-1) == y).float().mean().item()
            print(f"[rm] step {step} loss {out.loss.item():.4f} acc {acc:.3f}", flush=True)

    model.eval()
    test = build_corpus(n=200, seed=1)
    test_y = [1 if lexicon_sentiment([t])[0] > 0 else 0 for t in test]
    with torch.no_grad():
        enc = tok(test, return_tensors="pt", padding=True, truncation=True, max_length=64)
        pred = model(**enc).logits.argmax(-1).numpy()
    acc = float((pred == np.asarray(test_y)).mean())
    print(f"[rm] held-out acc {acc:.3f}")

    model.save_pretrained(out_dir)
    tok.save_pretrained(out_dir)
    print(f"[rm] saved to {out_dir}")
    return acc


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="ckpts/tiny_rm_rank")
    parser.add_argument("--steps", type=int, default=2000)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--tokenizer", default="bytes",
                        help='RM tokenizer (e.g. "bpe://ckpts/hh_bpe_1024.json"); '
                             "must match the policy's tokenizer family")
    parser.add_argument("--classifier", action="store_true",
                        help="legacy torch DistilBERT classifier mode")
    args = parser.parse_args()
    if args.classifier:
        train_classifier_rm(args.out, args.steps, args.batch_size)
    else:
        train_ranking_rm(args.out, args.steps, seed=args.seed,
                         tokenizer_path=args.tokenizer)


if __name__ == "__main__":
    main()
