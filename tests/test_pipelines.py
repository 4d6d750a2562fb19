"""Pipeline tests: dialogue tokenization truncation semantics (parity with reference
tests/test_pipelines.py), prompt pipeline metadata, PPO collate, minibatch slicing."""

import numpy as np
import pytest

from trlx_tpu.data.ilql_types import ILQLBatch, flatten_dataclass, unflatten_dataclass
from trlx_tpu.data.ppo_types import PPORLElement
from trlx_tpu.pipeline import PromptPipeline
from trlx_tpu.pipeline.offline_pipeline import DialogStore, tokenize_dialogue
from trlx_tpu.pipeline.ppo_pipeline import PPORolloutStorage, ppo_collate_fn
from trlx_tpu.pipeline.tokenization import CharTokenizer


@pytest.fixture
def tok():
    return CharTokenizer("abcdefgh ", padding_side="left", truncation_side="right")


def test_tokenize_dialogue_single_string(tok):
    msgs = tokenize_dialogue("abc", tok)
    # bos prompt + output ending in eos
    assert msgs[0].is_output is False
    assert msgs[-1].is_output is True
    assert msgs[-1].tokens[-1] == tok.eos_token_id


def test_tokenize_dialogue_multi_turn(tok):
    msgs = tokenize_dialogue(["ab", "cd", "ef", "gh"], tok)
    assert [m.is_output for m in msgs] == [False, True, False, True]
    assert msgs[-1].tokens[-1] == tok.eos_token_id


def test_tokenize_dialogue_right_truncation(tok):
    msgs = tokenize_dialogue(["abcd", "efgh"], tok, max_length=6)
    total = sum(len(m.tokens) for m in msgs)
    assert total <= 6
    # right truncation keeps the left side (prompt intact)
    assert msgs[0].tokens == tuple(tok.encode("abcd"))


def test_tokenize_dialogue_left_truncation():
    tok = CharTokenizer("abcdefgh ", truncation_side="left")
    msgs = tokenize_dialogue(["abcd", "efgh"], tok, max_length=6)
    total = sum(len(m.tokens) for m in msgs)
    assert total <= 6
    # left truncation keeps the right side (output + eos intact)
    assert msgs[-1].tokens[-1] == tok.eos_token_id
    # fully-truncated leading prompt is replaced by bos
    assert msgs[0].is_output is False


def test_tokenize_dialogue_right_truncation_saturated_empty_prompt():
    """A truncation edge worth pinning: on
    the RIGHT-truncation side, a fully-truncated leading prompt (only possible
    via an empty prompt string) triggers the bos re-insertion, and when the
    surviving content already saturates max_length the algorithm must trim one
    token from the LAST message (reference offline_pipeline.py:38-87 trims the
    far end of the truncation side) to make room for bos."""
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=200, deadline=None)
    @given(
        output=st.text(alphabet="abcdefgh ", min_size=1, max_size=24),
        max_length=st.integers(min_value=2, max_value=12),
    )
    def check(output, max_length):
        tok = CharTokenizer("abcdefgh ", truncation_side="right")
        msgs = tokenize_dialogue(["", output], tok, max_length=max_length)
        full_output = tuple(tok.encode(output)) + (tok.eos_token_id,)

        # bos was re-inserted for the vanished prompt, and the budget holds
        assert msgs[0].is_output is False
        assert msgs[0].tokens == (tok.bos_token_id,)
        total = sum(len(m.tokens) for m in msgs)
        assert total <= max_length

        stream = tuple(t for m in msgs[1:] for t in m.tokens)
        if len(full_output) >= max_length:
            # saturated: right truncation keeps the left end of the output and
            # gives up its LAST token to the inserted bos
            assert stream == full_output[: max_length - 1]
            assert total == max_length
        else:
            # unsaturated: output intact (eos included), bos is pure gain
            assert stream == full_output

    check()


def test_prompt_pipeline_metadata(tok):
    prompts = [{"prompt": "abc", "label": 1}, {"prompt": "de", "label": 0}]
    pipe = PromptPipeline(prompts, max_prompt_length=8, tokenizer=tok)
    loader = pipe.create_loader(batch_size=2)
    batch = next(iter(loader))
    assert [len(x) for x in batch["input_ids"]] == [3, 2]
    assert batch["label"] == [1, 0]


def test_prompt_pipeline_truncates(tok):
    pipe = PromptPipeline(["abcdefgh"], max_prompt_length=4, tokenizer=tok)
    assert len(pipe[0]["input_ids"]) == 4
    # left truncation side keeps the tail
    tok_l = CharTokenizer("abcdefgh ", truncation_side="left")
    pipe_l = PromptPipeline(["abcdefgh"], max_prompt_length=4, tokenizer=tok_l)
    assert pipe_l[0]["input_ids"] == tok_l.encode("efgh")


def test_dialog_store_masks_prompt(tok):
    dialogs = [tokenize_dialogue(["ab", "cd"], tok)]
    store = DialogStore(dialogs, tok)
    batch = next(iter(store.create_loader(1)))
    labels = batch["labels"][0]
    ids = batch["input_ids"][0]
    n_prompt = len(tok.encode("ab"))
    assert (labels[:n_prompt] == -100).all()
    assert (labels[n_prompt:] == ids[n_prompt:]).all()


def test_ppo_collate_padding():
    e1 = PPORLElement(
        np.array([1, 2, 3]), np.array([4, 5]), np.array([0.1, 0.2]),
        np.array([1.0, 2.0]), np.array([0.0, 1.0]),
    )
    e2 = PPORLElement(
        np.array([7]), np.array([8, 9, 10]), np.array([0.3, 0.4, 0.5]),
        np.array([3.0, 4.0, 5.0]), np.array([0.0, 0.0, 2.0]),
    )
    batch = ppo_collate_fn(0, [e1, e2])
    # queries left-padded
    assert batch.query_tensors.tolist() == [[1, 2, 3], [0, 0, 7]]
    assert batch.attention_mask.tolist() == [[1, 1, 1], [0, 0, 1]]
    # responses right-padded
    assert batch.response_tensors.tolist() == [[4, 5, 0], [8, 9, 10]]
    assert batch.response_mask.tolist() == [[1, 1, 0], [1, 1, 1]]
    assert batch.rewards[0].tolist() == [0.0, 1.0, 0.0]


def test_ppo_storage_loader():
    store = PPORolloutStorage(pad_token_id=0)
    elems = [
        PPORLElement(
            np.arange(1, 4), np.arange(4, 7), np.ones(3), np.ones(3), np.ones(3)
        )
        for _ in range(8)
    ]
    store.push(elems)
    assert len(store) == 8
    loader = store.create_loader(batch_size=4, shuffle=True)
    batch = next(iter(loader))
    assert batch.query_tensors.shape == (4, 3)


def test_flatten_unflatten_dataclass():
    batch = ILQLBatch(
        np.ones((2, 3)), np.ones((2, 3)), np.ones((2, 2)),
        np.ones((2, 3)), np.ones((2, 2)), np.ones((2, 3)),
    )
    leaves = flatten_dataclass(ILQLBatch)(batch)
    assert len(leaves) == 6
    rebuilt = unflatten_dataclass(ILQLBatch)(leaves)
    assert np.allclose(rebuilt.rewards, batch.rewards)


def test_char_tokenizer_roundtrip(tok):
    ids = tok.encode("abc de")
    assert tok.decode(ids) == "abc de"
    assert tok.decode([tok.eos_token_id] + ids) == "abc de"
    assert tok.decode([tok.eos_token_id], skip_special_tokens=False) == "<eos>"


def test_grounded_dsl_interpreter():
    """The grounded-program-synthesis DSL grounds rewards correctly (parity:
    reference experiments/grounded_program_synthesis/lang.py)."""
    from examples.grounded_program_synthesis.lang import Interpreter, generate_dataset

    interp = Interpreter()
    assert interp("reverse", [1, 2, 3]) == [3, 2, 1]
    assert interp("sort;take(2)", [3, 1, 2]) == [1, 2]
    assert interp("add(2);mul(3)", [0, 1]) == [6, 9]
    assert interp("frobnicate", [1]) == "ERROR"
    assert interp("take(x)", [1]) == "ERROR"

    samples, rewards = generate_dataset(n=64, seed=1)
    assert len(samples) == len(rewards) > 0
    assert set(rewards) <= {1.0, -1.0}
    assert any(r < 0 for r in rewards) and any(r > 0 for r in rewards)
    # positive samples really do reproduce their stated output
    import json as _json

    for s, r in zip(samples, rewards):
        xs = _json.loads(s.split("Input:")[1].split("Output:")[0].strip())
        out = _json.loads(s.split("Output:")[1].split("Function:")[0].strip())
        code = s.split("Function:")[1].strip()
        assert (interp(code, xs) == out) == (r > 0)


def test_bpe_tokenizer_roundtrip_and_compression(tmp_path):
    """From-scratch byte-level BPE (trlx_tpu/pipeline/bpe.py): merges learned
    on a corpus must (a) roundtrip exactly on arbitrary text, (b) compress
    corpus words into multi-byte tokens, (c) persist through save/load and the
    bpe:// tokenizer scheme (what moves the hh chain off char-level
    tokenization)."""
    from trlx_tpu.data.configs import TokenizerConfig
    from trlx_tpu.pipeline.bpe import BPETokenizer, train_bpe, train_and_save
    from trlx_tpu.pipeline.tokenization import load_tokenizer

    corpus = ["the helpful assistant gives helpful answers"] * 50 + [
        "the unhelpful assistant gives harmful answers"] * 30
    merges = train_bpe(corpus, vocab_size=300)
    assert merges, "no merges learned"
    tok = BPETokenizer(merges)

    # exact roundtrip, including text with characters unseen at training time
    for text in corpus[:1] + ["Human: zebra quartz?! 42", "  spaces  galore "]:
        assert tok.decode(tok.encode(text)) == text

    # corpus words compress below their byte length
    ids = tok.encode("the helpful assistant")
    assert len(ids) < len("the helpful assistant".encode())

    # novel words still encode (fall back to bytes), ids stay in-vocab
    ids = tok.encode("xyzzy")
    assert ids and all(0 <= i < tok.vocab_size for i in ids)

    # save -> load -> load_tokenizer(bpe://) give identical encodings
    path = str(tmp_path / "bpe.json")
    saved = train_and_save(corpus, 300, path)
    loaded = load_tokenizer(TokenizerConfig(tokenizer_path=f"bpe://{path}"))
    text = "the helpful assistant gives harmful answers"
    assert saved.encode(text) == loaded.encode(text) == BPETokenizer(merges).encode(text)
    assert loaded.vocab_size == saved.vocab_size
    assert loaded.decode(loaded.encode(text)) == text
