"""``trlx_tpu/obs/op_scopes.py``: the walk over a small canned optimized-HLO
text, and the registry and tables of a tiny ``PPOTrainer`` on the CPU."""

import json
import os
import re
from types import SimpleNamespace

import pytest

from trlx_tpu.obs import op_scopes

PROGRAMS = ("generate", "ppo_score", "ppo_train_step")

# What the walk has to tell apart, cut down from a compiled train step: a
# forward and a backward product under ``loss``, the accumulator's add fused
# onto a weight-gradient product, a fusion of copies, a ``while`` body, an
# instruction outside every scope, a kernel, and the compiler's own moves
# between memory spaces, which carry no ``op_name`` (a file of its own: HLO text's lines are long).
CANNED = os.path.join(os.path.dirname(__file__), "data", "op_scopes_train_step.hlo")


@pytest.fixture(scope="module")
def canned():
    with open(CANNED) as f:
        return op_scopes.walk(f.read())


WALKED = {
    # a fusion holding a dot follows the dot; forward and backward of one scope apart
    "%fusion.10": ("product", "loss", "forward"),
    "%head.13": ("product", "logprobs", "forward"),
    # the accumulator's add fused onto a weight-gradient product: the product's, the backward's
    "%select_add_fusion.14": ("product", "loss", "backward"),
    "%add_fusion.15": ("other", "accumulate", "forward"),
    "%copy_fusion.12": ("move", "loss", "forward"),  # a fusion of nothing but copies
    "%attn.11": ("kernel", "loss", "forward"),
    "%next": ("other", "loss", "forward"),  # a while body's instructions are found
    "%compare.20": ("other", "loss", "forward"),  # and its condition's
    "%while.21": ("container", "loss", "forward"),
    "%update.23": ("other", "optimizer", "forward"),
    "%mean.22": ("other", None, "forward"),  # under no scope of the vocabulary
    "%zeros": ("other", None, "forward"),
}


@pytest.mark.parametrize("name", sorted(WALKED))
def test_walk_over_a_canned_program(canned, name):
    kind, scope, direction = WALKED[name]
    row = canned[name]
    assert (row["kind"], row["scope"][-1] if row["scope"] else None, row["pass"]) == (kind, scope, direction), row


def test_walk_keeps_the_scopes_inside_a_fusion_and_the_whole_op_name(canned):
    row = canned["%select_add_fusion.14"]
    assert row["inside"] == ["loss", "accumulate"]  # a fusion that spans two
    assert row["source"].endswith("transpose(jvp(Model))/mlp/dot_general") and row["opcode"] == "fusion"
    assert canned["%fusion.10"]["inside"] == ["loss"]
    assert canned["%head.13"]["scope"] == ["loss", "logprobs"]  # outermost first


def test_walk_leaves_out_what_never_runs_and_the_insides_of_fusions(canned):
    assert not {"%params", "%init", "%grads", "%carry", "%i", "%out"} & set(canned)
    assert not {"%dot.1", "%dot.2", "%add.3", "%copy.5", "%add.6"} & set(canned)


def test_an_instruction_the_compiler_put_in_takes_its_users_op_name(canned):
    """``copy-start`` / ``copy-done`` of the memory-space assignment carry no
    ``op_name``: they exist for the product that reads what they move."""
    for name in ("%copy-start.1", "%copy-done.1"):
        row = canned[name]
        assert (row["kind"], row["scope"], row["via"]) == ("move", ["loss"], "%fusion.10"), row


@pytest.mark.parametrize("op_name, scopes, direction", [
    ("jit(ppo_train_step)/loss/while/body/closed_call/transpose(jvp(Model))/transformer/h_0/attn/dot_general",
     ["loss"], "backward"),
    ("jit(ppo_train_step)/loss/while/body/jvp(logprobs)/jit(response_logprobs)/dot_general",
     ["loss", "logprobs"], "forward"),
    ("jit(ppo_train_step)/loss/while/body/transpose(jvp(logprobs))/mul", ["loss", "logprobs"], "backward"),
    ("jit(generate)/decode/while/body/model/h_3/moe/moe.experts/jit(gmm)/pallas_call",
     ["decode", "moe.experts"], "forward"),
    ("jit(ppo_score)/policy_forward/model/transpose", ["policy_forward"], "forward"),  # the primitive, no wrapper
    ("jit(ppo_score)/policy_forward/model/layers_0/conv/conv/in_proj/dot_general",
     ["policy_forward", "conv"], "forward"),  # a module of the scope's name, then the scope
    ("a/loss/transpose;transpose(jvp(b))/optimizer/mul", ["loss"], "forward"),  # of names XLA joined, the first
    ("jit(ppo_train_step)/div", [], "forward"),
    ("", [], "forward"),
])
def test_scope_of_an_op_name(op_name, scopes, direction):
    assert op_scopes.scope_of(op_name) == (scopes, direction)


# ------------------------------------------- a tiny PPOTrainer on the CPU


@pytest.fixture(scope="module")
def tiny_ppo(tmp_path_factory):
    """Four optimizer steps of a tiny PPO through ``trlx_tpu.train()``; what
    ``op_scopes`` holds afterwards, before anybody asked for a table."""
    import trlx_tpu
    from tests.test_trainers import base_kwargs, dog_reward
    from trlx_tpu.data.configs import TRLConfig
    from trlx_tpu.methods.ppo import PPOConfig

    op_scopes.reset()
    kwargs = base_kwargs(tmp_path_factory.mktemp("op_scopes"), "PPOTrainer", total_steps=4)
    kwargs["train"].tracker = None
    kwargs["train"].checkpoint_interval = 10 ** 6
    config = TRLConfig(
        method=PPOConfig(
            num_rollouts=8, chunk_size=4, ppo_epochs=2, init_kl_coef=0.01, target=None,
            gen_kwargs=dict(max_new_tokens=6, min_new_tokens=6, do_sample=True, top_k=0, top_p=1.0),
        ),
        **kwargs,
    )
    trainer = trlx_tpu.train(
        reward_fn=dog_reward, prompts=["ab", "cd ef", "gh", "a b c"] * 2, eval_prompts=["ab", "cd"], config=config)
    held = {program: op_scopes.noted(program) for program in op_scopes.programs()}
    return {"trainer": trainer, "held": held, "built_after_the_run": op_scopes.built}


def test_nothing_is_built_when_nobody_asks(tiny_ppo):
    """After a run ``op_scopes`` holds the jitted functions and abstract values:
    no array, no text, no table."""
    import jax

    assert tiny_ppo["built_after_the_run"] == 0
    assert set(tiny_ppo["held"]) == set(PROGRAMS)
    for entry in tiny_ppo["held"].values():
        leaves = jax.tree.leaves((entry.args, entry.kwargs))
        assert leaves and not [leaf for leaf in leaves if isinstance(leaf, jax.Array)]
        assert any(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves)
        assert hasattr(entry.jitted, "lower")


def test_note_is_a_look_up_after_the_first_call(tiny_ppo, monkeypatch):
    entry = tiny_ppo["held"]["ppo_train_step"]
    monkeypatch.setattr(op_scopes, "_abstract", lambda x: pytest.fail("the arguments were walked again"))
    before = op_scopes.noted("ppo_train_step")
    op_scopes.note("ppo_train_step", entry.jitted, (object(),), mesh=None)
    assert op_scopes.noted("ppo_train_step") is before


@pytest.mark.parametrize("program", PROGRAMS)
def test_table_is_of_the_executable_that_ran(tiny_ppo, program):
    """``table()`` lowers and compiles from abstract values alone and finds
    the executable jax already holds: no event reaches the compile log. Every
    instruction of the compiled text's entry computation is in the table."""
    from trlx_tpu.obs import compile_log

    before = compile_log.log.total
    rows = op_scopes.table(program)
    assert compile_log.log.total == before, compile_log.log.compiles()[before:]
    assert op_scopes.built >= 1
    assert op_scopes.table(program) is rows  # cached

    text = op_scopes.compiled_text(tiny_ppo["held"][program])
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    names = re.findall(r"^\s+(?:ROOT\s+)?(%?[\w.\-]+) = (?:\([^=]*\)|\S+) ([a-z\-]+)\(", entry, re.M)
    assert len(names) > 10
    missing = [name for name, opcode in names
               if opcode not in ("parameter", "tuple", "get-tuple-element") and "%" + name.lstrip("%") not in rows]
    assert not missing, missing[:5]
    innermost = {row["scope"][-1] for row in rows.values() if row["scope"]}
    expected = {
        "generate": {"prefill", "decode"},
        "ppo_score": {"policy_forward", "reference_forward", "logprobs"},
        "ppo_train_step": {"loss", "accumulate", "logprobs", "optimizer"},
    }[program]
    assert expected <= innermost, innermost
    if program == "ppo_train_step":
        passes = {row["pass"] for row in rows.values() if "loss" in row["scope"]}
        assert passes == {"forward", "backward"}


def test_write_puts_every_program_in_one_file(tiny_ppo, tmp_path):
    path = tmp_path / "op_scopes.json"
    counts = op_scopes.write(str(path))
    assert set(counts) == set(PROGRAMS) and all(counts.values())
    written = json.loads(path.read_text())
    assert written["vocabulary"] == list(op_scopes.VOCABULARY)
    some = next(iter(written["programs"]["ppo_train_step"].values()))
    assert set(some) >= {"opcode", "kind", "scope", "pass", "source", "inside"}


def test_the_registry_is_bounded(monkeypatch):
    monkeypatch.setattr(op_scopes, "_noted", type(op_scopes._noted)())
    monkeypatch.setattr(op_scopes, "_latest", {})
    monkeypatch.setattr(op_scopes, "_tables", {})
    held = [SimpleNamespace(lower=None) for _ in range(op_scopes.CAPACITY + 3)]
    for i, fn in enumerate(held):
        op_scopes.note(f"p{i}", fn, (1,))
    assert len(op_scopes._noted) == op_scopes.CAPACITY
    assert op_scopes.noted("p0") is None and op_scopes.table("p0") is None
    assert op_scopes.noted(f"p{len(held) - 1}").args == (1,)
    op_scopes.note("wrapper", lambda *a: None, (1,))  # cannot be lowered: the health guard's ``run``
    assert op_scopes.noted("wrapper") is None
