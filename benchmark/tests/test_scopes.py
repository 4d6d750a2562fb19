"""``readers/scopes.py`` on a handful of recorded ``(name, start, end)`` tuples
and a hand-made table: the join by time and by instruction name, the filters,
what goes to ``info``, and ``None`` on a program that keeps no tables."""

from types import SimpleNamespace

import pytest

from benchmark import harness
from benchmark.readers import program_trace, scopes

# one iteration, in ns: two train steps, a generate of one decode loop, a score.
# ``%fusion.1`` is an instruction name in all three programs.
MODULES = [
    ("jit_ppo_train_step(11)", 1000, 2000), ("jit_ppo_train_step(11)", 2600, 3600),
    ("jit_copy_params(3)", 3700, 3750),
    ("jit_generate(7)", 4000, 6000), ("jit_ppo_score(9)", 6500, 7000),
]
OPS = [
    ("%fusion.1 fusion", 1000, 1300), ("%while.2 while", 1300, 1990),  # the microbatch scan: a container
    ("%fusion.3 fusion", 1300, 1500), ("%select_add_fusion.4 fusion", 1500, 1700), ("%add_fusion.5 fusion", 1700, 1800),
    ("%copy-done.6 copy-done", 1800, 1850), ("%fusion.7 fusion", 1850, 1990),
    ("%fusion.1 fusion", 2600, 2900), ("%fusion.3 fusion", 2900, 3100), ("%select_add_fusion.4 fusion", 3100, 3300),
    ("%add_fusion.5 fusion", 3300, 3400), ("%fusion.7 fusion", 3400, 3500), ("%mystery.8 fusion", 3500, 3600),
    ("%copy.9 copy", 3700, 3750),  # a program nobody noted
    ("%fusion.1 fusion", 4000, 4400),  # prefill
    ("%while.10 while", 4400, 5900),
    ("%decode_attn.11 custom-call", 4400, 5000), ("%fusion.12 fusion", 5000, 5700), ("%gmm.13 custom-call", 5700, 5800),
    ("%sort.14 sort", 5800, 5900),
    ("%fusion.1 fusion", 6500, 7000),
]


def row(kind, scope, direction="forward", inside=None, source=None):
    return {"opcode": "fusion", "kind": kind, "scope": scope, "pass": direction,
            "source": source or "/".join(["jit(f)"] + scope + ["op"]), "inside": inside or scope[-1:]}


TABLES = {
    "ppo_train_step": {
        "%fusion.1": row("product", ["loss"]), "%fusion.3": row("product", ["loss"], "backward"),
        "%select_add_fusion.4": row("product", ["loss"], "backward", inside=["loss", "accumulate"]),
        "%add_fusion.5": row("other", ["loss", "accumulate"]), "%copy-done.6": row("move", ["loss"]),
        "%fusion.7": row("other", ["optimizer"]), "%while.2": row("container", ["loss"]),
        "%fusion.12": row("other", ["loss", "logprobs"]),  # never runs in this program's module events
    },
    "generate": {
        "%fusion.1": row("product", ["prefill"]), "%decode_attn.11": row("kernel", ["decode"]),
        "%fusion.12": row("other", ["decode"]), "%gmm.13": row("kernel", ["decode", "moe.experts"]),
        "%sort.14": row("other", ["decode", "moe.experts"]), "%while.10": row("container", ["decode"]),
    },
    "ppo_score": {"%fusion.1": row("other", [])},  # under no scope of the vocabulary
}


def context(ops=OPS, modules=MODULES, seconds=10000e-9, new_tokens=4, trace=True):
    return SimpleNamespace(
        trace={"ops": ops} if trace else None, interval=(50.0, 50.0 + seconds), cell={"new_tokens": new_tokens},
        notes={}, session=SimpleNamespace(marks=[50.0, 60.0], trace_dir=None),
        _loaded=(sorted(modules, key=lambda e: e[1]), []),
    )


@pytest.fixture(autouse=True)
def the_programs_tables(monkeypatch):
    """The module events from the context, not from a file; the tables made by hand."""
    monkeypatch.setattr(program_trace, "_program_trace", lambda ctx: ctx._loaded if ctx.trace else None)
    asked = []
    fake = SimpleNamespace(
        programs=lambda: list(TABLES), table=lambda program: asked.append(program) or TABLES[program])
    monkeypatch.setattr(scopes, "_op_scopes", lambda: fake)
    return asked


def read(ctx, name):
    spec = harness.load_json("metrics", f"{name}.json")
    module, function = spec["reader"].rsplit(".", 1)
    assert module == "scopes"
    return getattr(scopes, function)(ctx, **spec.get("args", {}))


def test_an_op_belongs_to_the_program_whose_module_event_holds_it():
    """``%fusion.1`` is the learner's forward product, the generator's prefill
    and an unscoped op of the scorer: 600, 400 and 500 ns."""
    ctx = context()
    by_scope = scopes._joined(ctx) and ctx.notes["device_s_by_scope"]
    assert by_scope["ppo_train_step"]["loss:forward"] == pytest.approx((600 + 50) * 1e-9)  # with the copy-done
    assert by_scope["generate"]["prefill"] == pytest.approx(400e-9)
    assert by_scope["ppo_score"] == {"unscoped": pytest.approx(500e-9)}


def test_the_learners_split_is_a_partition():
    ctx = context()
    head, accumulate, optimizer = (
        read(ctx, f"learn_{name}_device_pct") for name in ("head", "accumulate", "optimizer"))
    assert head is None  # nothing of this learner runs under ``logprobs``
    assert accumulate == pytest.approx(100 * 200 / 10000)  # the add alone: the fused one follows its product
    assert optimizer == pytest.approx(100 * 240 / 10000)
    split = ctx.notes["device_s_by_scope"]["ppo_train_step"]
    assert split == {
        "loss:forward": pytest.approx(650e-9), "loss:backward": pytest.approx(800e-9),
        "loss/accumulate:forward": pytest.approx(200e-9), "optimizer": pytest.approx(240e-9),
        "unknown": pytest.approx(100e-9)}
    # the containers left out, every other op second inside the program's module events is in the split
    assert sum(split.values()) == pytest.approx((990 - 0 + 1000) * 1e-9)
    assert ctx.notes["device_s_mixed_fusions"] == {"ppo_train_step": {"accumulate+loss": pytest.approx(400e-9)}}
    assert ctx.notes["device_named_pct_by_program"]["ppo_train_step"] == pytest.approx(100 * 1890 / 1990)


def test_decode_outside_the_kernel_per_step():
    """Under ``decode`` however deep, the kernel's events excluded: 700 + 100 +
    100 ns over one call of three steps."""
    assert read(context(), "decode_outside_kernel_ms") == pytest.approx(900 / 1e6 / 3)
    assert read(context(new_tokens=1), "decode_outside_kernel_ms") is None  # no step


def test_around_the_grouped_products_and_the_moves():
    ctx = context()
    assert read(ctx, "moe_around_products_device_pct") == pytest.approx(100 * 100 / 10000)  # the sort, not %gmm
    assert read(ctx, "data_movement_device_pct") == pytest.approx(100 * 50 / 10000)
    assert scopes.device_share(ctx, scopes=["moe.experts"]) == pytest.approx(100 * 200 / 10000)
    kernels = scopes.device_share(ctx, program="generate", kinds=["kernel"], exclude="^%gmm")
    assert kernels == pytest.approx(100 * 600 / 10000)
    assert scopes.device_share(ctx, program="ppo_score", scopes=["loss"]) is None  # no matching event


def test_scoped_share_counts_what_is_in_a_program_known_and_scoped():
    ctx = context()
    ops_s = sum(e - s for n, s, e in OPS if not n.endswith(" while"))
    outside = 50 + 500 + 100  # jit_copy_params, the scorer's unscoped op, the unknown name
    assert read(ctx, "scoped_device_pct") == pytest.approx(100 * (ops_s - outside) / ops_s)
    named = ctx.notes["device_ops_named"]
    assert named[0][:5] == ["%fusion.12 fusion", pytest.approx(700e-9), "generate", "decode", "other"]
    assert ["%copy.9 copy", pytest.approx(50e-9), None, "unknown", None, None] in named
    assert [n for n in named if n[0] == "%fusion.1 fusion" and n[2] == "ppo_score"][0][3] == "unscoped"
    assert set(ctx.notes["op_scopes_table_s"]) == set(TABLES) and ctx.notes["op_scopes_rows"]["generate"] == 6


def test_the_tables_are_asked_for_once_a_run(the_programs_tables):
    ctx = context()
    for name in ("learn_accumulate_device_pct", "data_movement_device_pct", "scoped_device_pct"):
        read(ctx, name)
    assert sorted(the_programs_tables) == sorted(TABLES)


@pytest.mark.parametrize("why", ["no op_scopes", "not traced", "nothing noted", "no module event"])
def test_none_where_there_is_nothing_to_read(monkeypatch, why):
    ctx = context(trace=why != "not traced", modules=[] if why == "no module event" else MODULES)
    if why == "no op_scopes":  # any commit before the program kept the table
        monkeypatch.setattr(scopes, "_op_scopes", lambda: None)
    if why == "nothing noted":
        monkeypatch.setattr(scopes, "_op_scopes", lambda: SimpleNamespace(programs=lambda: [], table=lambda p: None))
    for name in ("learn_head_device_pct", "learn_accumulate_device_pct", "learn_optimizer_device_pct",
                 "decode_outside_kernel_ms", "moe_around_products_device_pct", "data_movement_device_pct",
                 "scoped_device_pct"):
        assert read(ctx, name) is None
    assert "device_s_by_scope" not in ctx.notes
