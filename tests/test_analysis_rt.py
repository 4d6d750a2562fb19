"""graftcheck-rt (trlx_tpu/analysis/rt): SH001-SH004 positive and negative
fixtures (bucketing ladders, weak-type floats and float fields, unstable
statics, data-dependent shapes), noqa/baseline round-trips, the CompileWatcher
warmup-vs-steady attribution contract, budget compare/write semantics, the
seeded shape_churn self-test, the unified --suite driver, and the repo-level
SH-clean contract.

Static fixtures run through the public ``run()`` entry with SH selects so the
whole pipeline — parse, call graph, rule replay, noqa — is exercised, isolated
from the JX/TH/CC rules the same snippets would also trip. Runtime fixtures
drive a real ``jax.jit`` cache on CPU; the full probe subprocess gates are
slow-marked (ci.sh runs them as their own leg).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from trlx_tpu.analysis import RULES, run
from trlx_tpu.analysis.cli import SUITE_SELECTS, main as cli_main
from trlx_tpu.analysis.core import resolve_select
from trlx_tpu.analysis.rt import budget as budget_mod
from trlx_tpu.analysis.rt import contracts, seeds
from trlx_tpu.analysis.rt import watcher as watcher_mod
from trlx_tpu.analysis.rt.cli import main as rt_cli_main
from trlx_tpu.analysis.rt.watcher import CompileWatcher

pytestmark = pytest.mark.analysis_rt

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def check_snippet(tmp_path, source, name="snippet.py", select=("SH",)):
    f = tmp_path / name
    f.write_text(textwrap.dedent(source))
    return run([str(f)], select=list(select) if select else None)


def rule_ids(findings):
    return [f.rule for f in findings]


# ----------------------------------------------------------------- registry


def test_sh_rules_registered():
    assert {"SH001", "SH002", "SH003", "SH004"} <= set(RULES)
    for rid in ("SH001", "SH002", "SH003", "SH004"):
        assert RULES[rid].summary


def test_select_family_prefix():
    assert [r.id for r in resolve_select(["SH"])] == [
        "SH001", "SH002", "SH003", "SH004",
    ]


def test_shape_contracts_declare_the_quantizers():
    # SH001's sanction list comes from the contracts registry, not the rule
    assert "quantize_stream_response" in contracts.quantizer_names()
    assert "pad_to_bucket" in contracts.quantizer_names()
    assert "check_stream_bucket_family" in contracts.guard_names()
    assert contracts.get("stream_score_ladder").max_shapes == 4


# ------------------------------------------------------------------- SH001


SH001_POSITIVE = """
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: x * 2)

    def feed(items):
        n = len(items)
        buf = jnp.zeros((n, 4), jnp.float32)
        return step(buf)
    """


def test_sh001_len_derived_shape_positive(tmp_path):
    findings = check_snippet(tmp_path, SH001_POSITIVE, select=("SH001",))
    assert rule_ids(findings) == ["SH001"]
    assert "bucketing ladder" in findings[0].message


def test_sh001_quantized_through_ladder_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        from trlx_tpu.ops.generation import pad_to_bucket

        step = jax.jit(lambda x: x * 2)

        def feed(items):
            n = pad_to_bucket(len(items), (8, 16, 32))
            buf = jnp.zeros((n, 4), jnp.float32)
            return step(buf)
        """,
        select=("SH001",),
    )
    assert findings == []


def test_sh001_quantized_through_a_capped_ladder_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        from trlx_tpu.ops.generation import pad_to_bucket

        step = jax.jit(lambda x: x * 2)

        def feed(items, longest_allowed):
            n = pad_to_bucket(len(items), (8, 16, 32), cap=longest_allowed)
            buf = jnp.zeros((n, 4), jnp.float32)
            return step(buf)
        """,
        select=("SH001",),
    )
    assert findings == []


def test_sh001_raw_len_inline_in_ctor(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        step = jax.jit(lambda x: x + 1)

        def feed(items):
            return step(jnp.zeros((len(items),), jnp.float32))
        """,
        select=("SH001",),
    )
    assert rule_ids(findings) == ["SH001"]
    assert "raw len()" in findings[0].message


def test_sh001_fixed_shape_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        step = jax.jit(lambda x: x + 1)

        def feed():
            return step(jnp.zeros((8, 4), jnp.float32))
        """,
        select=("SH001",),
    )
    assert findings == []


# ------------------------------------------------------------------- SH002


def test_sh002_float_literal_operand_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax

        step = jax.jit(lambda x, c: x * c)

        def go(x):
            return step(x, 0.5)
        """,
        select=("SH002",),
    )
    assert rule_ids(findings) == ["SH002"]
    assert "weak_type" in findings[0].message


def test_sh002_float_name_and_conversion_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax

        step = jax.jit(lambda x, c: x * c)

        def go(x, raw):
            coef = 0.25
            a = step(x, coef)
            return step(a, float(raw))
        """,
        select=("SH002",),
    )
    assert rule_ids(findings) == ["SH002", "SH002"]


def test_sh002_asarray_pinned_operand_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        step = jax.jit(lambda x, c: x * c)

        def go(x):
            return step(x, jnp.asarray(0.5, x.dtype))
        """,
        select=("SH002",),
    )
    assert findings == []


def test_sh002_static_marked_float_is_sh003_jurisdiction(tmp_path):
    # a float deliberately marked static is SH003's hazard, not weak-type drift
    findings = check_snippet(
        tmp_path,
        """
        import jax

        step = jax.jit(lambda x, c: x * c, static_argnums=(1,))

        def go(x):
            return step(x, 0.5)
        """,
        select=("SH002",),
    )
    assert findings == []


def test_sh002_float_field_in_traced_binop_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        from dataclasses import dataclass

        import jax.numpy as jnp

        @dataclass
        class Cfg:
            scale: float = 0.5

            def loss(self, x):
                y = jnp.sum(x)
                return y * self.scale
        """,
        select=("SH002",),
    )
    assert rule_ids(findings) == ["SH002"]
    assert "self.scale" in findings[0].message


def test_sh002_float_field_in_array_call_args_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        from dataclasses import dataclass

        import jax.numpy as jnp

        @dataclass
        class Cfg:
            cap: float = 1.0

            def loss(self, x):
                return jnp.clip(x, -self.cap, self.cap)
        """,
        select=("SH002",),
    )
    # both uses sit on one line: deduped to one finding per (line, field)
    assert rule_ids(findings) == ["SH002"]


def test_sh002_float_field_inherited_across_classes(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        from dataclasses import dataclass

        import jax.numpy as jnp

        @dataclass
        class Base:
            coef: float = 1.0

        @dataclass
        class Child(Base):
            def loss(self, x):
                return jnp.sum(x) * self.coef
        """,
        select=("SH002",),
    )
    assert rule_ids(findings) == ["SH002"]
    assert "self.coef" in findings[0].message


def test_sh002_pinned_float_field_is_clean(tmp_path):
    # the recommended fix must not re-flag: asarray pin, then use the pin
    findings = check_snippet(
        tmp_path,
        """
        from dataclasses import dataclass

        import jax.numpy as jnp

        @dataclass
        class Cfg:
            cap: float = 1.0

            def loss(self, x):
                cap = jnp.asarray(self.cap, x.dtype)
                return jnp.clip(x, -cap, cap)
        """,
        select=("SH002",),
    )
    assert findings == []


def test_sh002_inline_pin_inside_bigger_call_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        from dataclasses import dataclass

        import jax.numpy as jnp

        @dataclass
        class Cfg:
            cap: float = 1.0

            def loss(self, x):
                return jnp.minimum(x, jnp.asarray(self.cap, x.dtype))
        """,
        select=("SH002",),
    )
    assert findings == []


def test_sh002_non_float_fields_are_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        from dataclasses import dataclass

        import jax.numpy as jnp

        @dataclass
        class Cfg:
            n: int = 4
            name: str = "x"

            def loss(self, x):
                return jnp.sum(x) * self.n
        """,
        select=("SH002",),
    )
    assert findings == []


def test_sh002_scalar_ratio_of_float_fields_positive(tmp_path):
    # the LoRA idiom: a pure-scalar expression over float fields against a
    # matmul side (this exact in-tree case is baselined as weak-type by design)
    findings = check_snippet(
        tmp_path,
        """
        from dataclasses import dataclass

        @dataclass
        class Adapter:
            alpha: float = 16.0
            r: float = 8.0

            def apply(self, x, a, b):
                return (x @ a) @ b * (self.alpha / self.r)
        """,
        select=("SH002",),
    )
    assert rule_ids(findings) == ["SH002"]


# ------------------------------------------------------------------- SH003


def test_sh003_static_float_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax

        step = jax.jit(lambda x, c: x * int(c), static_argnums=(1,))

        def go(x):
            return step(x, 0.5)
        """,
        select=("SH003",),
    )
    assert rule_ids(findings) == ["SH003"]
    assert "every distinct value" in findings[0].message


def test_sh003_static_dict_and_lambda_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax

        step = jax.jit(lambda x, opts: x, static_argnums=(1,))
        apply = jax.jit(lambda x, fn: fn(x), static_argnames=("fn",))

        def go(x):
            a = step(x, {"k": 2})
            return apply(a, fn=lambda v: v * 2)
        """,
        select=("SH003",),
    )
    assert sorted(rule_ids(findings)) == ["SH003", "SH003"]
    msgs = " ".join(f.message for f in findings)
    assert "unhashable" in msgs and "fresh lambda" in msgs


def test_sh003_stable_int_static_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax

        step = jax.jit(lambda x, n: x[:n], static_argnums=(1,))

        def go(x):
            return step(x, 8)
        """,
        select=("SH003",),
    )
    assert findings == []


# ------------------------------------------------------------------- SH004


def test_sh004_nonzero_under_jit_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.nonzero(x > 0)
        """,
        select=("SH004",),
    )
    assert rule_ids(findings) == ["SH004"]


def test_sh004_nonzero_with_size_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.nonzero(x > 0, size=4, fill_value=0)
        """,
        select=("SH004",),
    )
    assert findings == []


def test_sh004_single_arg_where_positive_three_arg_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            good = jnp.where(x > 0, x, 0.0)
            return jnp.where(good > 1)
        """,
        select=("SH004",),
    )
    assert rule_ids(findings) == ["SH004"]
    assert "single-argument" in findings[0].message


def test_sh004_boolean_mask_indexing_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax

        @jax.jit
        def f(x):
            mask = x > 0
            return x[mask]
        """,
        select=("SH004",),
    )
    assert rule_ids(findings) == ["SH004"]
    assert "boolean-mask" in findings[0].message


def test_sh004_traced_reduction_slice_bound_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, m):
            return x[: jnp.sum(m)]
        """,
        select=("SH004",),
    )
    assert rule_ids(findings) == ["SH004"]
    assert "slice bound" in findings[0].message


def test_sh004_untraced_body_is_out_of_scope(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import numpy as np

        def host_side(x):
            return np.nonzero(x > 0)
        """,
        select=("SH004",),
    )
    assert findings == []


# ------------------------------------------------- noqa / baseline plumbing


def test_sh_noqa_suppresses(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import jax

        step = jax.jit(lambda x, c: x * c)

        def go(x):
            return step(x, 0.5)  # graftcheck: noqa[SH002]
        """,
        select=("SH002",),
    )
    assert findings == []


def test_sh_baseline_round_trip(tmp_path, monkeypatch):
    f = tmp_path / "seam.py"
    f.write_text(
        textwrap.dedent(
            """
            import jax

            step = jax.jit(lambda x, c: x * c)

            def go(x):
                return step(x, 0.5)
            """
        )
    )
    bl = tmp_path / "baseline.txt"
    argv = [str(f), "--select", "SH", "--baseline", str(bl)]
    assert cli_main(argv) == 1
    assert cli_main(argv + ["--write-baseline"]) == 0
    assert cli_main(argv) == 0  # baselined: no longer a new finding


# -------------------------------------------------------------- the watcher


def test_watcher_warmup_vs_steady_tracked_counts():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x + 1

    with CompileWatcher() as w:
        w.track("e", f)
        with w.attributed("e"):
            jax.block_until_ready(f(jnp.zeros((2,), jnp.float32)))
        w.mark_steady("e")
        # same shape: cache hit, no steady compile
        with w.attributed("e"):
            jax.block_until_ready(f(jnp.ones((2,), jnp.float32)))
        led = w.ledger()["e"]
        assert led["warmup_compiles"] == 1
        assert led["steady_compiles"] == 0
        # new shape after mark_steady: exactly the violation the gate exists for
        with w.attributed("e"):
            jax.block_until_ready(f(jnp.zeros((3,), jnp.float32)))
        assert w.steady_compiles("e") == 1


def test_watcher_event_attribution_and_unattributed_bucket():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def g(x):
        return x * 2

    @jax.jit
    def h(x):
        return x * 3

    with CompileWatcher() as w:
        with w.attributed("scoped"):
            jax.block_until_ready(g(jnp.zeros((4,), jnp.float32)))
        # a compile outside any attribution scope lands in __unattributed__
        jax.block_until_ready(h(jnp.zeros((4,), jnp.float32)))
        led = w.ledger()
        assert led["scoped"]["event_compiles_warmup"] >= 1
        assert led["scoped"]["compile_time_warmup_s"] > 0
        assert led["__unattributed__"]["event_compiles_warmup"] >= 1


def test_watcher_mark_warmup_returns_to_warmup():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x - 1

    with CompileWatcher() as w:
        w.track("e", f)
        jax.block_until_ready(f(jnp.zeros((2,), jnp.float32)))
        w.mark_steady("e")
        w.mark_warmup("e")  # bench reuses one watcher across engine variants
        jax.block_until_ready(f(jnp.zeros((5,), jnp.float32)))
        led = w.ledger()["e"]
        assert led["warmup_compiles"] == 2
        assert led["steady_compiles"] == 0


def test_watcher_single_active_and_noop_scope():
    with CompileWatcher() as w:
        with pytest.raises(RuntimeError):
            CompileWatcher().install()
        del w
    # module-level attributed() is a no-op without an active watcher
    with watcher_mod.attributed("nobody-listening"):
        pass


# -------------------------------------------------------------- the budget


def _m(warm, steady):
    return {"warmup_compiles": warm, "steady_compiles": steady}


def test_budget_steady_nonzero_is_always_rt001():
    # even a committed nonzero steady count cannot waive the promise
    violations, _ = budget_mod.compare(
        {"e": _m(2, 3)}, {"e": {"warmup_compiles": 2, "steady_compiles": 3}}
    )
    assert any(v.startswith("RT001 e:") for v in violations)


def test_budget_warmup_drift_and_missing_entry():
    violations, notes = budget_mod.compare(
        {"grew": _m(5, 0), "shrank": _m(1, 0), "new": _m(1, 0)},
        {"grew": _m(3, 0), "shrank": _m(2, 0)},
    )
    assert any(v.startswith("RT002 grew:") and "3 -> 5" in v for v in violations)
    assert any(v.startswith("RT002 new:") for v in violations)
    assert any("improved 2 -> 1" in n for n in notes)
    # a --probe subset never complains about probes it did not run
    v2, _ = budget_mod.compare({"grew": _m(3, 0)}, {"grew": _m(3, 0), "shrank": _m(2, 0)})
    assert v2 == []


def test_budget_write_pins_steady_to_zero(tmp_path):
    path = tmp_path / "budget.json"
    budget_mod.write(path, {"e": _m(4, 7)})
    doc = json.loads(path.read_text())
    assert doc["e"]["steady_compiles"] == 0
    assert budget_mod.load(path) == {"e": {"warmup_compiles": 4, "steady_compiles": 0}}


def test_budget_write_refuses_under_seed(tmp_path, monkeypatch):
    monkeypatch.setenv(seeds.ENV_VAR, "shape_churn")
    with pytest.raises(RuntimeError, match="refusing"):
        budget_mod.write(tmp_path / "budget.json", {"e": _m(1, 0)})


def test_committed_budget_covers_the_probe_entrypoints():
    committed = budget_mod.load(os.path.join(REPO_ROOT, budget_mod.DEFAULT_BUDGET))
    assert committed, "graftcheck-rt-budget.json must be committed"
    for entry in committed.values():
        assert entry["steady_compiles"] == 0, "the committed steady budget is zero, always"
    # the train-step probes, the streamed-scoring ladder, and the serving
    # engine's per-step entrypoints all have committed warmup numbers
    assert {
        "ppo_train_step", "grpo_train_step", "stream_score_bucket",
        "serving_prefill", "serving_pack_step", "serving_decode_step",
        "serving_chunk_step", "serving_verify_step",
    } <= set(committed)


# ------------------------------------------------------ seeds & quantizer


def test_seed_validation(monkeypatch):
    monkeypatch.delenv(seeds.ENV_VAR, raising=False)
    assert seeds.active() is None
    monkeypatch.setenv(seeds.ENV_VAR, "shape_churn")
    assert seeds.active() == "shape_churn"
    assert seeds.shape_churn()
    monkeypatch.setenv(seeds.ENV_VAR, "not_a_seed")
    with pytest.raises(ValueError):
        seeds.active()


def test_shape_churn_seed_breaks_the_quantizer(monkeypatch):
    from trlx_tpu.trainer.ppo_trainer import overlap_r_buckets, quantize_stream_response

    ladder = overlap_r_buckets(64)
    monkeypatch.delenv(seeds.ENV_VAR, raising=False)
    assert quantize_stream_response(7, ladder) in ladder
    assert quantize_stream_response(7, ladder) != 7
    # the seed makes the PRODUCTION quantizer leak raw lengths — the exact
    # defect the compile gate must turn into a nonzero exit (ci.sh proves it)
    monkeypatch.setenv(seeds.ENV_VAR, "shape_churn")
    assert quantize_stream_response(7, ladder) == 7


# ------------------------------------------------------------- CLI / driver


def test_rt_cli_unknown_probe_is_usage_error(capsys):
    assert rt_cli_main(["--exec-only", "--probe", "no_such_probe"]) == 2
    assert "unknown probe" in capsys.readouterr().err


def test_driver_suite_selects():
    assert SUITE_SELECTS == {"ast": "JX,TH", "conc": "CC"}


def test_driver_suite_static_passes_on_clean_file(tmp_path):
    f = tmp_path / "clean.py"
    f.write_text("import jax\n\nstep = jax.jit(lambda x: x)\n")
    assert cli_main([str(f), "--suite", "ast"]) == 0
    assert cli_main([str(f), "--suite", "conc"]) == 0


def test_driver_suite_ast_excludes_sh(tmp_path):
    f = tmp_path / "seam.py"
    f.write_text(
        textwrap.dedent(
            """
            import jax

            step = jax.jit(lambda x, c: x * c)

            def go(x):
                return step(x, 0.5)
            """
        )
    )
    bl = str(tmp_path / "empty-baseline.txt")
    # the SH002 seam is invisible to --suite ast but caught by the full run
    assert cli_main([str(f), "--suite", "ast", "--baseline", bl]) == 0
    assert cli_main([str(f), "--baseline", bl]) == 1


def test_driver_rejects_baseline_writes_for_exec_suites(capsys):
    assert cli_main(["--suite", "rt", "--write-baseline"]) == 2
    assert cli_main(["--suite", "ir", "--prune-baseline"]) == 2


# --------------------------------------------------------- repo-level gates


@pytest.mark.slow
def test_repo_tree_sh_clean():
    """The committed tree carries no new SH finding (deliberate exceptions
    live in graftcheck-baseline.txt with justifications)."""
    rc = subprocess.call(
        [sys.executable, "-m", "trlx_tpu.analysis",
         "trlx_tpu", "tests", "examples", "scripts",
         "--select", "SH", "--jobs", "4"],
        cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert rc == 0


@pytest.mark.slow
def test_stream_probe_passes_clean_and_fails_seeded():
    """The gate proves itself end-to-end: the stream_score_bucket probe passes
    against the committed budget, and the SAME command exits nonzero under
    TRLX_RT_SEED_REGRESSION=shape_churn (RT001: steady-state recompiles)."""
    cmd = [sys.executable, "-m", "trlx_tpu.analysis.rt",
           "--exec-only", "--probe", "stream_score_bucket"]
    env = {k: v for k, v in os.environ.items() if k != seeds.ENV_VAR}
    clean = subprocess.run(cmd, cwd=REPO_ROOT, env=env, capture_output=True, text=True)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    seeded = subprocess.run(
        cmd, cwd=REPO_ROOT, env={**env, seeds.ENV_VAR: "shape_churn"},
        capture_output=True, text=True,
    )
    assert seeded.returncode == 1, seeded.stdout + seeded.stderr
    assert "RT001 stream_score_bucket" in seeded.stdout
