"""graftcheck-ir (trlx_tpu/analysis/ir): entrypoint registry, deviceless
lowering, IR001-IR004 rule positives/negatives on tiny inline steps,
collective-to-mesh-axis attribution, budget round-trip/compare, noqa at the
registration site, and the persistent compilation cache.

The heavy paths — full-model lowering of the registered entrypoints, the CLI
budget gate against seeded regressions, and the 1.5B-shaped decode lowering —
are slow-marked; ``scripts/ci.sh`` runs the fast half in its analysis-ir
section and the CLI gate as a separate hard step.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.analysis.core import RULES, load_context
from trlx_tpu.analysis.ir import budget as budget_mod
from trlx_tpu.analysis.ir.entrypoints import (
    DEFAULT_AUDIT_MESH,
    EntryArtifacts,
    EntryPoint,
    load_all,
)
from trlx_tpu.analysis.ir.lowering import (
    lower_entry,
    measure,
    parse_collectives,
)
from trlx_tpu.analysis.ir.rules_ir import audit_entry
from trlx_tpu.parallel.mesh import make_deviceless_mesh

pytestmark = pytest.mark.analysis_ir

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def toy_entry(fn, args, name="toy_step", module="tests.test_analysis_ir",
              lineno=1, mesh_shape=None, **art_kwargs):
    """An EntryPoint over an inline fn with a trivial 1-device mesh, so rule
    tests compile in milliseconds instead of lowering a model."""
    art = EntryArtifacts(fn=fn, args=tuple(args), **art_kwargs)
    return EntryPoint(
        name=name,
        builder=lambda spec, mesh: art,
        specs=("small",),
        mesh_shape=mesh_shape or {"data": 1, "fsdp": 1, "pipe": 1, "model": 1},
        module=module,
        lineno=lineno,
    )


def rules_fired(lowered):
    return sorted({f.rule for f in audit_entry(lowered)})


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# -------------------------------------------------------------- registry


def test_registered_rules_include_ir():
    for rid in ("IR001", "IR002", "IR003", "IR004", "IR005", "IR006"):
        assert rid in RULES
        assert RULES[rid].summary


def test_entrypoint_registry_covers_the_hot_steps():
    eps = load_all()
    assert {"ppo_train_step", "ilql_train_step", "decode_step"} <= set(eps)
    for ep in eps.values():
        assert os.path.exists(os.path.join(REPO_ROOT, ep.rel_path()))
        assert ep.lineno > 0
        assert set(ep.mesh_shape) == set(DEFAULT_AUDIT_MESH)
    # the xl spec exists for the scale lowering proof (slow test below)
    assert "xl" in eps["decode_step"].specs


# ---------------------------------------------------------------- IR001


def test_ir001_f32_dot_in_bf16_step_fires():
    def step(x):
        return (x @ x).sum()

    lowered = lower_entry(toy_entry(step, [sds((16, 16), jnp.float32)]))
    findings = audit_entry(lowered)
    assert [f.rule for f in findings] == ["IR001"]
    assert "float32 `dot_general`" in findings[0].message
    assert findings[0].path == "tests/test_analysis_ir.py"


def test_ir001_bf16_dot_is_clean():
    def step(x):
        return (x @ x).sum(dtype=jnp.float32)

    lowered = lower_entry(toy_entry(step, [sds((16, 16), jnp.bfloat16)]))
    assert "IR001" not in rules_fired(lowered)


def test_ir001_f32_allow_cap():
    def step(x):
        return (x @ x).sum()

    args = [sds((16, 16), jnp.float32)]
    # unlimited allow and a covering cap both pass
    for allow in (frozenset({"dot_general"}), frozenset({"dot_general:1"})):
        lowered = lower_entry(toy_entry(step, args, f32_allow=allow))
        assert "IR001" not in rules_fired(lowered)
    # one dot over the cap fires, and the message names the cap
    lowered = lower_entry(toy_entry(step, args, f32_allow=frozenset({"dot_general:0"})))
    findings = [f for f in audit_entry(lowered) if f.rule == "IR001"]
    assert len(findings) == 1
    assert "allow-listed cap is 0" in findings[0].message


# ---------------------------------------------------------------- IR002


def test_ir002_declared_donation_that_cannot_alias_fires():
    def step(x):
        return (x * 2).astype(jnp.bfloat16)  # dtype change: no alias possible

    lowered = lower_entry(
        toy_entry(step, [sds((256, 256), jnp.float32)], donate_argnums=(0,))
    )
    findings = [f for f in audit_entry(lowered) if f.rule == "IR002"]
    assert len(findings) == 1
    assert "no input_output_alias" in findings[0].message


def test_ir002_effective_donation_is_clean():
    def step(x):
        return x * 2  # same shape/dtype: XLA aliases the donated buffer

    lowered = lower_entry(
        toy_entry(step, [sds((256, 256), jnp.float32)], donate_argnums=(0,))
    )
    assert "IR002" not in rules_fired(lowered)


def test_ir002_missed_donation_opportunity_fires():
    def step(x):
        return x + 1.0  # 1 MiB in, same-signature 1 MiB out, nothing donated

    lowered = lower_entry(toy_entry(step, [sds((512, 512), jnp.float32)]))
    findings = [f for f in audit_entry(lowered) if f.rule == "IR002"]
    assert len(findings) == 1
    assert "consider donate_argnums" in findings[0].message


# ---------------------------------------------------------------- IR003


def test_ir003_baked_constant_fires_and_threshold_is_tunable():
    big = jnp.asarray(np.ones(1024, np.float32))  # 4 KiB closure constant

    def step(x):
        return x + big.sum()

    args = [sds((8,), jnp.float32)]
    lowered = lower_entry(
        toy_entry(step, args, meta={"const_bytes_threshold": 1024})
    )
    findings = [f for f in audit_entry(lowered) if f.rule == "IR003"]
    assert len(findings) == 1
    assert "trace-time constant" in findings[0].message
    # under the default 1 MiB threshold the same constant rides along free
    lowered = lower_entry(toy_entry(step, args))
    assert "IR003" not in rules_fired(lowered)


# ---------------------------------------------------------------- IR004


def test_ir004_host_callback_fires():
    def step(x):
        jax.debug.callback(lambda v: None, x.sum())
        return x * 2

    lowered = lower_entry(toy_entry(step, [sds((8,), jnp.float32)]))
    findings = [f for f in audit_entry(lowered) if f.rule == "IR004"]
    assert len(findings) == 1
    assert "round-trip" in findings[0].message


# ------------------------------------------------- noqa at registration site


def test_noqa_on_builder_def_line_suppresses(tmp_path):
    src = tmp_path / "regmod.py"
    src.write_text(
        textwrap.dedent(
            """
            def build_toy(spec, mesh):  # graftcheck: noqa[IR001]
                pass
            """
        )
    )
    ctx = load_context(src, rel="regmod.py")

    def step(x):
        return (x @ x).sum()

    entry = toy_entry(step, [sds((16, 16), jnp.float32)], module="regmod", lineno=2)
    lowered = lower_entry(entry)
    assert audit_entry(lowered) != []  # fires without the context...
    assert audit_entry(lowered, ctx) == []  # ...suppressed with it


# ------------------------------------------------- collective attribution


def test_collective_axis_attribution():
    mesh = make_deviceless_mesh(**DEFAULT_AUDIT_MESH)  # 2x2x1x2, flat order
    hlo = "\n".join([
        "ENTRY main {",
        # consecutive pairs = innermost (model) axis
        "  %ag = bf16[16,8]{1,0} all-gather(bf16[8,8]{1,0} %p),"
        " replica_groups={{0,1},{2,3},{4,5},{6,7}}, dimensions={0}",
        # stride-2 pairs = fsdp axis, iota form
        "  %rs = f32[4,8]{1,0} reduce-scatter(f32[8,8]{1,0} %q),"
        " replica_groups={{0,2},{1,3},{4,6},{5,7}}, dimensions={0}",
        # iota form [4,2]<=[8]: {0,1},{2,3},... = model again
        "  %ar = f32[8]{0} all-reduce(f32[8]{0} %r), replica_groups=[4,2]<=[8]",
        # a grouping matching no axis subset gets an anonymous signature
        "  %odd = f32[8]{0} all-reduce(f32[8]{0} %s),"
        " replica_groups={{0,3},{1,2},{4,7},{5,6}}",
        # no replica_groups attribute at all = all devices
        "  ROOT %cp = u32[2]{0} collective-permute(u32[2]{0} %t),"
        " source_target_pairs={{0,1}}",
        "}",
    ])
    got = parse_collectives(hlo, mesh)
    assert got["all-gather:model"] == {"count": 1, "bytes": 16 * 8 * 2}
    assert got["reduce-scatter:fsdp"] == {"count": 1, "bytes": 4 * 8 * 4}
    assert got["all-reduce:model"] == {"count": 1, "bytes": 8 * 4}
    assert got["all-reduce:g4x2"] == {"count": 1, "bytes": 8 * 4}
    assert got["collective-permute:all"] == {"count": 1, "bytes": 2 * 4}


def test_deviceless_mesh_needs_enough_devices():
    with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
        make_deviceless_mesh(data=64, fsdp=2, pipe=1, model=2)


# ------------------------------------------------------------------ budget


def _toy_measurements():
    return {
        "step@small": {
            "mesh": dict(DEFAULT_AUDIT_MESH),
            "collectives": {
                "all-gather:fsdp": {"count": 3, "bytes": 1000},
                "all-reduce:model": {"count": 2, "bytes": 500},
            },
            "memory_bytes": 10000,
        }
    }


def test_budget_round_trip_and_compare(tmp_path):
    path = tmp_path / "budget.json"
    meas = _toy_measurements()
    assert budget_mod.write(path, meas) == 1
    loaded = budget_mod.load(path)
    assert loaded == meas  # _-prefixed doc keys are stripped on load

    violations, notes = budget_mod.compare(meas, loaded)
    assert violations == [] and notes == []


def test_budget_compare_flags_regressions(tmp_path):
    want = _toy_measurements()
    got = json.loads(json.dumps(want))  # deep copy
    got["step@small"]["collectives"]["all-gather:fsdp"]["count"] = 4
    got["step@small"]["collectives"]["all-gather:model"] = {"count": 1, "bytes": 64}
    got["step@small"]["memory_bytes"] = 12000  # +20% > 10% headroom
    violations, notes = budget_mod.compare(got, want)
    text = "\n".join(violations)
    assert "IR005" in text and "count 3 -> 4" in text
    assert "NEW collective all-gather:model" in text
    assert "IR006" in text and "memory_bytes" in text
    assert len(violations) == 3 and notes == []


def test_budget_compare_notes_improvements():
    want = _toy_measurements()
    got = json.loads(json.dumps(want))
    del got["step@small"]["collectives"]["all-reduce:model"]
    got["step@small"]["memory_bytes"] = 5000
    violations, notes = budget_mod.compare(got, want)
    assert violations == []
    assert any("no longer emitted" in n for n in notes)
    assert any("improved" in n for n in notes)


def test_budget_missing_entry_is_a_violation():
    violations, _ = budget_mod.compare(_toy_measurements(), {})
    assert len(violations) == 1 and "no committed budget entry" in violations[0]


def test_budget_bytes_tolerance():
    want = _toy_measurements()
    got = json.loads(json.dumps(want))
    got["step@small"]["collectives"]["all-gather:fsdp"]["bytes"] = 1050  # +5%
    violations, _ = budget_mod.compare(got, want)
    assert violations == []
    got["step@small"]["collectives"]["all-gather:fsdp"]["bytes"] = 1200  # +20%
    violations, _ = budget_mod.compare(got, want)
    assert len(violations) == 1 and "grew" in violations[0]


def test_committed_budget_covers_every_small_entrypoint():
    budget = budget_mod.load(os.path.join(REPO_ROOT, budget_mod.DEFAULT_BUDGET))
    for name, ep in load_all().items():
        if "small" in ep.specs:
            assert f"{name}@small" in budget


# -------------------------------------------------- persistent compile cache


def _cache_config(train_dir=None, mesh_dir=None):
    from types import SimpleNamespace

    from trlx_tpu.data.configs import MeshConfig, TrainConfig

    return SimpleNamespace(
        train=TrainConfig(compilation_cache_dir=train_dir),
        mesh=MeshConfig(compilation_cache_dir=mesh_dir),
    )


def test_resolve_cache_dir_precedence(monkeypatch):
    from trlx_tpu.data.configs import TrainConfig
    from trlx_tpu.utils.compilation_cache import JAX_ENV_VAR, resolve_cache_dir

    monkeypatch.delenv(JAX_ENV_VAR, raising=False)
    assert TrainConfig().compilation_cache_dir is None  # knob exists, off by default
    config = _cache_config("/train-dir", "/mesh-dir")
    assert resolve_cache_dir(config, cache_dir="/explicit") == "/explicit"
    assert resolve_cache_dir(config) == "/train-dir"
    assert resolve_cache_dir(_cache_config(mesh_dir="/mesh-dir")) == "/mesh-dir"
    # nothing configured, CPU backend: the cache stays off
    assert jax.default_backend() == "cpu"
    assert resolve_cache_dir(_cache_config()) is None
    assert resolve_cache_dir(None) is None


def test_jax_env_var_set_means_no_directory_set_in_code(monkeypatch):
    # whoever runs the program placed the cache: with the variable set, no
    # path of ours may name another directory, whatever the config says
    from trlx_tpu.utils import compilation_cache as cc

    monkeypatch.setenv(cc.JAX_ENV_VAR, "/placed/from/outside")
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: (updates.append(key), real_update(key, value))
    )
    config = _cache_config("/train-dir", "/mesh-dir")
    assert cc.resolve_cache_dir(config, cache_dir="/explicit") is None
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda backend=backend: backend)
        cc.configure_compilation_cache(cache_dir="/explicit", config=config)
    assert "jax_compilation_cache_dir" not in updates


def test_unset_on_tpu_resolves_one_fixed_path_inside_the_checkout(monkeypatch):
    from trlx_tpu.utils import compilation_cache as cc

    monkeypatch.delenv(cc.JAX_ENV_VAR, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    first, second = cc.resolve_cache_dir(_cache_config()), cc.resolve_cache_dir(None)
    assert first == second == os.path.join(REPO_ROOT, ".jax_cache")
    # listed in .gitignore, so a checkout never carries a cache along
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_second_lower_hits_persistent_cache(tmp_path):
    # the cache-enablement latch (see trlx_tpu/utils/compilation_cache.py)
    # demands a fresh process: configure BEFORE the first compile, compile,
    # clear the in-memory executable caches, compile the same fn again and
    # observe the persistent-cache hit in jax's compiler log
    script = textwrap.dedent(
        """
        import logging, os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)  # the argument places it here
        cache_dir = sys.argv[1]

        from trlx_tpu.utils.compilation_cache import configure_compilation_cache
        assert configure_compilation_cache(
            cache_dir=cache_dir, min_compile_time_secs=0.0) == cache_dir

        records = []
        handler = logging.Handler()
        handler.emit = lambda r: records.append(r.getMessage())
        for name in ("jax", "jax._src.compiler", "jax._src.compilation_cache"):
            logging.getLogger(name).addHandler(handler)
            logging.getLogger(name).setLevel(logging.DEBUG)

        import jax, jax.numpy as jnp

        def f(x):
            return (x @ x.T).sum()

        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        jax.jit(f).lower(x).compile()
        n_entries = len(os.listdir(cache_dir))
        assert n_entries > 0, "first compile wrote nothing to the cache dir"

        jax.clear_caches()  # drop in-memory executables, keep the disk cache
        records.clear()
        jax.jit(f).lower(x).compile()
        hit = any("cache hit" in m.lower() for m in records)
        assert hit, f"no persistent-cache hit logged; got: {records[:5]}"
        assert len(os.listdir(cache_dir)) == n_entries, "second compile re-wrote"
        print(f"CACHE_OK entries={n_entries}")
        """
    )
    cache_dir = tmp_path / "xla-cache"
    cache_dir.mkdir()
    proc = subprocess.run(
        [sys.executable, "-c", script, str(cache_dir)],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "CACHE_OK" in proc.stdout


# ------------------------------------------------------- slow: full models


@pytest.mark.slow
def test_registered_entrypoints_audit_clean():
    # the committed-budget contract end to end, in process: every small-spec
    # entrypoint lowers devicelessly, produces no findings, and matches the
    # committed budget exactly
    budget = budget_mod.load(os.path.join(REPO_ROOT, budget_mod.DEFAULT_BUDGET))
    measurements = {}
    for name, ep in sorted(load_all().items()):
        lowered = lower_entry(ep)
        assert audit_entry(lowered) == [], name
        measurements[lowered.key] = measure(lowered)
    violations, _ = budget_mod.compare(measurements, budget)
    assert violations == []


@pytest.mark.slow
@pytest.mark.parametrize("seed,expect", [
    ("f32_upcast", "IR001"),
    ("allgather", "BUDGET IR005"),
])
def test_cli_gate_fails_closed_on_seeded_regression(seed, expect):
    env = dict(os.environ, TRLX_IR_SEED_REGRESSION=seed)
    env.pop("JAX_PLATFORMS", None)  # __main__ forces its own cpu platform
    proc = subprocess.run(
        [sys.executable, "-m", "trlx_tpu.analysis.ir", "--entry", "ppo_train_step"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert expect in proc.stdout


@pytest.mark.slow
def test_decode_step_lowers_at_xl_scale():
    # satellite of the scale story: the 1.5B-shaped decode step (GPT-2-XL
    # dims, scan_layers) traces and lowers devicelessly — the same artifact a
    # TPU pod would compile, proven without one. Lower-only: compiling 48
    # layers on the CPU backend is minutes for no extra signal.
    ep = load_all()["decode_step"]
    lowered = lower_entry(ep, spec="xl", compile=False)
    assert lowered.compiled is None
    hidden = lowered.artifacts.meta.get("hidden_size")
    assert hidden == 1600
    text = lowered.lowered.as_text()
    assert "stablehlo" in text or "module" in text


@pytest.mark.slow
def test_spec_verify_step_lowers_at_xl_scale():
    # the speculative-verify evidence beyond gpt2-small: the GPT-2-XL-shaped
    # verify step (stacked scan_layers pools, int8 KV, K+1 query positions)
    # traces and lowers devicelessly. Lower-only, same reasoning as above.
    ep = load_all()["spec_verify_step"]
    assert {"small", "xl"} <= set(ep.specs)
    lowered = lower_entry(ep, spec="xl", compile=False)
    assert lowered.compiled is None
    assert lowered.artifacts.meta.get("hidden_size") == 1600
    assert lowered.artifacts.meta.get("spec_k", 0) > 0
    text = lowered.lowered.as_text()
    assert "stablehlo" in text or "module" in text
