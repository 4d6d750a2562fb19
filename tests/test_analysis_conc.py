"""Concurrency analyzer (trlx_tpu/analysis/conc): CC001-CC005 positive and
negative fixtures, thread-entry-point modeling (Thread targets, escalation
callbacks, spawned closures), noqa/baseline round-trips, the seeded-regression
gate self-test, --jobs parity, and the repo-level CC-clean contract.

Fixtures run through the public ``run()`` entry with ``select=["CC"]`` (the
family prefix) so the whole pipeline — parse, call graph, conc model, rule
replay, noqa — is exercised, isolated from the JX/TH rules the same snippets
would also trip.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from trlx_tpu.analysis import RULES, run
from trlx_tpu.analysis.cli import main as cli_main
from trlx_tpu.analysis.conc import seeds
from trlx_tpu.analysis.core import resolve_select
from trlx_tpu.analysis import core as core_mod

pytestmark = pytest.mark.analysis_conc

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def check_snippet(tmp_path, source, name="snippet.py", select=("CC",)):
    f = tmp_path / name
    f.write_text(textwrap.dedent(source))
    return run([str(f)], select=list(select) if select else None)


def rule_ids(findings):
    return [f.rule for f in findings]


# ----------------------------------------------------------------- registry


def test_cc_rules_registered():
    assert {"CC001", "CC002", "CC003", "CC004", "CC005"} <= set(RULES)
    for rid in ("CC001", "CC002", "CC003", "CC004", "CC005"):
        assert RULES[rid].summary


def test_select_family_prefix():
    assert [r.id for r in resolve_select(["CC"])] == [
        "CC001", "CC002", "CC003", "CC004", "CC005",
    ]
    with pytest.raises(ValueError):
        resolve_select(["CC9"])


# ------------------------------------------------------------------- CC001


CC001_POSITIVE = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.items = []
            self._thread = None

        def start(self):
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

        def _loop(self):
            self.items.append(1)

        def drain(self):
            with self._lock:
                return list(self.items)
    """


def test_cc001_unguarded_shared_attr_positive(tmp_path):
    findings = check_snippet(tmp_path, CC001_POSITIVE)
    assert rule_ids(findings) == ["CC001"]
    assert "items" in findings[0].message
    assert "_loop" in findings[0].message  # anchored at the unguarded side


def test_cc001_both_sides_locked_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []

            def start(self):
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                with self._lock:
                    self.items.append(1)

            def drain(self):
                with self._lock:
                    return list(self.items)
        """,
    )
    assert findings == []


def test_cc001_entry_lockset_propagates_through_private_helper(tmp_path):
    # _snapshot is only ever called with the lock held: the interprocedural
    # entry lockset proves self.items guarded, where TH001's lexical view
    # could not
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []

            def start(self):
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                with self._lock:
                    self.items.append(1)

            def drain(self):
                with self._lock:
                    return self._snapshot()

            def _snapshot(self):
                return list(self.items)
        """,
    )
    assert findings == []


def test_cc001_init_writes_do_not_count_as_shared(tmp_path):
    # construction happens-before publication: __init__-only writes plus one
    # reader role must stay clean
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Holder:
            def __init__(self, limit):
                self._lock = threading.Lock()
                self.limit = limit

            def start(self):
                threading.Thread(target=self._loop, daemon=True).start()

            def _loop(self):
                return self.limit

            def read(self):
                return self.limit
        """,
    )
    assert findings == []


def test_cc001_escalation_callback_is_a_thread_root(tmp_path):
    # watchdog-style `x.escalate(name, self._cb)` registration: _cb runs on
    # the watchdog thread, so its unguarded write races the locked reader
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Supervisor:
            def __init__(self, dog):
                self._lock = threading.Lock()
                self.flag = 0
                dog.escalate("producer", self._on_stall)

            def _on_stall(self, name, age):
                self.flag = 1

            def read(self):
                with self._lock:
                    return self.flag
        """,
    )
    assert rule_ids(findings) == ["CC001"]
    assert "flag" in findings[0].message


def test_cc001_spawned_closure_is_a_thread_root(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.done = 0

            def start(self):
                def work():
                    self.done = 1
                threading.Thread(target=work, daemon=True).start()

            def poll(self):
                with self._lock:
                    return self.done
        """,
    )
    assert rule_ids(findings) == ["CC001"]
    assert "done" in findings[0].message


# ------------------------------------------------------------------- CC002


def test_cc002_lock_order_cycle_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self.x = 0

            def fwd(self):
                with self._a:
                    with self._b:
                        self.x += 1

            def rev(self):
                with self._b:
                    with self._a:
                        self.x += 1
        """,
    )
    assert "CC002" in rule_ids(findings)


def test_cc002_consistent_order_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self.x = 0

            def fwd(self):
                with self._a:
                    with self._b:
                        self.x += 1

            def rev(self):
                with self._a:
                    with self._b:
                        self.x -= 1
        """,
    )
    assert "CC002" not in rule_ids(findings)


def test_cc002_cycle_through_callee_summary(tmp_path):
    # fwd holds _a and calls a helper that takes _b; rev orders b-then-a:
    # the edge comes from the call-graph acquired-lock summary, not lexical
    # nesting
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self.x = 0

            def fwd(self):
                with self._a:
                    self._bump()

            def _bump(self):
                with self._b:
                    self.x += 1

            def rev(self):
                with self._b:
                    with self._a:
                        self.x -= 1
        """,
    )
    assert "CC002" in rule_ids(findings)


# ------------------------------------------------------------------- CC003


def test_cc003_wait_outside_predicate_loop_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Box:
            def __init__(self):
                self._cv = threading.Condition()
                self.items = []

            def get(self):
                with self._cv:
                    if not self.items:
                        self._cv.wait()
                    return self.items.pop()

            def put(self, x):
                with self._cv:
                    self.items.append(x)
                    self._cv.notify()
        """,
    )
    assert rule_ids(findings) == ["CC003"]
    assert "wait" in findings[0].message


def test_cc003_notify_without_lock_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Box:
            def __init__(self):
                self._cv = threading.Condition()
                self.items = []

            def get(self):
                with self._cv:
                    while not self.items:
                        self._cv.wait()
                    return self.items.pop()

            def put(self, x):
                with self._cv:
                    self.items.append(x)
                self._cv.notify()
        """,
    )
    assert rule_ids(findings) == ["CC003"]
    assert "notify" in findings[0].message


def test_cc003_discarded_timed_wait_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Box:
            def __init__(self):
                self._cv = threading.Condition()
                self.n = 0

            def poke(self):
                with self._cv:
                    self._cv.wait(1.0)
                    return self.n

            def put(self):
                with self._cv:
                    self.n += 1
                    self._cv.notify()
        """,
    )
    assert rule_ids(findings) == ["CC003"]
    assert "timeout" in findings[0].message


def test_cc003_textbook_protocol_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Box:
            def __init__(self):
                self._cv = threading.Condition()
                self.items = []

            def get(self):
                with self._cv:
                    while not self.items:
                        self._cv.wait()
                    return self.items.pop()

            def get_bounded(self):
                with self._cv:
                    while not self.items:
                        if not self._cv.wait(1.0):
                            return None
                    return self.items.pop()

            def put(self, x):
                with self._cv:
                    self.items.append(x)
                    self._cv.notify()
        """,
    )
    assert findings == []


# ------------------------------------------------------------------- CC004


def test_cc004_check_then_act_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.total = 0

            def bump(self, n):
                with self._lock:
                    cur = self.total
                grown = cur + n
                with self._lock:
                    self.total = grown
        """,
    )
    assert rule_ids(findings) == ["CC004"]
    assert "total" in findings[0].message


def test_cc004_reread_merge_is_clean(tmp_path):
    # the scheduler's kept+pending idiom: the second section re-reads before
    # writing, so nothing observed in the first section is trusted stale
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Queue:
            def __init__(self):
                self._lock = threading.Lock()
                self.pending = []

            def requeue(self, kept):
                with self._lock:
                    current = list(self.pending)
                kept = [k for k in kept if k not in current]
                with self._lock:
                    self.pending = kept + self.pending
        """,
    )
    assert findings == []


def test_cc004_single_section_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.total = 0

            def bump(self, n):
                with self._lock:
                    self.total += n
        """,
    )
    assert findings == []


# ------------------------------------------------------------------- CC005


def test_cc005_file_io_under_lock_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Sink:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def write(self, rec):
                with self._lock:
                    self.n += 1
                    with open("log.txt", "a") as f:
                        f.write(rec)
        """,
    )
    assert rule_ids(findings) == ["CC005"]
    assert "open" in findings[0].message


def test_cc005_queue_put_under_lock_positive(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import queue
        import threading

        class Producer:
            def __init__(self):
                self._lock = threading.Lock()
                self.q = queue.Queue()
                self.n = 0

            def send(self, x):
                with self._lock:
                    self.n += 1
                    self.q.put(x)
        """,
    )
    assert rule_ids(findings) == ["CC005"]


def test_cc005_blocking_callee_summary_positive(tmp_path):
    # client.py shape: the blocking op is inside another class's method; the
    # call-site report needs the cross-class may-block summary
    findings = check_snippet(
        tmp_path,
        """
        import threading
        import jax

        class Engine:
            def run(self):
                return jax.device_get(1)

        class Client:
            def __init__(self, engine: Engine):
                self._lock = threading.Lock()
                self.engine = engine

            def step(self):
                with self._lock:
                    return self.engine.run()
        """,
    )
    assert rule_ids(findings) == ["CC005"]
    assert "Engine.run" in findings[0].message


def test_cc005_blocking_outside_lock_is_clean(tmp_path):
    findings = check_snippet(
        tmp_path,
        """
        import threading

        class Sink:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def write(self, rec):
                with self._lock:
                    self.n += 1
                with open("log.txt", "a") as f:
                    f.write(rec)
        """,
    )
    assert findings == []


# ------------------------------------------------- suppression round-trips


def test_cc_noqa_suppresses_at_the_anchor_line(tmp_path):
    src = CC001_POSITIVE.replace(
        "self.items.append(1)",
        "self.items.append(1)  # graftcheck: noqa[CC001]",
    )
    assert check_snippet(tmp_path, src) == []


def test_cc_baseline_round_trip(tmp_path, monkeypatch):
    f = tmp_path / "racy.py"
    f.write_text(textwrap.dedent(CC001_POSITIVE))
    bl = tmp_path / "baseline.txt"
    monkeypatch.delenv(seeds.ENV_VAR, raising=False)
    assert cli_main([str(f), "--select", "CC", "--baseline", str(bl), "--write-baseline"]) == 0
    assert cli_main([str(f), "--select", "CC", "--baseline", str(bl)]) == 0
    # the entry keys on the code text: fixing the line makes it stale, and a
    # genuinely new finding still fails
    assert cli_main([str(f), "--select", "CC", "--baseline", str(bl), "--no-baseline"]) == 1


def test_stale_baseline_for_unselected_rule_not_reported(tmp_path, capsys):
    f = tmp_path / "clean.py"
    f.write_text("x = 1\n")
    bl = tmp_path / "baseline.txt"
    bl.write_text("other.py:TH001:self.x = 1  # grandfathered\n")
    assert cli_main([str(f), "--select", "CC", "--baseline", str(bl)]) == 0
    assert "stale baseline entry" not in capsys.readouterr().out


def test_stale_baseline_for_unscanned_file_not_reported(tmp_path, capsys):
    # precommit passes only changed files: entries for files outside that
    # list never had the chance to be re-found and must not read as stale
    f = tmp_path / "clean.py"
    f.write_text("x = 1\n")
    bl = tmp_path / "baseline.txt"
    bl.write_text("elsewhere/racy.py:CC005:self.q.put(x)  # grandfathered\n")
    assert cli_main([str(f), "--baseline", str(bl)]) == 0
    assert "stale baseline entry" not in capsys.readouterr().out


# ------------------------------------------------------- seeded regression


def test_seed_scheduler_race_fires_cc001(tmp_path, monkeypatch):
    monkeypatch.setenv(seeds.ENV_VAR, "scheduler_race")
    findings = run(
        [os.path.join(REPO_ROOT, "trlx_tpu", "serving", "scheduler.py")],
        select=["CC"],
    )
    hits = [f for f in findings if f.rule == "CC001" and "finished" in f.message]
    assert hits, rule_ids(findings)


def test_seed_is_in_memory_only(tmp_path, monkeypatch):
    # same file, seed unset: clean — the seed never touches the tree on disk
    monkeypatch.delenv(seeds.ENV_VAR, raising=False)
    findings = run(
        [os.path.join(REPO_ROOT, "trlx_tpu", "serving", "scheduler.py")],
        select=["CC"],
    )
    assert [f for f in findings if f.rule == "CC001"] == []


def test_unknown_seed_is_exit_2(tmp_path, monkeypatch):
    f = tmp_path / "empty.py"
    f.write_text("x = 1\n")
    monkeypatch.setenv(seeds.ENV_VAR, "not_a_seed")
    assert cli_main([str(f), "--select", "CC", "--no-baseline"]) == 2


# ----------------------------------------------------------------- --jobs


def test_jobs_pool_parity(tmp_path, monkeypatch):
    # force the fork-pool path even on 1-core CI hosts (run() clamps jobs to
    # cpu_count); findings must match the serial path exactly
    for i in range(4):
        (tmp_path / f"mod{i}.py").write_text(
            textwrap.dedent(CC001_POSITIVE).replace("Worker", f"Worker{i}")
        )
    serial = run([str(tmp_path)], select=["CC"], jobs=1)
    monkeypatch.setattr(core_mod.os, "cpu_count", lambda: 4)
    pooled = run([str(tmp_path)], select=["CC"], jobs=4)
    key = lambda f: (f.path, f.lineno, f.rule, f.message)  # noqa: E731
    assert sorted(map(key, serial)) == sorted(map(key, pooled))
    assert len(serial) == 4


# ----------------------------------------------------- repo-level contract


@pytest.mark.slow
def test_repo_tree_is_cc_clean():
    """Acceptance criteria: the merged tree passes the CC gate..."""
    env = {k: v for k, v in os.environ.items() if k != seeds.ENV_VAR}
    proc = subprocess.run(
        [sys.executable, "-m", "trlx_tpu.analysis", "trlx_tpu", "tests",
         "examples", "scripts", "__graft_entry__.py", "--select", "CC"],
        cwd=REPO_ROOT, capture_output=True, text=True, env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.slow
def test_repo_tree_seeded_race_fails_the_gate():
    """...and the seeded PR-8 race makes the same command exit 1."""
    env = dict(os.environ, **{seeds.ENV_VAR: "scheduler_race"})
    proc = subprocess.run(
        [sys.executable, "-m", "trlx_tpu.analysis", "trlx_tpu", "tests",
         "examples", "scripts", "__graft_entry__.py", "--select", "CC"],
        cwd=REPO_ROOT, capture_output=True, text=True, env=env,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "CC001" in proc.stdout and "finished" in proc.stdout
