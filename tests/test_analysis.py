"""tpulint: planted-violation fixtures, suppression, trace checks, CLI gate.

The fixture modules under tests/fixtures/tpulint/ are ANALYZED, never
imported: each violation line carries a ``# PLANT: <RULE>`` marker, and the
contract is exact — every planted rule fires at its marked line, and no
rule fires anywhere else.
"""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from mlops_tpu.analysis import analyze_paths, analyze_source
from mlops_tpu.analysis.astrules import RULES

FIXTURES = Path(__file__).parent / "fixtures" / "tpulint"
_PLANT = re.compile(r"#\s*PLANT:\s*(TPU\d+)")


def _planted(path: Path) -> set[tuple[int, str]]:
    out = set()
    for lineno, line in enumerate(path.read_text().splitlines(), start=1):
        m = _PLANT.search(line)
        if m:
            out.add((lineno, m.group(1)))
    return out


@pytest.mark.parametrize(
    "name",
    [
        "host_sync",
        "rng_clock",
        "tracer_branch",
        "config_arg",
        "missing_donate",
        "broad_except",
        "mutable_default",
        "serve/uncached_jit",
        "serve/swallowed_exception",
    ],
)
def test_each_planted_violation_fires_at_its_line(name):
    path = FIXTURES / f"{name}.py"
    planted = _planted(path)
    assert planted, f"fixture {name} has no PLANT markers"
    found = {
        (f.line, f.rule)
        for f in analyze_source(path.read_text(), path)
    }
    assert planted <= found, f"missed: {planted - found}"
    # No findings beyond the planted lines — the false-positive contract.
    extra = {(ln, r) for ln, r in found if (ln, r) not in planted}
    assert not extra, f"unexpected findings: {extra}"


def test_every_shipped_rule_is_exercised_by_a_fixture():
    """A rule without a fixture is a rule that can silently stop firing."""
    from mlops_tpu.analysis import (
        ASYNC_RULES,
        CONCURRENCY_RULES,
        CONTRACT_RULES,
    )

    shipped = (
        set(RULES)
        | set(CONCURRENCY_RULES)
        | set(CONTRACT_RULES)
        | set(ASYNC_RULES)
    )
    planted_rules = set()
    for path in FIXTURES.rglob("*.py"):
        planted_rules |= {rule for _, rule in _planted(path)}
    assert planted_rules == shipped, (
        f"fixture-less rules: {shipped - planted_rules}; "
        f"unknown planted: {planted_rules - shipped}"
    )


def test_suppression_comments_silence_findings():
    path = FIXTURES / "suppressed.py"
    findings = analyze_source(path.read_text(), path)
    assert findings == [], [f.format() for f in findings]


def test_clean_fixture_has_no_findings():
    path = FIXTURES / "clean.py"
    findings = analyze_source(path.read_text(), path)
    assert findings == [], [f.format() for f in findings]


def test_suppression_is_rule_specific():
    source = (
        "def f(x=[]):  # tpulint: disable=TPU101\n"
        "    return x\n"
    )
    findings = analyze_source(source, "inline.py")
    assert [f.rule for f in findings] == ["TPU202"]


def test_skip_file_pragma():
    source = "# tpulint: skip-file\ndef f(x=[]):\n    return x\n"
    assert analyze_source(source, "skipped.py") == []


def test_trailing_suppression_does_not_leak_to_next_line():
    """A disable comment trailing code on line N silences only line N; a
    STANDALONE comment line above silences the line below."""
    leaking = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    a = x.tolist()  # tpulint: disable=TPU101\n"
        "    b = x.tolist()\n"
        "    return a, b\n"
    )
    findings = analyze_source(leaking, "leak.py")
    assert [(f.rule, f.line) for f in findings] == [("TPU101", 5)]
    standalone = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    # tpulint: disable=TPU101\n"
        "    return x.tolist()\n"
    )
    assert analyze_source(standalone, "standalone.py") == []


def test_cli_exit_2_on_missing_path(capsys):
    from mlops_tpu.cli import main

    assert main(["analyze", "--no-trace", "definitely/not/a/path.py"]) == 2
    assert "no such path" in capsys.readouterr().out


# ------------------------------------------------------------ Layer 3
CONCURRENCY_FIXTURES = FIXTURES / "concurrency"
# The planted-count contract per rule, pinned exactly: the fixture suite
# is the regression net for the analyzer's precision in BOTH directions —
# a rule firing fewer times silently went blind, firing more went noisy.
CONCURRENCY_COUNTS = {"TPU401": 4, "TPU402": 2, "TPU403": 6, "TPU404": 2}


def _concurrency_findings(path):
    from mlops_tpu.analysis import analyze_concurrency_source

    src = path.read_text()
    return analyze_source(src, path) + analyze_concurrency_source(src, path)


@pytest.mark.parametrize(
    "name",
    ["lock_order", "guard_inference", "blocking_under_lock", "ring_pairing"],
)
def test_each_planted_concurrency_violation_fires_at_its_line(name):
    path = CONCURRENCY_FIXTURES / f"{name}.py"
    planted = _planted(path)
    assert planted, f"fixture {name} has no PLANT markers"
    found = {(f.line, f.rule) for f in _concurrency_findings(path)}
    assert planted <= found, f"missed: {planted - found}"
    extra = {(ln, r) for ln, r in found if (ln, r) not in planted}
    assert not extra, f"unexpected findings: {extra}"


def test_concurrency_fixture_counts_pinned():
    """Exact per-rule finding counts over the whole fixture dir — and the
    CLI detects all of them through `analyze --concurrency`."""
    from collections import Counter

    from mlops_tpu.cli import main

    counts = Counter()
    for path in sorted(CONCURRENCY_FIXTURES.glob("*.py")):
        counts.update(f.rule for f in _concurrency_findings(path))
    assert dict(counts) == CONCURRENCY_COUNTS

    assert (
        main(
            ["analyze", "--no-trace", "--concurrency",
             str(CONCURRENCY_FIXTURES)]
        )
        == 1
    )


def test_concurrency_rules_respect_suppressions():
    from mlops_tpu.analysis import analyze_concurrency_source

    source = (
        "import threading\n"
        "import numpy as np\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def f(self, h):\n"
        "        with self._lock:\n"
        "            return np.asarray(h)  # tpulint: disable=TPU403\n"
    )
    assert analyze_concurrency_source(source, "inline.py") == []
    kept = analyze_concurrency_source(
        source, "inline.py", keep_suppressed=True
    )
    assert [f.rule for f in kept] == ["TPU403"]


def test_concurrency_layer_requires_flag():
    """Without --concurrency the fixtures raise no TPU40x findings (the
    planted files are Layer-1 clean by construction)."""
    from mlops_tpu.cli import main

    assert (
        main(["analyze", "--no-trace", str(CONCURRENCY_FIXTURES)]) == 0
    )


def test_lockless_class_methods_see_module_locks():
    """A class with no lock attributes of its own still gets walked: its
    methods holding a MODULE-level lock are in scope for TPU403 (regression
    — lock-less classes were skipped entirely, so shared-module-lock misuse
    inside them was invisible)."""
    from mlops_tpu.analysis import analyze_concurrency_source

    source = (
        "import threading\n"
        "import numpy as np\n"
        "_LOCK = threading.Lock()\n"
        "class NoLocks:\n"
        "    def f(self, h):\n"
        "        with _LOCK:\n"
        "            return np.asarray(h)\n"
    )
    findings = analyze_concurrency_source(source, "inline.py")
    assert [f.rule for f in findings] == ["TPU403"]


def test_annotated_manifest_is_read():
    """`TPULINT_LOCK_ORDER: dict = {...}` (an AnnAssign) must work like the
    bare assignment — regression: the annotated form was silently dropped,
    downgrading the scope to cycles-only while the runtime sanitizer still
    imported the manifest (the exact static/dynamic divergence the shared
    declaration exists to prevent)."""
    from mlops_tpu.analysis import analyze_concurrency_source

    source = (
        "import threading\n"
        'TPULINT_LOCK_ORDER: dict = {"C": ("_a", "_b")}\n'
        "class C:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def inverted(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n"
    )
    findings = analyze_concurrency_source(source, "inline.py")
    assert [f.rule for f in findings] == ["TPU401"]


# ------------------------------------------------------------ Layer 4
CONTRACT_FIXTURES = FIXTURES / "contracts"
# Exact planted counts per contract rule — the precision net in both
# directions, same contract as CONCURRENCY_COUNTS above.
CONTRACT_COUNTS = {"TPU501": 5, "TPU502": 3, "TPU503": 1, "TPU504": 2}


@pytest.mark.parametrize(
    "name",
    ["shm_ownership", "series_parity", "dead_knob", "fault_points"],
)
def test_each_planted_contract_violation_fires_at_its_line(name):
    from mlops_tpu.analysis import analyze_contracts_source

    path = CONTRACT_FIXTURES / f"{name}.py"
    planted = _planted(path)
    assert planted, f"fixture {name} has no PLANT markers"
    found = {
        (f.line, f.rule)
        for f in analyze_contracts_source(path.read_text(), path)
    }
    assert planted <= found, f"missed: {planted - found}"
    extra = {(ln, r) for ln, r in found if (ln, r) not in planted}
    assert not extra, f"unexpected findings: {extra}"


def test_contract_fixture_counts_pinned():
    """Exact per-rule counts over the contracts dir analyzed as ONE
    project — including the alert-rules yml, whose typo'd series
    reference must land on its planted line — and the CLI detects all of
    them through `analyze --contracts`."""
    from collections import Counter

    from mlops_tpu.analysis import analyze_contracts_paths
    from mlops_tpu.cli import main

    findings = analyze_contracts_paths([CONTRACT_FIXTURES])
    assert dict(Counter(f.rule for f in findings)) == CONTRACT_COUNTS
    planted = {
        (path.as_posix(), lineno, rule)
        for path in sorted(CONTRACT_FIXTURES.iterdir())
        for lineno, rule in _planted(path)
    }
    found = {(f.path, f.line, f.rule) for f in findings}
    assert found == planted
    assert (
        main(["analyze", "--no-trace", "--contracts",
              str(CONTRACT_FIXTURES)])
        == 1
    )


def test_contract_layer_requires_flag():
    """Without --contracts the fixtures raise no TPU50x findings (the
    planted files are Layer-1 clean by construction)."""
    from mlops_tpu.cli import main

    assert main(["analyze", "--no-trace", str(CONTRACT_FIXTURES)]) == 0


def test_contract_rules_respect_suppressions():
    from mlops_tpu.analysis import analyze_contracts_source

    source = (
        'POINTS = {"a.b": "x"}\n'
        "def f():\n"
        '    fire("a.c")  # tpulint: disable=TPU504\n'
        '    return fire("a.b")\n'
    )
    assert analyze_contracts_source(source, "inline.py") == []
    kept = analyze_contracts_source(source, "inline.py", keep_suppressed=True)
    assert [f.rule for f in kept] == ["TPU504"]


def test_deleting_a_series_from_one_plane_fails_parity():
    """The acceptance scenario: drop one series from one renderer plane
    and TPU502 gates. Extraction is pinned by the fixtures; this pins the
    parity check against the REAL registry built from the shipped
    package."""
    from mlops_tpu.analysis.contracts import _check_series
    from mlops_tpu.analysis.seriesreg import registry_from_paths

    package = Path(__file__).parents[1] / "mlops_tpu"
    registry = registry_from_paths([package])
    assert registry is not None
    info = registry.series["mlops_tpu_requests_total"]
    assert info.planes == {"single", "ring"}
    info.planes.discard("ring")
    findings = _check_series(
        [], registry, alert_files=[], docs_file=None, extra_sources={}
    )
    assert any(
        f.rule == "TPU502" and "mlops_tpu_requests_total" in f.message
        for f in findings
    )


def test_renamed_alert_series_fails_gate(tmp_path):
    """The other acceptance scenario: rename one series in the alert
    rules and the reference-integrity check gates against the real
    registry."""
    from mlops_tpu.analysis.contracts import _check_series
    from mlops_tpu.analysis.seriesreg import registry_from_paths

    root = Path(__file__).parents[1]
    registry = registry_from_paths([root / "mlops_tpu"])
    rules = root / "configs" / "alerts" / "mlops_tpu_slo.rules.yml"
    bad = tmp_path / "rules.yml"
    bad.write_text(
        rules.read_text().replace(
            "mlops_tpu_alert_active", "mlops_tpu_alert_actve"
        )
    )
    findings = _check_series(
        [], registry, alert_files=[bad], docs_file=None, extra_sources={}
    )
    assert findings and all(f.rule == "TPU502" for f in findings)
    assert all("mlops_tpu_alert_actve" in f.message for f in findings)
    # The committed rules file itself is clean against the registry.
    assert (
        _check_series(
            [], registry, alert_files=[rules], docs_file=None,
            extra_sources={},
        )
        == []
    )


def test_contract_suppressions_count_in_ledger(tmp_path, capsys):
    """A disable covering a Layer-4 finding is LIVE in the ledger even
    though Layer 4 is cross-file: audit_paths computes the contract
    findings project-wide and slices them per file."""
    from mlops_tpu.cli import main

    mod = tmp_path / "faulty.py"
    mod.write_text(
        'POINTS = {"a.b": "x"}  # tpulint: disable=TPU504\n'
        "def f():\n"
        "    return 1\n"
    )
    assert main(["analyze", "--list-suppressions", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "faulty.py:1: disable=TPU504 [live]" in out


def test_repo_contract_gate_clean_at_head():
    """`analyze --contracts` over the shipped package exits clean: the
    shm ownership map, both metrics planes, the committed alert rules,
    the docs series table, every config knob and every fault point hold
    at HEAD."""
    from mlops_tpu.cli import main

    package = Path(__file__).parents[1] / "mlops_tpu"
    assert (
        main(["analyze", "--no-trace", "--contracts", str(package)]) == 0
    )


# ------------------------------------------------------------ Layer 5
ASYNC_FIXTURES = FIXTURES / "asyncio"
# Exact planted counts per async-discipline rule — the precision net in
# both directions, same contract as the Layer 3/4 count pins above.
ASYNC_COUNTS = {"TPU601": 9, "TPU602": 3, "TPU603": 2, "TPU604": 2}


@pytest.mark.parametrize(
    "name",
    [
        "blocking_in_coroutine",
        "fire_and_forget",
        "cross_thread_write",
        "await_under_lock",
    ],
)
def test_each_planted_async_violation_fires_at_its_line(name):
    from mlops_tpu.analysis import analyze_async_source

    path = ASYNC_FIXTURES / f"{name}.py"
    planted = _planted(path)
    assert planted, f"fixture {name} has no PLANT markers"
    found = {
        (f.line, f.rule)
        for f in analyze_async_source(path.read_text(), path)
    }
    assert planted <= found, f"missed: {planted - found}"
    extra = {(ln, r) for ln, r in found if (ln, r) not in planted}
    assert not extra, f"unexpected findings: {extra}"


def test_async_fixture_counts_pinned():
    """Exact per-rule counts over the asyncio dir analyzed as ONE project
    (cross-file confinement must not add or lose findings versus the
    per-file runs) — and the CLI detects all of them through
    `analyze --async`."""
    from collections import Counter

    from mlops_tpu.analysis import analyze_async_paths
    from mlops_tpu.cli import main

    findings = analyze_async_paths([ASYNC_FIXTURES])
    assert dict(Counter(f.rule for f in findings)) == ASYNC_COUNTS
    planted = {
        (path.as_posix(), lineno, rule)
        for path in sorted(ASYNC_FIXTURES.iterdir())
        for lineno, rule in _planted(path)
    }
    found = {(f.path, f.line, f.rule) for f in findings}
    assert found == planted
    assert (
        main(["analyze", "--no-trace", "--async", str(ASYNC_FIXTURES)])
        == 1
    )


def test_async_layer_requires_flag():
    """Without --async the fixtures raise no TPU60x findings (the planted
    files are Layer-1 clean by construction)."""
    from mlops_tpu.cli import main

    assert main(["analyze", "--no-trace", str(ASYNC_FIXTURES)]) == 0


def test_async_rules_respect_suppressions():
    from mlops_tpu.analysis import analyze_async_source

    source = (
        "import time\n"
        "async def tick():\n"
        "    time.sleep(0.1)  # tpulint: disable=TPU601\n"
    )
    assert analyze_async_source(source, "inline.py") == []
    kept = analyze_async_source(source, "inline.py", keep_suppressed=True)
    assert [f.rule for f in kept] == ["TPU601"]


def test_async_suppressions_count_in_ledger(tmp_path, capsys):
    """A disable covering a Layer-5 finding is LIVE in the ledger even
    though Layer 5 is cross-file: audit_paths computes the async findings
    project-wide and slices them per file, exactly like Layer 4's."""
    from mlops_tpu.cli import main

    mod = tmp_path / "looped.py"
    mod.write_text(
        "import time\n"
        "async def tick():\n"
        "    time.sleep(0.1)  # tpulint: disable=TPU601\n"
    )
    assert main(["analyze", "--list-suppressions", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "looped.py:3: disable=TPU601 [live]" in out


def test_repo_async_gate_clean_at_head():
    """`analyze --async` over the shipped package exits clean: the serve
    plane's executor-offload discipline (every blocking call rides
    run_in_executor, no fire-and-forget tasks, no unmarshalled
    cross-thread writes, no await under a sync mutex) holds at HEAD."""
    from mlops_tpu.analysis import analyze_async_paths
    from mlops_tpu.cli import main

    package = Path(__file__).parents[1] / "mlops_tpu"
    assert analyze_async_paths([package]) == []
    assert main(["analyze", "--no-trace", "--async", str(package)]) == 0


def test_removing_executor_offload_yields_one_tpu601():
    """The mutation scenario: strip ONE executor offload from the serve
    plane in memory (the monitor fetch — the exact /metrics-wedging bug
    class Layer 5 exists for) and the gate must produce exactly one
    TPU601 at the de-offloaded call."""
    import re as _re

    from mlops_tpu.analysis import analyze_async_source

    server_py = (
        Path(__file__).parents[1] / "mlops_tpu" / "serve" / "server.py"
    )
    source = server_py.read_text()
    assert analyze_async_source(source, server_py) == []
    pattern = (
        r"await loop\.run_in_executor\(\s*"
        r"self\._executor, eng\.monitor_snapshot\s*\)"
    )
    mutated, n = _re.subn(
        pattern,
        "jax.device_get(eng.monitor_snapshot())",
        source,
    )
    assert n == 1, "the monitor-fetch offload moved; update the pattern"
    findings = analyze_async_source(mutated, server_py)
    assert [f.rule for f in findings] == ["TPU601"]
    assert "jax.device_get()" in findings[0].message


# ------------------------------------------- suppression ledger (TPU400)
def test_list_suppressions_reports_live_and_stale(tmp_path, capsys):
    from mlops_tpu.cli import main

    live = tmp_path / "live.py"
    live.write_text(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.tolist()  # tpulint: disable=TPU101\n"
    )
    stale = tmp_path / "stale.py"
    stale.write_text(
        "def g(x):\n"
        "    return x  # tpulint: disable=TPU101\n"
    )
    assert main(["analyze", "--list-suppressions", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "live.py:4: disable=TPU101 [live]" in out
    assert "stale.py:2: disable=TPU101 [STALE]" in out
    assert "2 suppression(s), 1 stale" in out
    # --fail-stale flips the exit code in list mode...
    assert (
        main(["analyze", "--list-suppressions", "--fail-stale",
              str(tmp_path)])
        == 1
    )
    capsys.readouterr()
    # ...and in gate mode the stale comment is a TPU400 finding that a
    # disable comment can NOT silence (it must not hide its own report).
    assert main(["analyze", "--no-trace", "--fail-stale", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "TPU400" in out and "stale.py:2" in out


def test_suppression_examples_in_docstrings_are_not_counted(tmp_path, capsys):
    """The audit reads real COMMENT tokens (tokenize): the disable syntax
    quoted inside a docstring is documentation, not a suppression."""
    from mlops_tpu.cli import main

    doc = tmp_path / "doc.py"
    doc.write_text(
        '"""Suppress with ``# tpulint: disable=TPU101`` on the line."""\n'
        "X = 1\n"
    )
    assert main(["analyze", "--list-suppressions", str(tmp_path)]) == 0
    assert "0 suppression(s), 0 stale" in capsys.readouterr().out


def test_untokenizable_file_does_not_crash_the_audit(tmp_path, capsys):
    """A file tokenize rejects (unterminated triple-quote, bad dedent) must
    degrade to 'nothing to audit' — Layer 1 owns the syntax-error report.
    Regression: the except clause once named the nonexistent
    ``tokenize.TokenizeError``, so any such file killed the whole
    ``--fail-stale`` gate with an AttributeError (exit 2)."""
    from mlops_tpu.cli import main

    bad = tmp_path / "bad.py"
    bad.write_text("x = '''unterminated\n")
    dedent = tmp_path / "dedent.py"
    dedent.write_text("def f():\n        x = 1\n    return x\n")
    assert main(["analyze", "--list-suppressions", str(tmp_path)]) == 0
    assert "0 suppression(s), 0 stale" in capsys.readouterr().out
    # Gate mode still reports the syntax errors (Layer 1 TPU000), exit 1
    # not an internal-failure exit 2.
    assert main(["analyze", "--no-trace", "--fail-stale", str(tmp_path)]) == 1
    assert "TPU000" in capsys.readouterr().out


def test_package_suppressions_all_live():
    """The PR 1/3/4 disables stay honest: every suppression in the shipped
    package still suppresses a real finding (the CI --fail-stale gate)."""
    from mlops_tpu.analysis.suppressions import audit_paths

    package = Path(__file__).parents[1] / "mlops_tpu"
    stale = [
        s.describe()
        for s in audit_paths([package])
        if not s.live and not s.skipped_file
    ]
    assert stale == []


# ------------------------------------------------- runtime lock sanitizer
def test_lockcheck_detects_declared_order_inversion():
    import threading

    from mlops_tpu.analysis.lockcheck import LockSanitizer

    san = LockSanitizer(order=("a", "b"))
    a = san.wrap(threading.Lock(), "a")
    b = san.wrap(threading.Lock(), "b")
    with a:
        with b:
            pass
    assert san.violations == []
    with b:
        with a:
            pass
    assert len(san.violations) == 1
    v = san.violations[0]
    assert (v.acquiring, v.holding) == ("a", ("b",))
    assert "inverts the declared order" in str(v)


def test_lockcheck_flags_undeclared_lock_in_nesting():
    import threading

    from mlops_tpu.analysis.lockcheck import LockSanitizer

    san = LockSanitizer(order=("a",))
    a = san.wrap(threading.Lock(), "a")
    rogue = san.wrap(threading.Lock(), "rogue")
    with a:
        with rogue:
            pass
    assert len(san.violations) == 1
    assert "not in the declared order" in san.violations[0].note


def test_lockcheck_accounts_contended_wait():
    import threading

    from mlops_tpu.analysis.lockcheck import LockSanitizer

    san = LockSanitizer()
    lock = san.wrap(threading.Lock(), "l")
    started = threading.Event()

    def holder():
        with lock:
            started.set()
            import time

            time.sleep(0.05)

    t = threading.Thread(target=holder)
    t.start()
    started.wait()
    with lock:
        pass
    t.join()
    assert san.total_wait_s >= 0.02
    assert san.acquired["l"] == 2
    assert san.violations == []


def test_lockcheck_cross_thread_semaphore_release():
    """A permit acquired on one thread and released on another (the
    two-phase dispatch/fetch handoff) must be popped from the ACQUIRER's
    held stack — regression: the stale entry manufactured bogus order
    violations on every later acquisition and grew the stack forever."""
    import threading

    from mlops_tpu.analysis.lockcheck import LockSanitizer

    san = LockSanitizer(order=("lock", "sem"))
    sem = san.wrap(threading.Semaphore(2), "sem")
    lock = san.wrap(threading.Lock(), "lock")
    sem.acquire()
    t = threading.Thread(target=sem.release)
    t.start()
    t.join()
    with lock:  # must NOT report "lock after sem" — sem was handed back
        pass
    assert san.violations == [], [str(v) for v in san.violations]
    assert san._stacks[threading.get_ident()] == []


def test_instrument_locks_skips_asyncio_primitives():
    """asyncio locks/semaphores duck-type acquire/release but acquire() is
    a coroutine — a sync wrapper would return it un-awaited (truthy!) and
    the permit count would never move, silently unbounding the batcher's
    rings. They must not be swapped."""
    import asyncio
    import threading

    from mlops_tpu.analysis.lockcheck import (
        InstrumentedLock,
        instrument_locks,
    )

    class Mixed:
        def __init__(self):
            self._ring = asyncio.Semaphore(2)
            self._mutex = threading.Lock()

    obj = Mixed()
    ring = obj._ring
    with instrument_locks(obj):
        assert obj._ring is ring  # untouched
        assert isinstance(obj._mutex, InstrumentedLock)


def test_instrument_locks_swaps_and_restores(warm_engine):
    import threading

    from mlops_tpu.analysis.lockcheck import (
        InstrumentedLock,
        instrument_locks,
    )

    original = warm_engine._acc_lock
    with instrument_locks(warm_engine) as san:
        assert isinstance(warm_engine._acc_lock, InstrumentedLock)
        assert isinstance(warm_engine._compile_lock, InstrumentedLock)
        warm_engine.monitor_snapshot()
        assert san.acquired.get("_acc_lock", 0) >= 1
        assert san.violations == []
    assert warm_engine._acc_lock is original
    assert isinstance(original, type(threading.Lock()))


# ------------------------------------------- runtime loop-lag sanitizer
def test_loopcheck_times_slow_callback_with_attribution():
    """A coroutine that blocks the loop is timed with its qualname — the
    runtime counterpart of TPU601."""
    import asyncio
    import time

    from mlops_tpu.analysis.loopcheck import instrument_loop

    async def stall():
        time.sleep(0.03)  # deliberate: the bug class under test

    async def main(san_holder):
        loop = asyncio.get_running_loop()
        with instrument_loop(loop, slow_ms=10.0) as san:
            await asyncio.create_task(stall())
            san_holder.append(san)
        # detached: the loop's own scheduling methods are restored
        assert "call_soon" not in vars(loop)

    holder = []
    asyncio.run(main(holder))
    san = holder[0]
    assert san.max_lag_ms >= 25.0
    assert san.callbacks > 0
    slow = [r for r in san.slow if "stall" in r.label]
    assert slow and slow[0].label.startswith("task:")
    assert "held the event loop" in str(slow[0])
    assert slow[0].schedule_site  # capture_stacks defaults on here


def test_loopcheck_assert_max_lag_and_window_reset():
    import asyncio
    import time

    from mlops_tpu.analysis.loopcheck import LoopLagSanitizer

    san = LoopLagSanitizer(slow_ms=10.0)

    async def main():
        loop = asyncio.get_running_loop()
        san.attach(loop)
        try:
            await asyncio.sleep(0)
            time.sleep(0.02)  # rides the coroutine step: seen as lag
            await asyncio.sleep(0)
        finally:
            san.detach()

    asyncio.run(main())
    # Gauge semantics: the first snapshot drains the window's max, a
    # quiet window then reads 0.0 — while the all-time max still gates.
    assert san.snapshot_ms() >= 15.0
    assert san.snapshot_ms() == 0.0
    san.assert_max_lag(1000.0)  # under the bar: no raise
    with pytest.raises(AssertionError) as err:
        san.assert_max_lag(10.0)
    assert "event-loop lag" in str(err.value)
    assert "held the event loop" in str(err.value)


def test_loopcheck_attach_is_exclusive_and_detach_idempotent():
    import asyncio

    from mlops_tpu.analysis.loopcheck import LoopLagSanitizer

    san = LoopLagSanitizer()

    async def main():
        loop = asyncio.get_running_loop()
        san.attach(loop)
        with pytest.raises(RuntimeError):
            san.attach(loop)
        san.detach()
        san.detach()  # no-op, like lockcheck's restore
        assert "call_soon" not in vars(loop)
        assert "call_later" not in vars(loop)

    asyncio.run(main())


def test_loopcheck_seeded_perturbation_is_deterministic():
    """The SchedulePerturber discipline from lockcheck: a seeded
    perturbation shifts the interleaving without changing results —
    the same seed replays the same schedule, and the workload's output
    stays bit-identical to the unperturbed run."""
    import asyncio

    from mlops_tpu.analysis.loopcheck import instrument_loop

    async def workload():
        out = []

        async def step(i):
            await asyncio.sleep(0)
            out.append(i)

        await asyncio.gather(*(step(i) for i in range(8)))
        return out

    def run(seed):
        async def main():
            loop = asyncio.get_running_loop()
            with instrument_loop(
                loop, slow_ms=1000.0, perturb_seed=seed
            ) as san:
                result = await workload()
            return result, san.callbacks

        return asyncio.run(main())

    baseline = asyncio.run(workload())
    r7a, calls7a = run(7)
    r7b, calls7b = run(7)
    assert r7a == r7b == baseline
    assert calls7a == calls7b > 0


# ------------------------------------------------------------ Layer 2
def test_trace_layer_clean_on_registered_entry_points():
    """The acceptance gate: every registered entry point traces abstractly
    (no device execution) and raises no findings on the real framework."""
    from mlops_tpu.analysis.traces import run_trace_checks

    findings, notes = run_trace_checks()
    assert findings == [], [f.format() for f in findings]
    traced = [n for n in notes if n.startswith("traced ")]
    # conftest forces an 8-device mesh, so nothing may be skipped. 9 =
    # dense + TP train steps, exact packed solo/group, quant packed
    # solo/group (ISSUE 17), gbm packed solo/group (ISSUE 19), bulk
    # chunk.
    assert len(traced) == 9, notes
    assert any("serve-predict-quant-packed" in n for n in traced)
    assert any("serve-predict-quant-group-packed" in n for n in traced)
    assert any("serve-predict-gbm-packed" in n for n in traced)
    assert any("serve-predict-gbm-group-packed" in n for n in traced)
    assert all("no device code executed" in n for n in traced)


def test_float64_leak_detected():
    from mlops_tpu.analysis.traces import check_dtypes

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(lambda x: jnp.sin(x) * 2.0)(
            jax.ShapeDtypeStruct((4,), jnp.float64)
        )
    findings = check_dtypes("fixture", 4, jaxpr)
    assert any(f.rule == "TPU301" for f in findings)


def test_convert_round_trip_detected():
    from mlops_tpu.analysis.traces import check_dtypes

    def roundtrip(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) + 1.0

    jaxpr = jax.make_jaxpr(roundtrip)(jax.ShapeDtypeStruct((4,), jnp.float32))
    findings = check_dtypes("fixture", 4, jaxpr)
    assert any(f.rule == "TPU303" for f in findings)


def test_weak_type_output_detected():
    from mlops_tpu.analysis.traces import check_weak_types

    def weak_out(x):
        return x.sum(), jnp.asarray(1.0) * 2.0  # second output weak f32

    jaxpr = jax.make_jaxpr(weak_out)(jax.ShapeDtypeStruct((4,), jnp.float32))
    findings = check_weak_types("fixture", 4, jaxpr)
    assert any(f.rule == "TPU302" for f in findings), [
        (a, getattr(a, "weak_type", None)) for a in jaxpr.out_avals
    ]


def test_bucket_polymorphism_detected_and_families_respected():
    from mlops_tpu.analysis.traces import check_bucket_stability

    def polymorphic(x):
        # Different program per size: the shape branch changes the ops.
        if x.shape[0] <= 4:
            return jnp.sort(x)
        return x * 2.0

    jaxprs = {
        n: jax.make_jaxpr(polymorphic)(jax.ShapeDtypeStruct((n,), jnp.float32))
        for n in (2, 8)
    }
    assert any(
        f.rule == "TPU304" for f in check_bucket_stability("fixture", jaxprs)
    )
    # The same divergence DECLARED as two families passes.
    assert (
        check_bucket_stability("fixture", jaxprs, families=((2,), (8,))) == []
    )


def test_sharding_link_mismatch_detected():
    from jax.sharding import PartitionSpec as P

    from mlops_tpu.analysis.traces import (
        EntryPoint,
        ShardingLink,
        check_sharding_links,
    )

    entries = {
        "producer": EntryPoint(
            name="producer",
            build=lambda: None,
            params_out_spec={"w": P("model", None)},
        ),
        "consumer": EntryPoint(
            name="consumer",
            build=lambda: None,
            params_in_spec={"w": P()},
        ),
    }
    links = [ShardingLink("producer", "consumer")]
    findings = check_sharding_links(entries, links)
    assert [f.rule for f in findings] == ["TPU305"]
    # Matching specs pass.
    entries["consumer"].params_in_spec = {"w": P("model", None)}
    assert check_sharding_links(entries, links) == []


# ------------------------------------------------------------ CLI gate
def test_cli_analyze_nonzero_on_fixtures_and_zero_on_package(capsys):
    from mlops_tpu.cli import main

    assert main(["analyze", "--no-trace", str(FIXTURES)]) == 1
    out = capsys.readouterr().out
    assert "TPU101" in out and "gating" in out

    package = Path(__file__).parents[1] / "mlops_tpu"
    assert main(["analyze", "--no-trace", "--strict", str(package)]) == 0
    # The CI gate shape minus the (slow) trace layer: concurrency rules,
    # the async/event-loop rules, and the stale-suppression audit are
    # clean on the shipped package.
    assert (
        main(
            ["analyze", "--no-trace", "--strict", "--concurrency",
             "--async", "--fail-stale", str(package)]
        )
        == 0
    )


@pytest.mark.slow
def test_cli_analyze_full_gate(capsys):
    """`mlops-tpu analyze --strict --concurrency --contracts --async
    --fail-stale mlops_tpu/` — the exact CI invocation — exits 0 with
    every entry point traced."""
    from mlops_tpu.cli import main

    package = Path(__file__).parents[1] / "mlops_tpu"
    assert (
        main(
            ["analyze", "--strict", "--concurrency", "--contracts",
             "--async", "--fail-stale", str(package)]
        )
        == 0
    )
    out = capsys.readouterr().out
    # One note per registered entry point (analysis/entrypoints.py) —
    # keep in lockstep with the trace-layer test's count above.
    assert out.count("traced ") == 9


def test_rule_catalog_documented():
    """Every rule ID (all five layers + the suppression audit) appears in
    docs/static-analysis.md."""
    from mlops_tpu.analysis import (
        ASYNC_RULES,
        CONCURRENCY_RULES,
        CONTRACT_RULES,
    )
    from mlops_tpu.analysis.suppressions import STALE_RULE
    from mlops_tpu.analysis.traces import TRACE_RULES

    doc = (Path(__file__).parents[1] / "docs" / "static-analysis.md").read_text()
    for rule in [
        *RULES, *CONCURRENCY_RULES, *CONTRACT_RULES, *ASYNC_RULES,
        STALE_RULE, *TRACE_RULES,
    ]:
        assert rule in doc, f"{rule} missing from docs/static-analysis.md"
