"""Tests for the analysis subsystem (docs/ANALYSIS.md).

Three layers:

* **corpus** — every seeded bug in ``tests/analysis_corpus/`` must be
  flagged with the right check ID at the right file:line, and each
  known-good twin must stay silent (the checkers' own regression
  fence);
* **lockgraph** — the AB/BA inversion is caught at acquire time with
  the full cycle in the error, Condition-wait composes, and the
  make_lock seam actually wires TrackedLock into the threaded classes
  under ``THEANOMPI_TPU_LOCKCHECK=1`` (which tests/conftest.py sets);
* **repo gate** — ``tmlint --gate`` on this repo with the committed
  baseline is green, and stays under its runtime budget.
"""

from __future__ import annotations

import os
import threading
import time

import pytest

from theanompi_tpu.analysis import donation, guarded_by, jit_hygiene, \
    site_coverage
from theanompi_tpu.analysis.cli import main as tmlint_main, run_checks
from theanompi_tpu.analysis.common import (
    SourceFile,
    load_baseline,
    split_by_baseline,
)
from theanompi_tpu.analysis.lockgraph import (
    LockGraph,
    LockOrderError,
    TrackedLock,
    make_condition,
    make_lock,
)

CORPUS = os.path.join(os.path.dirname(__file__), "analysis_corpus")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def corpus_file(name: str) -> SourceFile:
    return SourceFile(os.path.join(CORPUS, name), f"corpus/{name}")


def seeded_lines(name: str, check_id: str) -> list[int]:
    with open(os.path.join(CORPUS, name)) as f:
        return [i for i, line in enumerate(f, start=1)
                if f"SEED: {check_id}" in line]


def lines_of(findings, check_id):
    return sorted(f.line for f in findings if f.check_id == check_id)


# ---------------------------------------------------------------------------
# Corpus: TM101 guarded-by
# ---------------------------------------------------------------------------


def test_guarded_by_flags_every_seeded_bug():
    findings = guarded_by.run([corpus_file("guarded_bad.py")])
    assert {f.check_id for f in findings} == {"TM101"}
    assert lines_of(findings, "TM101") == \
        seeded_lines("guarded_bad.py", "TM101")
    # file:line and stable key both carried
    f0 = findings[0]
    assert f0.path == "corpus/guarded_bad.py" and f0.key.startswith(
        "TM101:corpus/guarded_bad.py:")


def test_guarded_by_silent_on_good_twin():
    assert guarded_by.run([corpus_file("guarded_good.py")]) == []


# ---------------------------------------------------------------------------
# Corpus: TM201 donation
# ---------------------------------------------------------------------------


def test_donation_flags_every_seeded_bug():
    src = corpus_file("donation_bad.py")
    findings = donation.run([src])
    assert {f.check_id for f in findings} == {"TM201"}
    assert lines_of(findings, "TM201") == \
        seeded_lines("donation_bad.py", "TM201")


def test_donation_silent_on_good_twin():
    # registry includes the bad file's donating fns: same names, so the
    # good twin proves the DATAFLOW exonerates, not a registry miss
    reg = donation.build_registry([corpus_file("donation_bad.py"),
                                   corpus_file("donation_good.py")])
    assert reg.get("update") == (0,)
    # the explicit no-donate spec donate_argnums=() must NOT register
    assert "keep_step" not in reg
    assert donation.run([corpus_file("donation_good.py")],
                        registry=reg) == []


# ---------------------------------------------------------------------------
# Corpus: TM301/TM302 jit hygiene + pickle
# ---------------------------------------------------------------------------


def test_jit_hygiene_flags_every_seeded_bug():
    findings = jit_hygiene.run([corpus_file("jit_bad.py")])
    assert lines_of(findings, "TM301") == \
        seeded_lines("jit_bad.py", "TM301")
    assert lines_of(findings, "TM302") == \
        seeded_lines("jit_bad.py", "TM302")


def test_jit_hygiene_silent_on_good_twin():
    assert jit_hygiene.run([corpus_file("jit_good.py")]) == []


# ---------------------------------------------------------------------------
# Corpus: TM401–TM404 site coverage
# ---------------------------------------------------------------------------


def test_site_coverage_all_four_directions():
    code = corpus_file("coverage_code.py")
    doc = os.path.join(CORPUS, "coverage_docs.md")
    findings = site_coverage.run([code], doc, "corpus/coverage_docs.md")
    by_id = {f.check_id: f for f in findings}
    assert set(by_id) == {"TM401", "TM402", "TM403", "TM404"}
    # code-side findings land at the seeded code lines...
    assert by_id["TM401"].line == \
        seeded_lines("coverage_code.py", "TM401")[0]
    assert by_id["TM403"].line == \
        seeded_lines("coverage_code.py", "TM403")[0]
    # ...docs-side findings at the stale docs rows
    assert by_id["TM402"].path == "corpus/coverage_docs.md"
    assert "beta" in by_id["TM402"].message
    assert by_id["TM404"].path == "corpus/coverage_docs.md"
    assert "corpus/ghost_total" in by_id["TM404"].message


def test_inventory_reflects_repo_emissions():
    from theanompi_tpu.analysis.common import iter_source_files

    files = list(iter_source_files(
        os.path.join(REPO, "theanompi_tpu"), REPO))
    names = {e.name for e in site_coverage.collect_metrics(files)}
    # spot-pin a few series every subsystem owns
    assert {"step_ms", "serving/request_ms", "service/wire_bytes_pre",
            "resilience/worker_restarts_total"} <= names
    sites = {f.site for f in site_coverage.collect_fires(files)}
    assert {"worker_step", "service_call", "exchange", "checkpoint",
            "serve_step", "serve_rpc", "decode_step", "ingest_batch",
            "ingest_pull", "router_route", "page_migrate"} == sites


# ---------------------------------------------------------------------------
# Lockgraph: runtime lock-order detection
# ---------------------------------------------------------------------------


def test_lock_inversion_caught_with_full_cycle():
    """The acceptance inversion: thread 1 takes A then B, thread 2
    takes B then A — thread 2's acquire of A must raise with the whole
    cycle, BEFORE blocking (no deadlock, no timeout)."""
    g = LockGraph()
    lock_a = TrackedLock("site.A", graph=g)
    lock_b = TrackedLock("site.B", graph=g)

    def order_ab():
        with lock_a:
            with lock_b:
                pass

    t1 = threading.Thread(target=order_ab)
    t1.start()
    t1.join(5)

    errs: list[BaseException] = []

    def order_ba():
        try:
            with lock_b:
                with lock_a:
                    pass
        except LockOrderError as e:
            errs.append(e)

    t2 = threading.Thread(target=order_ba)
    t2.start()
    t2.join(5)
    assert not t2.is_alive(), "inversion deadlocked instead of raising"
    assert errs, "AB/BA inversion was not detected"
    msg = str(errs[0])
    assert "cycle" in msg and "site.A" in msg and "site.B" in msg
    # the full cycle chain is spelled out
    assert "site.B -> site.A -> site.B" in msg \
        or "site.A -> site.B -> site.A" in msg


def test_consistent_order_never_raises():
    g = LockGraph()
    lock_a = TrackedLock("c.A", graph=g)
    lock_b = TrackedLock("c.B", graph=g)
    for _ in range(3):
        with lock_a:
            with lock_b:
                pass
    assert g.edges() == {"c.A": {"c.B"}}


def test_same_thread_reacquire_raises():
    lock = TrackedLock("r.lock", graph=LockGraph())
    with lock:
        with pytest.raises(LockOrderError, match="re-acquire"):
            lock.acquire()
    # and the lock still works afterwards
    with lock:
        pass


def test_same_site_distinct_instances_nest_freely():
    """Two locks constructed at the same site (two batcher replicas)
    are distinct objects: nesting them is legal and must neither raise
    nor corrupt the held stack."""
    g = LockGraph()
    rep_a = TrackedLock("dup.site", graph=g)
    rep_b = TrackedLock("dup.site", graph=g)
    other = TrackedLock("dup.other", graph=g)
    with rep_a:
        with rep_b:
            with other:
                pass
    # stack bookkeeping survived: a fresh cycle-free nesting still
    # works and the graph recorded the cross-site edge only
    with rep_a:
        with other:
            pass
    assert g.edges() == {"dup.site": {"dup.other"}}


def test_condition_wait_composes_with_tracked_lock():
    g = LockGraph()
    lock = TrackedLock("cv.lock", graph=g)
    cond = threading.Condition(lock)
    box: list[int] = []

    def waiter():
        with cond:
            while not box:
                cond.wait(0.05)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cond:
        box.append(1)
        cond.notify_all()
    t.join(5)
    assert not t.is_alive()


def test_make_lock_seam(monkeypatch):
    monkeypatch.setenv("THEANOMPI_TPU_LOCKCHECK", "0")
    assert not isinstance(make_lock("x"), TrackedLock)
    monkeypatch.setenv("THEANOMPI_TPU_LOCKCHECK", "1")
    assert isinstance(make_lock("x"), TrackedLock)
    cond = make_condition(make_lock("y"))
    assert isinstance(cond, threading.Condition)


def test_threaded_classes_run_tracked_under_tier1():
    """conftest sets THEANOMPI_TPU_LOCKCHECK=1, so the host plane's
    locks must actually BE tracked in this suite."""
    from theanompi_tpu.resilience.supervisor import WorkerSupervisor
    from theanompi_tpu.serving.batcher import DynamicBatcher

    b = DynamicBatcher(lambda x: x)
    assert isinstance(b._lock, TrackedLock)
    sup = WorkerSupervisor(n_workers=1)
    assert isinstance(sup._lock, TrackedLock)


# ---------------------------------------------------------------------------
# Regression tests for the violations the checkers surfaced
# ---------------------------------------------------------------------------


def test_supervisor_restart_ordinal_from_under_lock():
    """TM101 fix: the backoff ordinal is returned by _handle_failure
    (computed under its lock) instead of a bare _restarts read."""
    from theanompi_tpu.resilience.supervisor import WorkerSupervisor

    sup = WorkerSupervisor(n_workers=2, max_restarts=2, min_workers=1,
                           restart_from=lambda rank: None)
    errors: list[BaseException] = []
    abort = threading.Event()
    assert sup._handle_failure(0, ValueError("x"), errors, abort) == 1
    assert sup._handle_failure(0, ValueError("x"), errors, abort) == 2
    # budget spent -> lost (returns 0), quorum still held
    assert sup._handle_failure(0, ValueError("x"), errors, abort) == 0
    assert sup.lost_workers() == [0]
    assert sup.restart_counts() == {0: 2}
    assert not abort.is_set() and errors == []


def test_batcher_alive_and_dead_rejection():
    """TM101 fix: alive reads _dead under the lock; a dead replica
    rejects immediately with Overloaded."""
    import numpy as np

    from theanompi_tpu.serving.batcher import DynamicBatcher, Overloaded

    b = DynamicBatcher(lambda x: x)
    assert b.alive
    b._mark_dead()
    assert not b.alive
    with pytest.raises(Overloaded):
        b.submit(np.zeros((1, 2), np.float32))
    assert b.stats()["alive"] is False


def test_exchange_pipe_barrier_and_sticky_error():
    """TM101 fix: outstanding/_err are lock-guarded; semantics pinned:
    double submit raises, an exchange error re-raises at collect and
    stays sticky for later submits."""
    from theanompi_tpu.rules.async_rules import _ExchangePipe

    calls: list[int] = []

    def fn(payload):
        calls.append(payload)
        if payload < 0:
            raise ValueError("boom")
        return payload * 10

    pipe = _ExchangePipe(fn, "test/exchange", worker=0)
    try:
        pipe.submit(1)
        with pytest.raises(RuntimeError, match="outstanding"):
            pipe.submit(2)
        payload, result = pipe.collect()
        assert (payload, result) == (1, 10)
        pipe.submit(-1)
        with pytest.raises(ValueError, match="boom"):
            pipe.collect()
        with pytest.raises(ValueError, match="boom"):
            pipe.submit(3)  # sticky error
    finally:
        pipe.close()
    assert calls == [1, -1]


# ---------------------------------------------------------------------------
# Thread-leak fixture
# ---------------------------------------------------------------------------


def test_leak_detector_sees_a_leak_and_clears():
    import conftest

    before = set(threading.enumerate())
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="deliberate-leak",
                         daemon=False)
    t.start()
    try:
        leaked = conftest.leaked_threads(before, grace_s=0.2)
        assert any(th.name == "deliberate-leak" for th in leaked)
    finally:
        stop.set()
        t.join(5)
    assert conftest.leaked_threads(before, grace_s=0.2) == []


# ---------------------------------------------------------------------------
# The per-phase alarm
# ---------------------------------------------------------------------------


def test_phase_limit_fails_an_unbounded_wait(request, monkeypatch):
    """A wait with no limit of its own FAILS when the phase's alarm
    rings (and keeps failing while the unwinding waits again); once the
    phase is over the alarm is off and the handler restored."""
    import signal

    import conftest

    monkeypatch.setattr(conftest, "_PHASE_LIMIT_S", 0.2)
    outer = signal.getsignal(signal.SIGALRM)
    phase = conftest._phase_limit(request.node, "call")
    next(phase)
    try:
        with pytest.raises(pytest.fail.Exception,
                           match="call still running after 0 s"):
            threading.Event().wait()
    finally:
        phase.close()
    assert signal.getsignal(signal.SIGALRM) is outer
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)


# ---------------------------------------------------------------------------
# Shm-segment-leak fixture
# ---------------------------------------------------------------------------

#: creates one lane-named segment under its own pid, says so, and lives
#: until its stdin closes (or 60 s, so a lost test leaves nothing)
_SEGMENT_HOLDER = """
import os, select, sys
name = f"tmshm_{os.getpid()}_foreign_1"
with open(os.path.join("/dev/shm", name), "wb") as f:
    f.write(b"x" * 64)
print(name, flush=True)
select.select([sys.stdin], [], [], 60)
"""


def test_segment_guard_judges_only_its_own_process_tree():
    """Six xdist workers share one /dev/shm: a live segment of ANOTHER
    process tree is neither reported nor unlinked by this process's
    fence, while this process's own and a spawned child's are."""
    import select
    import subprocess
    import sys

    import conftest
    from theanompi_tpu.parallel import shm

    def spawn(code):
        p = subprocess.Popen([sys.executable, "-c", code],
                             stdin=subprocess.PIPE,
                             stdout=subprocess.PIPE, text=True)
        assert select.select([p.stdout], [], [], 30)[0], \
            "segment holder did not report its segment within 30 s"
        return p, p.stdout.readline().strip()

    before = set(shm.segment_names())
    # an unrelated live process: the grandchild of a parent that exits
    # at once, so its ancestry no longer leads here
    launcher, foreign = spawn(
        "import subprocess, sys\n"
        f"subprocess.Popen([sys.executable, '-c', {_SEGMENT_HOLDER!r}])")
    child, childs = spawn(_SEGMENT_HOLDER)
    own = f"tmshm_{os.getpid()}_own_1"
    try:
        assert launcher.wait(30) == 0
        with open(os.path.join("/dev/shm", own), "wb") as f:
            f.write(b"x" * 64)
        assert foreign in shm.segment_names()
        leaked = conftest.leaked_segments(before, grace_s=0.2)
        assert sorted(leaked) == sorted([own, childs])
        assert foreign in shm.segment_names()  # judged AND left alone
    finally:
        for p in (launcher, child):  # stdin closes: the holders exit
            p.stdin.close()
            p.stdout.close()
        child.wait(30)
        os.unlink(os.path.join("/dev/shm", own))
        # the foreign holder is nobody's child now: once it is dead
        # its segment is an orphan and the sweep takes it
        deadline = time.monotonic() + 30
        while (foreign in shm.segment_names()
               and time.monotonic() < deadline):
            shm.sweep_orphans()
            time.sleep(0.05)
    assert conftest.leaked_segments(before, grace_s=0.2) == []
    assert foreign not in shm.segment_names()


# ---------------------------------------------------------------------------
# The repo gate itself
# ---------------------------------------------------------------------------


def test_repo_gate_green_with_committed_baseline():
    t0 = time.monotonic()
    findings = run_checks(REPO)
    dt = time.monotonic() - t0
    baseline = load_baseline(os.path.join(
        REPO, "theanompi_tpu", "analysis", "baseline.json"))
    new, stale = split_by_baseline(findings, baseline)
    assert new == [], "new findings: " + "; ".join(
        f.render() for f in new)
    assert stale == [], f"stale baseline keys: {stale}"
    assert dt < 30, f"checker suite took {dt:.1f}s (budget: 30s)"


def test_tmlint_cli_gate_exit_code():
    assert tmlint_main(["--gate", "--root", REPO]) == 0


def test_tmlint_script_gate_runs_without_jax(tmp_path):
    """tools/tmlint.py must run the gate on a box where `import jax`
    raises (broken plugin, half-installed venv): it loads the analysis
    subpackage behind a parent-package stub so theanompi_tpu/__init__
    never executes."""
    import subprocess
    import sys as _sys

    (tmp_path / "jax.py").write_text(
        'raise ImportError("poisoned jax - the gate must not import me")')
    env = dict(os.environ, PYTHONPATH=str(tmp_path))
    p = subprocess.run(
        [_sys.executable, os.path.join(REPO, "tools", "tmlint.py"),
         "--gate"],
        capture_output=True, text=True, env=env, cwd=str(tmp_path),
        timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "0 new finding(s)" in p.stdout


def test_site_coverage_suppression_covers_all_sites_of_a_name(tmp_path):
    """An inline `# lint: ok TM403` on ANY emission of a metric covers
    the metric, regardless of file-walk order (the suppression is
    about the name, not one call site)."""
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text('monitor.inc("twice/emitted_total")\n')
    b.write_text('monitor.inc("twice/emitted_total")  # lint: ok TM403\n')
    doc = tmp_path / "obs.md"
    doc.write_text("## Metric catalog\n\n| Series |\n|---|\n\n"
                   "## Fault sites\n\n| Site |\n|---|\n")
    for order in ([a, b], [b, a]):
        files = [SourceFile(str(p), p.name) for p in order]
        found = site_coverage.run(files, str(doc), "obs.md")
        assert [f for f in found if f.check_id == "TM403"] == [], order
