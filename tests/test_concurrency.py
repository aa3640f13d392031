"""Concurrency-analysis legs (tony_tpu.analysis.concurrency): the
lock-discipline lint with its '# lockfree:' blessings, the static +
witnessed lock-order graph with cycle detection (a seeded inversion is a
NAMED finding, not a hung CI job), the thread-hygiene audit, the
committed blessings baseline, the profiler's lock-witness registry — and
the genuinely multi-threaded randomized kvcache interleave: concurrent
admit/fork/write/spec/evict from N threads over one shared pool with the
refcount/free/LRU partition pinned at every quiescent point."""

from __future__ import annotations

import json
import textwrap
import threading
from pathlib import Path

import numpy as np
import pytest

from tony_tpu import profiler
from tony_tpu.analysis import concurrency as conc

pytestmark = pytest.mark.conc

REPO = Path(__file__).resolve().parent.parent


def lint(src: str, rel: str = "mod.py"):
    return conc.lint_source(textwrap.dedent(src), rel, rel)


@pytest.fixture()
def fresh_witness():
    conc.reset_witness()
    yield
    conc.reset_witness()


# ---------------------------------------------------------------------------
# Rule 1: lock discipline
# ---------------------------------------------------------------------------

class TestLockDiscipline:
    GUARDED = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []

            def add(self, x):
                with self._lock:
                    self._items.append(x)

            def drop(self):
                self._items.pop()
    """

    def test_unguarded_write_fires_with_provenance(self):
        findings, _ = lint(self.GUARDED)
        assert len(findings) == 1
        f = findings[0]
        assert (f.rule, f.kind) == ("lock_discipline", "unguarded_write")
        assert f.provenance == "C.drop._items"
        assert not f.blessed
        assert "C._lock" in f.message and ".pop()" in f.message
        assert "drop()" in f.message

    def test_lockfree_pragma_blesses_with_reason(self):
        findings, _ = lint(self.GUARDED.replace(
            "self._items.pop()",
            "# lockfree: drop() is documented driver-thread-only\n"
            "                self._items.pop()"))
        active = [f for f in findings if not f.blessed]
        blessed = [f for f in findings if f.blessed]
        assert not active
        assert len(blessed) == 1
        assert blessed[0].blessed_by == \
            "drop() is documented driver-thread-only"

    def test_bare_pragma_is_itself_a_finding(self):
        findings, _ = lint(self.GUARDED.replace(
            "self._items.pop()",
            "self._items.pop()   # lockfree:"))
        assert len(findings) == 1
        assert findings[0].kind == "bare_pragma"
        assert not findings[0].blessed

    def test_init_is_construction_not_violation(self):
        # __init__ assigns the guarded attr bare — before any
        # concurrency exists; must not fire.
        findings, _ = lint("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def bump(self):
                    with self._lock:
                        self._n += 1
        """)
        assert findings == []

    def test_closure_under_lock_is_not_guard_evidence(self):
        # The closure's body runs later (another thread, after the
        # with exited) — the lexically enclosing lock is NOT held, so
        # it neither witnesses a guard nor gets flagged.
        findings, _ = lint("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def spawn(self):
                    with self._lock:
                        def worker():
                            self._n += 1
                        return worker

                def bump(self):
                    self._n += 1
        """)
        assert findings == []

    def test_helper_lock_method_counts_as_guard(self):
        # ``with self._part_lock(key):`` — a per-key lock table behind
        # a helper (the TpuVmScheduler staging idiom).
        findings, _ = lint("""
            import threading

            class C:
                def __init__(self):
                    self._staged = set()

                def _part_lock(self, key):
                    return threading.Lock()

                def stage(self, key):
                    with self._part_lock(key):
                        self._staged.add(key)

                def unstage(self, key):
                    self._staged.discard(key)
        """)
        assert len(findings) == 1
        assert findings[0].kind == "unguarded_write"
        assert findings[0].provenance == "C.unstage._staged"
        assert "_part_lock()" in findings[0].message

    def test_subclass_mutation_of_base_guarded_attr_fires(self):
        # Same-file inheritance: the base declares the lock and the
        # guard discipline; a subclass method that forgets the lock is
        # exactly the drift the pass exists to catch (the SpecEngine/
        # ServeEngine-style hierarchy).
        findings, _ = lint("""
            import threading

            class Base:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._events = []

                def add(self, x):
                    with self._lock:
                        self._events.append(x)

            class Sub(Base):
                def drain(self):
                    self._events.clear()
        """)
        assert len(findings) == 1
        assert findings[0].provenance == "Sub.drain._events"

    def test_subclass_with_over_base_lock_is_guard_evidence(self):
        # The subclass holds the BASE-declared lock: that's a real hold
        # (and real guard evidence), not an unknown context manager.
        findings, _ = lint("""
            import threading

            class Base:
                def __init__(self):
                    self._lock = threading.Lock()

            class Sub(Base):
                def add(self, x):
                    with self._lock:
                        self._items.append(x)

                def drain(self):
                    self._items.clear()
        """)
        assert len(findings) == 1
        assert findings[0].provenance == "Sub.drain._items"

    def test_augassign_subscript_and_del_count_as_mutations(self):
        findings, _ = lint("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._m = {}

                def put(self, k, v):
                    with self._lock:
                        self._m[k] = v

                def evict(self, k):
                    del self._m[k]
        """)
        assert len(findings) == 1
        assert findings[0].provenance == "C.evict._m"

    def test_reads_are_not_flagged(self):
        findings, _ = lint("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []

                def add(self, x):
                    with self._lock:
                        self._items.append(x)

                def peek(self):
                    return len(self._items)
        """)
        assert findings == []

    def test_engine_events_ring_is_guarded_at_head(self):
        # Regression pin for the race this PR fixed: the stats
        # publisher thread iterates ServeEngine._events while the drive
        # thread appends — both sides now hold ServeEngine._lock, and
        # the pass must SEE that (the guarded-elsewhere inference is
        # what would catch the next drift).
        import ast

        src = (REPO / "tony_tpu" / "serve" / "engine.py").read_text()
        cls = next(n for n in ast.walk(ast.parse(src))
                   if isinstance(n, ast.ClassDef)
                   and n.name == "ServeEngine")
        scan = conc._scan_class(cls, "serve/engine.py")
        assert "_events" in scan.guarded
        assert scan.guarded["_events"][0] == "_lock"

    def test_ckpt_writer_error_slot_is_guarded_at_head(self):
        # Same pin for AsyncCheckpointer._err: the writer thread banks,
        # the caller swap-reads — both under _err_lock since this PR.
        import ast

        src = (REPO / "tony_tpu" / "ckpt" / "snapshot.py").read_text()
        cls = next(n for n in ast.walk(ast.parse(src))
                   if isinstance(n, ast.ClassDef)
                   and n.name == "AsyncCheckpointer")
        scan = conc._scan_class(cls, "ckpt/snapshot.py")
        assert "_err" in scan.guarded
        assert scan.guarded["_err"][0] == "_err_lock"


# ---------------------------------------------------------------------------
# Rule 2: lock order (static graph + cycle detection)
# ---------------------------------------------------------------------------

class TestStaticLockOrder:
    def test_nested_with_extracts_edges(self):
        _, edges = lint("""
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def ab(self):
                    with self._a:
                        with self._b:
                            pass
        """, rel="m.py")
        assert [(s, d) for s, d, _ in edges] == [("C._a", "C._b")]
        assert edges[0][2].startswith("m.py:")

    def test_multi_item_with_orders_left_to_right(self):
        _, edges = lint("""
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def ab(self):
                    with self._a, self._b:
                        pass
        """)
        assert [(s, d) for s, d, _ in edges] == [("C._a", "C._b")]

    def test_cycle_named_with_both_sites(self):
        edges = [("C._a", "C._b", "m.py:10"), ("C._b", "C._a", "m.py:20")]
        findings = conc.check_lock_order(edges, observed=[])
        assert len(findings) == 1
        f = findings[0]
        assert (f.rule, f.kind) == ("lock_order", "inversion")
        assert f.provenance == "C._a -> C._b -> C._a"
        assert "m.py:10" in f.message and "m.py:20" in f.message

    def test_consistent_order_is_clean(self):
        edges = [("A", "B", "x:1"), ("B", "C", "x:2"), ("A", "C", "x:3")]
        assert conc.check_lock_order(edges, observed=[]) == []

    def test_find_cycles_dedups_rotations(self):
        cycles = conc.find_cycles([("a", "b"), ("b", "c"), ("c", "a")])
        assert cycles == [["a", "b", "c", "a"]]


# ---------------------------------------------------------------------------
# The runtime witness
# ---------------------------------------------------------------------------

class TestWitness:
    def test_nested_acquire_records_edge_and_banks(self, fresh_witness):
        a, b = conc.Lock("w.a"), conc.Lock("w.b")
        with a:
            with b:
                pass
        edges = conc.observed_edges()
        assert [(e["src"], e["dst"]) for e in edges] == [("w.a", "w.b")]
        assert edges[0]["count"] == 1
        assert edges[0]["threads"] == [threading.current_thread().name]
        assert "test_concurrency" in edges[0]["where"]
        rec = profiler.report("locks")["witness"]
        assert [(e["src"], e["dst"]) for e in rec["edges"]] \
            == [("w.a", "w.b")]
        assert rec["locks"] == ["w.a", "w.b"]

    def test_reentrant_rlock_never_self_edges(self, fresh_witness):
        r = conc.RLock("w.r")
        with r:
            with r:
                pass
        assert conc.observed_edges() == []

    def test_witness_catches_seeded_inversion(self, fresh_witness):
        """THE acceptance pin: two threads acquire the same two locks in
        opposite orders (at different times, so nothing actually
        deadlocks) and the merged-graph cycle check names the inversion
        instead of CI hanging on the real interleaving."""
        a, b = conc.Lock("inv.a"), conc.Lock("inv.b")

        def ab():
            with a:
                with b:
                    pass

        def ba():
            with b:
                with a:
                    pass

        t1 = threading.Thread(target=ab, name="t-ab")
        t1.start()
        t1.join()
        t2 = threading.Thread(target=ba, name="t-ba")
        t2.start()
        t2.join()
        findings = conc.check_lock_order([])
        assert len(findings) == 1
        f = findings[0]
        assert (f.rule, f.kind) == ("lock_order", "inversion")
        assert f.provenance == "inv.a -> inv.b -> inv.a"
        assert "witness" in f.message
        assert "t-ab" in f.message or "t-ba" in f.message

    def test_static_and_witness_edges_merge_into_one_cycle(
            self, fresh_witness):
        # Half the cycle only the AST sees, half only the runtime saw —
        # the point of merging before cycle detection.
        a, b = conc.Lock("m.a"), conc.Lock("m.b")
        with a:
            with b:
                pass
        findings = conc.check_lock_order([("m.b", "m.a", "seeded.py:1")])
        assert len(findings) == 1
        assert findings[0].provenance == "m.a -> m.b -> m.a"
        assert "static seeded.py:1" in findings[0].message

    def test_condition_wait_drops_and_reacquires_one_hold(
            self, fresh_witness):
        c = conc.Condition("w.cond")
        with c:
            assert conc._held_stack().count("w.cond") == 1
            c.wait(timeout=0.01)
            # wait() released for its sleep and re-recorded on wake —
            # exactly one hold, no duplicate stack entry.
            assert conc._held_stack().count("w.cond") == 1
        assert conc._held_stack() == []

    def test_timeout_failed_acquire_records_nothing(self, fresh_witness):
        a = conc.Lock("w.t")
        a.acquire()
        grabbed = []

        def try_it():
            grabbed.append(a.acquire(blocking=False))

        t = threading.Thread(target=try_it)
        t.start()
        t.join()
        assert grabbed == [False]
        a.release()
        assert conc._held_stack() == []


# ---------------------------------------------------------------------------
# Rule 3: thread hygiene
# ---------------------------------------------------------------------------

class TestThreadHygiene:
    def test_non_daemon_unjoined_thread_fires(self):
        findings, _ = lint("""
            import threading

            class C:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()
        """)
        assert len(findings) == 1
        f = findings[0]
        assert (f.rule, f.kind) == ("thread_hygiene", "unjoined_thread")
        assert f.provenance == "C.start.self._t"
        assert "non-daemon" in f.message

    def test_daemon_true_passes(self):
        findings, _ = lint("""
            import threading

            class C:
                def start(self):
                    self._t = threading.Thread(target=self._run,
                                               daemon=True)
                    self._t.start()
        """)
        assert findings == []

    def test_joined_self_thread_passes_across_methods(self):
        findings, _ = lint("""
            import threading

            class C:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def close(self):
                    self._t.join(timeout=5)
        """)
        assert findings == []

    def test_joined_local_thread_passes(self):
        findings, _ = lint("""
            import threading

            def run():
                t = threading.Thread(target=work)
                t.start()
                t.join()
        """)
        assert findings == []

    def test_unjoined_local_and_unassigned_fire(self):
        findings, _ = lint("""
            import threading

            def fire_and_forget():
                threading.Thread(target=work).start()
        """)
        assert len(findings) == 1
        assert findings[0].provenance == "fire_and_forget.<unassigned>"

    def test_non_literal_daemon_fires(self):
        findings, _ = lint("""
            import threading

            def run(flag):
                t = threading.Thread(target=work, daemon=flag)
                t.start()
        """)
        assert len(findings) == 1
        assert "daemon is not a literal True" in findings[0].message

    def test_threadlife_pragma_blesses(self):
        findings, _ = lint("""
            import threading

            def run():
                # threadlife: joined by the supervisor at job end
                t = threading.Thread(target=work)
                t.start()
        """)
        active = [f for f in findings if not f.blessed]
        assert not active
        assert findings and findings[0].blessed_by == \
            "joined by the supervisor at job end"


# ---------------------------------------------------------------------------
# Baseline (the committed blessings file)
# ---------------------------------------------------------------------------

class TestBaseline:
    def test_round_trip_blesses_by_fingerprint(self, tmp_path):
        findings, _ = lint(TestLockDiscipline.GUARDED)
        assert len(findings) == 1
        base = tmp_path / "concurrency.json"
        conc.write_baseline(base, findings, reason="audited: test-only")
        loaded = conc.load_baseline(base)
        assert loaded == {findings[0].fingerprint(): "audited: test-only"}
        active, blessed = conc.apply_baseline(findings, loaded)
        assert active == []
        assert blessed[0].blessed_by == "audited: test-only"

    def test_fingerprint_survives_line_churn(self):
        f1, _ = lint(TestLockDiscipline.GUARDED)
        f2, _ = lint("\n\n\n" + textwrap.dedent(TestLockDiscipline.GUARDED))
        assert f1[0].fingerprint() == f2[0].fingerprint()
        assert f1[0].line != f2[0].line

    def test_missing_baseline_is_empty(self, tmp_path):
        assert conc.load_baseline(tmp_path / "absent.json") == {}

    def test_main_update_baseline_then_clean(self, tmp_path):
        mod = tmp_path / "seeded.py"
        mod.write_text(textwrap.dedent(TestLockDiscipline.GUARDED))
        base = tmp_path / "base.json"
        assert conc.main([str(mod), "--baseline", str(base)]) == 1
        assert conc.main([str(mod), "--baseline", str(base),
                          "--update-baseline"]) == 0
        assert json.loads(base.read_text())["blessed"]
        assert conc.main([str(mod), "--baseline", str(base)]) == 0

    def test_main_missing_path_fails_loudly(self, tmp_path):
        assert conc.main([str(tmp_path / "nope")]) == 2

    def test_blessing_is_per_method_not_per_attribute(self):
        # Two unlocked mutations of the SAME guarded attribute in
        # different methods must carry distinct fingerprints — blessing
        # one audited site must not green-light the next call site that
        # forgets the lock.
        findings, _ = lint(TestLockDiscipline.GUARDED.replace(
            "def drop(self):",
            "def also(self):\n"
            "                self._items.pop()\n\n"
            "            def drop(self):"))
        fps = {f.fingerprint() for f in findings}
        assert len(findings) == 2 and len(fps) == 2

    def test_update_baseline_preserves_existing_reasons(self, tmp_path):
        # The regen must keep a still-firing blessing's audited reason
        # (not blow the baseline away and re-word everything), add the
        # new finding, and prune stale fingerprints.
        mod = tmp_path / "seeded.py"
        mod.write_text(textwrap.dedent(TestLockDiscipline.GUARDED))
        base = tmp_path / "base.json"
        findings, _ = conc.analyze_tree(mod)
        conc.write_baseline(base, findings, reason="audited: original")
        # Grow a second violation in another method, regen.
        mod.write_text(textwrap.dedent(TestLockDiscipline.GUARDED.replace(
            "def drop(self):",
            "def also(self):\n"
            "                self._items.pop()\n\n"
            "            def drop(self):")))
        assert conc.main([str(mod), "--baseline", str(base),
                          "--update-baseline"]) == 0
        loaded = conc.load_baseline(base)
        assert len(loaded) == 2
        old_fp = findings[0].fingerprint()
        assert loaded[old_fp] == "audited: original"
        assert conc.main([str(mod), "--baseline", str(base)]) == 0
        # Stale entries prune once the violation is gone.
        mod.write_text(textwrap.dedent(TestLockDiscipline.GUARDED))
        assert conc.main([str(mod), "--baseline", str(base),
                          "--update-baseline"]) == 0
        assert set(conc.load_baseline(base)) == {old_fp}


# ---------------------------------------------------------------------------
# The package tree at HEAD + the CLI verbs
# ---------------------------------------------------------------------------

class TestTreeCleanAtHead:
    def test_package_tree_is_clean(self, fresh_witness):
        report = conc.analyze_concurrency(
            REPO / "tony_tpu",
            baseline_path=REPO / "tests" / "signatures"
            / "concurrency.json")
        assert report.ok, "\n".join(str(f) for f in report.findings)

    def test_summary_banked_in_analysis_records(self, fresh_witness):
        profiler.reset_records("analysis")
        conc.analyze_concurrency(REPO / "tony_tpu")
        rec = profiler.report("analysis")["concurrency"]
        assert rec["findings"] == 0
        profiler.reset_records("analysis")

    def test_make_lint_invocation_is_clean(self, fresh_witness):
        assert conc.main(
            [str(REPO / "tony_tpu"), "--baseline",
             str(REPO / "tests" / "signatures" / "concurrency.json")]
        ) == 0

    def test_tony_analyze_concurrency_verb(self, fresh_witness, capsys):
        from types import SimpleNamespace

        from tony_tpu.analysis import cli as analysis_cli

        rc = analysis_cli.main(SimpleNamespace(
            concurrency=True, signatures=str(REPO / "tests"
                                             / "signatures"),
            update_signatures=False, config=None, json=None, lint=False))
        assert rc == 0
        assert "CLEAN" in capsys.readouterr().out

    def test_concurrency_json_report_written(self, fresh_witness,
                                             tmp_path):
        from types import SimpleNamespace

        from tony_tpu.analysis import cli as analysis_cli

        out = tmp_path / "conc.json"
        rc = analysis_cli.main(SimpleNamespace(
            concurrency=True, signatures=None, update_signatures=False,
            config=None, json=str(out), lint=False))
        assert rc == 0
        data = json.loads(out.read_text())
        assert data["concurrency"]["findings"] == []
        assert "static_edges" in data["concurrency"]

    def test_update_signatures_needs_dir(self):
        from types import SimpleNamespace

        from tony_tpu.analysis import cli as analysis_cli

        rc = analysis_cli.main(SimpleNamespace(
            concurrency=True, signatures=None, update_signatures=True,
            config=None, json=None, lint=False))
        assert rc == 2

    def test_explicit_config_with_concurrency_is_rejected(self, capsys):
        # --concurrency replaces the jaxpr configs; silently skipping a
        # requested one would read as "serve analyzed clean".
        from types import SimpleNamespace

        from tony_tpu.analysis import cli as analysis_cli

        rc = analysis_cli.main(SimpleNamespace(
            concurrency=True, signatures=None, update_signatures=False,
            config="serve", json=None, lint=False))
        assert rc == 2
        assert "INSTEAD" in capsys.readouterr().out

    def test_concurrency_module_is_jax_free(self):
        # Same layering contract as srclint: `make lint` and the
        # gateway-side `tony analyze --concurrency` must not pull jax.
        import subprocess
        import sys

        code = ("import sys; import tony_tpu.analysis.concurrency; "
                "sys.exit(1 if 'jax' in sys.modules else 0)")
        proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              capture_output=True)
        assert proc.returncode == 0, proc.stderr.decode()


# ---------------------------------------------------------------------------
# Profiler registry
# ---------------------------------------------------------------------------

class TestLockRegistry:
    def test_record_report_reset(self):
        profiler.reset_records("locks")
        profiler.record("locks", "t", locks=["a"], edges=[])
        assert profiler.report("locks") == {"t": {"locks": ["a"],
                                                  "edges": []}}
        profiler.reset_records("locks")
        assert profiler.report("locks") == {}

    def test_record_routes_locks(self):
        profiler.reset_records("locks")
        profiler.record("locks", "t", locks=["x"], edges=[])
        assert profiler.report("locks")["t"]["locks"] == ["x"]
        profiler.reset_records("locks")


# ---------------------------------------------------------------------------
# The genuinely multi-threaded kvcache interleave (the PR 13 randomized
# stress, now driven from N threads through the lock witness)
# ---------------------------------------------------------------------------

def _cache(n_blocks=16, block_size=4, **kw):
    from tony_tpu.serve import PagedKVCache

    return PagedKVCache(1, 4, n_blocks=n_blocks, block_size=block_size,
                        **kw)


def _keys(tokens, bs=4):
    from tony_tpu.serve import prefix

    return prefix.chain_keys(tokens, bs)


def check_partition(c):
    """THE pool invariant (same as test_route's): free tier + cached
    tier + refcounted ownership partition the block ids, and every
    refcount equals the number of tables holding the block. With the
    PR 16 host tier: host keys are disjoint from the device index (a
    promoted or re-published key leaves the host shadow), the tier
    stays inside its budget, and parked ids never alias live tables."""
    owned = {}
    for t in c.owned_blocks().values():
        for b in t:
            owned[b] = owned.get(b, 0) + 1
    free, lru = set(c._free), set(c.cached_blocks())
    assert not free & lru
    assert not (free | lru) & set(owned)
    assert free | lru | set(owned) == set(range(c.n_blocks))
    assert {b: c.ref(b) for b in owned} == owned
    assert set(c._refs) == set(owned)
    assert not set(c.host_keys()) & set(c._index), \
        "a chain key must live on exactly one tier"
    assert c.host_blocks_used <= max(0, c.host_blocks)
    assert not set(c.parked_ids()) & set(c.owned_blocks()), \
        "a parked id must not alias a live table"


@pytest.mark.slow
class TestThreadedKvcacheInterleave:
    N_THREADS = 4
    ROUNDS = 6
    OPS_PER_ROUND = 24

    def test_concurrent_interleave_partition_pinned(self, fresh_witness):
        """N threads hammer one shared pool with randomized
        admit/fork(shared-prefix)/write(COW)/spec(reserve-commit-
        rollback)/evict — and, PR 16, demote/promote/park/resume
        through the host tier — under the witnessed pool lock; at
        every quiescent point (a barrier each round) the
        refcount/free/LRU/host-tier partition is pinned exactly as the
        single-threaded PR 13 interleave pins it — and the witness
        graph of the run is cycle-free."""
        from tony_tpu.serve import AdmissionError

        c = _cache(n_blocks=16, block_size=4, host_blocks=8)
        pool_lock = conc.Lock("kvcache.pool")
        stats_lock = conc.Lock("kvcache.stats")
        stems = [list(np.random.RandomState(7).randint(0, 50, 8))
                 for _ in range(3)]
        barrier = threading.Barrier(self.N_THREADS + 1)
        errors = []
        stats = {"ops": 0, "admitted": 0}

        def one_op(rng, tid, seqs, parked, sid_n):
            op = rng.choice(["admit", "write", "spec", "free",
                             "handoff", "demote", "promote", "park",
                             "resume"])
            if op == "admit":
                sid = f"t{tid}-s{sid_n[0]}"
                sid_n[0] += 1
                toks = list(stems[rng.randint(3)][:rng.choice([4, 8])]) \
                    + list(rng.randint(0, 50, rng.randint(0, 6)))
                try:
                    c.admit_shared(sid, len(toks) + 4, _keys(toks))
                except AdmissionError:
                    return
                seqs[sid] = toks
                for i, key in enumerate(_keys(toks)):
                    c.publish_block(sid, i, key)
                # Consistent nesting pool -> stats: the witness sees a
                # real cross-lock edge, and it must stay acyclic.
                with stats_lock:
                    stats["admitted"] += 1
            elif op == "write" and seqs:
                sid = list(seqs)[rng.randint(len(seqs))]
                pos = rng.randint(len(c.table(sid)) * c.block_size)
                try:
                    c.write_index(sid, pos)
                except AdmissionError:
                    return
                b = c.table(sid)[pos // c.block_size]
                assert c.ref(b) == 1, \
                    "a write target must be exclusively owned"
            elif op == "spec" and seqs:
                sid = list(seqs)[rng.randint(len(seqs))]
                before = list(c.table(sid))
                extent = len(before) * c.block_size
                try:
                    c.spec_reserve(sid, extent + rng.randint(1, 9))
                except AdmissionError:
                    return
                c.commit(sid, rng.randint(extent + 1))
                c.rollback(sid)
                assert c.table(sid)[:len(before)] == before
            elif op == "free" and seqs:
                sid = list(seqs)[rng.randint(len(seqs))]
                del seqs[sid]
                c.free_seq(sid)
                assert c.free_seq(sid) == 0
            elif op == "handoff" and seqs:
                # The PR 15 wire tier under the same witnessed lock:
                # export a live sequence's prompt blocks and import
                # them as a new sequence — the self-handoff exercises
                # the receiver path (offer-matched adoption + fresh
                # byte writes) exactly as an RPC receiver thread would
                # drive it, and the partition stays pinned.
                from tony_tpu.serve import HandoffError

                src = list(seqs)[rng.randint(len(seqs))]
                toks = seqs[src]
                bs = c.block_size
                exp_len = rng.randint(1, len(toks) + 1)
                blocks = c.export_blocks(src, exp_len)
                keys = _keys(toks)[:exp_len // bs]
                offset = len(c.match_prefix(keys))
                sid = f"t{tid}-h{sid_n[0]}"
                sid_n[0] += 1
                if blocks[offset:] and rng.rand() < 0.25:
                    # Seeded corruption: the import must reject typed
                    # and state-unchanged (the partition check below
                    # pins "unchanged").
                    bad = [dict(b) for b in blocks[offset:]]
                    bad[0]["crc"] ^= 1
                    try:
                        c.import_blocks(sid, exp_len, bad, keys=keys,
                                        offset=offset)
                        raise AssertionError("corrupt import accepted")
                    except HandoffError:
                        return
                try:
                    adopted = c.import_blocks(
                        sid, exp_len + 4, blocks[offset:], keys=keys,
                        offset=offset)
                except AdmissionError:
                    return
                assert adopted == offset
                # Imported bytes are read-only until the engine's write
                # path COWs them: adopted blocks stay referenced (>= 2
                # with a live donor, 1 when revived from the cached
                # tier), fresh imports privately owned — and the write
                # op's exclusivity assert above covers the COW half.
                t_new = c.table(sid)
                for b in t_new[:adopted]:
                    assert c.ref(b) >= 1
                if blocks[offset:]:
                    i = adopted + rng.randint(len(blocks) - offset)
                    want_k, _ = c._decode_block(blocks[i])
                    assert np.array_equal(
                        np.asarray(c.k[:, t_new[i]]), want_k), \
                        "imported block bytes must land verbatim"
                seqs[sid] = list(toks[:exp_len])
            elif op == "demote":
                # PR 16 host tier: cold cached-tier blocks drop to host
                # payloads; the pool partition below pins the books.
                c.demote(rng.randint(1, 4))
            elif op == "promote" and c.host_keys():
                from tony_tpu.serve import HandoffError

                hk = c.host_keys()
                key = hk[rng.randint(len(hk))]
                payload = dict(c._host_index[key])
                # The corruption probe needs a free slot: with the LIFO
                # tier empty promote degrades to 0 BEFORE decoding (by
                # design — it never allocates through LRU eviction), so
                # the poison would go untested and leak to a later op.
                if rng.rand() < 0.25 and c._free:
                    # Seeded host-tier corruption: promote must reject
                    # typed with BOTH tiers unchanged (the partition
                    # check each round pins "unchanged"), and the
                    # poison entry discards cleanly.
                    before_free = list(c._free)
                    c._host_index[key]["crc"] ^= 1
                    try:
                        c.promote([key])
                        raise AssertionError("corrupt promote accepted")
                    except HandoffError:
                        pass
                    assert list(c._free) == before_free
                    assert c.discard_host([key]) == 1
                    return
                if c.promote([key]):
                    b = c._index[key]
                    want_k, want_v = c._decode_block(payload)
                    assert np.array_equal(np.asarray(c.k[:, b]),
                                          want_k) \
                        and np.array_equal(np.asarray(c.v[:, b]),
                                           want_v), \
                        "demoted bytes must promote back verbatim"
            elif op == "park" and seqs:
                sid = list(seqs)[rng.randint(len(seqs))]
                toks = seqs[sid]
                length = rng.randint(1, len(toks) + 1)
                try:
                    c.park(sid, length,
                           keys=_keys(toks)[:length // c.block_size])
                except AdmissionError:
                    return          # host tier full: plain evict path
                del seqs[sid]
                c.free_seq(sid)     # park already freed: idempotent 0
                pid = f"t{tid}-p{sid_n[0]}"
                sid_n[0] += 1
                parked[pid] = (sid, length, list(toks))
            elif op == "resume" and parked:
                from tony_tpu.serve import HandoffError

                pid = list(parked)[rng.randint(len(parked))]
                old_sid, length, toks = parked[pid]
                rec = c._parked[old_sid]
                rec["ready"].wait()
                # The probe must poison a block the resume will DECODE:
                # a stem block still published on device (another
                # thread's copy of the shared stem) is adopted without
                # touching its host payload, so corrupting it proves
                # nothing — match the prefix under the same lock the
                # resume will and corrupt the first decoded block.
                m = len(c.match_prefix(rec["keys"]))
                if rng.rand() < 0.25 and m < len(rec["blocks"]):
                    # Seeded CRC corruption on a parked payload: the
                    # resume must reject typed and state-unchanged —
                    # record intact, pool untouched — then restore.
                    rec["blocks"][m]["crc"] ^= 1
                    try:
                        c.resume(f"t{tid}-x", length + 4, old_sid)
                        raise AssertionError("corrupt resume accepted")
                    except HandoffError:
                        pass
                    assert old_sid in c._parked
                    rec["blocks"][m]["crc"] ^= 1
                    return
                sid = f"t{tid}-r{sid_n[0]}"
                sid_n[0] += 1
                try:
                    c.resume(sid, length + 4, old_sid)
                except AdmissionError:
                    return          # record kept: retryable next round
                del parked[pid]
                seqs[sid] = list(toks[:length])

        def worker(tid):
            rng = np.random.RandomState(100 + tid)
            seqs, parked, sid_n = {}, {}, [0]
            try:
                for _ in range(self.ROUNDS):
                    for _ in range(self.OPS_PER_ROUND):
                        with pool_lock:
                            one_op(rng, tid, seqs, parked, sid_n)
                            stats["ops"] += 1
                    barrier.wait()          # quiescent point reached
                    barrier.wait()          # main finished the check
                with pool_lock:
                    for sid in list(seqs):
                        c.free_seq(sid)
                    for _, (old_sid, _, _) in parked.items():
                        c.unpark(old_sid)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)
                barrier.abort()

        threads = [threading.Thread(target=worker, args=(i,),
                                    name=f"kv-stress-{i}", daemon=True)
                   for i in range(self.N_THREADS)]
        for t in threads:
            t.start()
        for _ in range(self.ROUNDS):
            # A worker failure aborts the barrier: fall through to the
            # error assert below, which names the REAL exception.
            try:
                barrier.wait()
                check_partition(c)          # every quiescent point
                barrier.wait()
            except threading.BrokenBarrierError:
                break
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        assert not any(t.is_alive() for t in threads)
        check_partition(c)
        assert c.free_blocks == c.n_blocks
        assert c.adopted_total > 0 and c.cow_total > 0, \
            "the interleave must actually exercise sharing and COW"
        assert c.imported_total > 0, \
            "the interleave must actually exercise the handoff wire tier"
        assert c.demoted_total > 0 and c.promoted_total > 0, \
            "the interleave must actually exercise the host tier"
        assert c.parked_total > 0 and c.resumed_total > 0, \
            "the interleave must actually exercise park/resume"
        assert stats["ops"] == self.N_THREADS * self.ROUNDS \
            * self.OPS_PER_ROUND
        # The witness watched the whole run: the pool->stats edge was
        # observed from multiple threads, and the merged order graph is
        # acyclic — a seeded inversion in this same harness IS caught
        # (TestWitness.test_witness_catches_seeded_inversion).
        edges = conc.observed_edges()
        assert [(e["src"], e["dst"]) for e in edges] \
            == [("kvcache.pool", "kvcache.stats")]
        assert len(edges[0]["threads"]) > 1
        assert conc.check_lock_order([]) == []

    def test_seeded_inversion_in_stress_harness_is_named(
            self, fresh_witness):
        """The same two stress locks acquired once in the WRONG order
        (from a thread that nests stats -> pool) turn the previous
        test's clean graph into a named deadlock finding."""
        pool_lock = conc.Lock("kvcache.pool")
        stats_lock = conc.Lock("kvcache.stats")
        with pool_lock:
            with stats_lock:
                pass

        def inverted():
            with stats_lock:
                with pool_lock:
                    pass

        t = threading.Thread(target=inverted, name="kv-inverted")
        t.start()
        t.join()
        findings = conc.check_lock_order([])
        assert len(findings) == 1
        assert findings[0].kind == "inversion"
        assert findings[0].provenance == \
            "kvcache.pool -> kvcache.stats -> kvcache.pool"
