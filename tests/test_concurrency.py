"""Concurrency-sanitizer tests (ISSUE 7 tentpole).

The contract under test (docs/static_analysis.md, "Concurrency rules"):

* the AST linter flags each H7xx hazard on embedded bad fixtures and
  stays silent on the good twins: H701 thread-reachable module-global
  mutation outside a registered lock, H702 explicit ``acquire()``, H703
  ``Thread`` without ``daemon=``/join, H704 blocking call under a
  registered lock, H705 sleep-polling next to a Condition/Event;
* the runtime sanitizer (``HEAT_TPU_TSAN``) detects a seeded ABBA lock
  cycle (``tsan.lock_cycle``, both acquisition stacks attached) and a
  seeded off-thread unguarded access (``tsan.unguarded_access``, both
  stacks attached), raises in raise mode, and reports ZERO findings on
  the real threaded surfaces — an N-thread metrics-registry hammer with
  concurrent ``snapshot()``/``reset_all()``, a live fit scraped from
  other threads, and the async-checkpoint writer;
* findings flow into the shared diagnostics pipeline
  (``analysis.diags.tsan.*`` counters) and the flight-recorder crash
  bundle; ``HEAT_TPU_TSAN_DUMP`` writes them at process exit;
* the telemetry server start/stop races and the flight-recorder
  excepthook re-entrancy are fixed (one bundle per crashing thread,
  distinct paths);
* the ``jax.shard_map`` kernels of the perf grid (sort, SpMM ring) run
  on this runner's jax.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import warnings

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.analysis import concurrency, tsan
from heat_tpu.analysis.ast_lint import RULES, lint_file
from heat_tpu.analysis.diagnostics import ProgramLintError
from heat_tpu.core import dispatch
from heat_tpu.telemetry import flight_recorder
from heat_tpu.telemetry import inspect as tinspect
from heat_tpu.telemetry import metrics as tm
from heat_tpu.telemetry import server as tserver

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KNOBS = {"HEAT_TPU_REGISTERED"}
SITES = {"good.site"}
LOCKS = {"_GUARD", "self._lock"}


def lint_src(src, rel="heat_tpu/somemod.py"):
    """Lint an embedded fixture without touching the filesystem."""
    return lint_file(
        "<fixture>", repo_root=REPO_ROOT, knobs=KNOBS, sites=SITES,
        source=textwrap.dedent(src), rel_path=rel, lock_spellings=LOCKS,
    )


def rules(violations):
    return [v.rule for v in violations]


@pytest.fixture
def armed():
    """Arm the sanitizer for one test with clean state; disarm after."""
    tsan.clear_findings()
    prev = tsan.arm("1")
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            yield tsan
    finally:
        tsan.disarm()
        tsan.clear_findings()


# ----------------------------------------------------------------------
# the lock registry (the static/dynamic shared table)
# ----------------------------------------------------------------------
class TestLockRegistry:
    def test_registry_shape(self):
        assert concurrency.LOCK_REGISTRY
        for name, rec in concurrency.LOCK_REGISTRY.items():
            assert rec["file"].startswith("heat_tpu/")
            assert isinstance(rec["spellings"], tuple) and rec["spellings"]
            assert isinstance(rec["structures"], tuple)
            assert rec["doc"]

    def test_static_parse_matches_live_table(self):
        from heat_tpu.analysis.ast_lint import load_lock_spellings

        assert load_lock_spellings(REPO_ROOT) == concurrency.registered_spellings()

    def test_structures_resolve_to_locks(self):
        for s, lock in concurrency.registered_structures().items():
            assert lock in concurrency.LOCK_REGISTRY
            assert concurrency.lock_for_structure(s) == lock

    def test_unregistered_lock_and_structure_refused(self):
        with pytest.raises(KeyError, match="LOCK_REGISTRY"):
            tsan.register_lock("nope.not.registered")
        with pytest.raises(KeyError, match="registered guarded structure"):
            concurrency.lock_for_structure("nope.struct")

    def test_registered_locks_are_proxies(self):
        assert isinstance(tm.REGISTRY._lock, tsan.TsanLock)
        assert isinstance(dispatch._CACHE_LOCK, tsan.TsanLock)
        from heat_tpu.telemetry import spans as tspans

        assert isinstance(tspans._RING_LOCK, tsan.TsanLock)
        assert isinstance(flight_recorder._DUMP_LOCK, tsan.TsanLock)


# ----------------------------------------------------------------------
# H701: thread-reachable module-global mutation outside a registered lock
# ----------------------------------------------------------------------
class TestH701ThreadGlobalMutation:
    def test_thread_target_mutations_flag(self):
        v = lint_src("""
            import threading
            _STATE = {}
            _ITEMS = []
            def worker():
                global _COUNT
                _COUNT = 1
                _STATE["k"] = 2
                _ITEMS.append(3)
            def start():
                threading.Thread(target=worker, daemon=True).start()
        """)
        assert rules(v) == ["H701", "H701", "H701"]

    def test_transitive_reachability_flags(self):
        v = lint_src("""
            import threading
            _STATE = {}
            def helper():
                _STATE.clear()
            def worker():
                helper()
            def start():
                threading.Thread(target=worker, daemon=True).start()
        """)
        assert rules(v) == ["H701"]

    def test_excepthook_and_handler_entries_flag(self):
        v = lint_src("""
            import sys
            from http.server import BaseHTTPRequestHandler
            _LAST = None
            def hook(t, e, tb):
                global _LAST
                _LAST = e
            sys.excepthook = hook
            class H(BaseHTTPRequestHandler):
                def do_GET(self):
                    global _LAST
                    _LAST = self.path
        """)
        assert rules(v) == ["H701", "H701"]

    def test_mutation_under_registered_lock_clean(self):
        assert lint_src("""
            import threading
            _GUARD = threading.Lock()
            _STATE = {}
            def worker():
                global _COUNT
                with _GUARD:
                    _COUNT = 1
                    _STATE["k"] = 2
            def start():
                threading.Thread(target=worker, daemon=True).start()
        """) == []

    def test_main_thread_only_code_clean(self):
        assert lint_src("""
            _STATE = {}
            def not_threaded():
                global _COUNT
                _COUNT = 1
                _STATE["k"] = 2
        """) == []

    def test_local_and_attr_state_clean(self):
        assert lint_src("""
            import threading
            def worker(obj):
                local = {}
                local["k"] = 1
                obj.field = 2
            def start():
                threading.Thread(target=worker, args=(object(),), daemon=True).start()
        """) == []


# ----------------------------------------------------------------------
# H702: explicit acquire()
# ----------------------------------------------------------------------
class TestH702ExplicitAcquire:
    def test_acquire_flags(self):
        v = lint_src("""
            import threading
            lock = threading.Lock()
            class C:
                def f(self):
                    lock.acquire()
                    self._lock.acquire(timeout=1)
        """)
        assert rules(v) == ["H702", "H702"]

    def test_with_statement_clean(self):
        assert lint_src("""
            import threading
            lock = threading.Lock()
            def f():
                with lock:
                    pass
        """) == []

    def test_non_lock_acquire_clean(self):
        # .acquire() on something not lock-named (a connection pool, a
        # semaphore API we don't govern) is out of scope
        assert lint_src("""
            def f(pool):
                conn = pool.acquire()
        """) == []

    def test_sanctioned_proxy_file_clean(self):
        assert lint_src(
            "def f(self):\n    self._lock.acquire()\n",
            rel="heat_tpu/analysis/tsan.py",
        ) == []


# ----------------------------------------------------------------------
# H703: Thread without daemon= / join close path
# ----------------------------------------------------------------------
class TestH703ThreadLifecycle:
    def test_no_daemon_no_join_flags(self):
        v = lint_src("""
            import threading
            def start(f):
                return threading.Thread(target=f)
        """)
        assert rules(v) == ["H703"]

    def test_explicit_daemon_clean(self):
        assert lint_src("""
            import threading
            def start(f):
                return threading.Thread(target=f, daemon=True)
        """) == []

    def test_join_close_path_clean(self):
        assert lint_src("""
            import threading
            def start(f):
                t = threading.Thread(target=f)
                t.start()
                return t
            def stop(t):
                t.join()
        """) == []


# ----------------------------------------------------------------------
# H704: blocking call while holding a registered lock
# ----------------------------------------------------------------------
class TestH704BlockingUnderLock:
    def test_blocking_calls_flag(self):
        v = lint_src("""
            import threading, time, jax
            _GUARD = threading.Lock()
            def f(q, t, x):
                with _GUARD:
                    q.get()
                    t.join()
                    time.sleep(1)
                    jax.block_until_ready(x)
        """)
        assert rules(v) == ["H704"] * 4

    def test_outside_lock_clean(self):
        assert lint_src("""
            import threading, time
            _GUARD = threading.Lock()
            def f(q, t):
                with _GUARD:
                    n = len(q.queue)
                q.get()
                t.join()
                time.sleep(1)
        """) == []

    def test_dict_get_and_str_join_clean(self):
        assert lint_src("""
            import threading
            _GUARD = threading.Lock()
            def f(d, parts):
                with _GUARD:
                    v = d.get("k")
                    s = ",".join(parts)
        """) == []


# ----------------------------------------------------------------------
# H705: sleep-polling loop next to a Condition/Event
# ----------------------------------------------------------------------
class TestH705SleepPolling:
    def test_polling_loop_flags(self):
        v = lint_src("""
            import threading, time
            class Worker:
                def __init__(self):
                    self._done = threading.Event()
                def run(self):
                    while not self._done.is_set():
                        time.sleep(0.1)
        """)
        assert rules(v) == ["H705"]

    def test_class_without_primitive_clean(self):
        assert lint_src("""
            import time
            class Backoff:
                def run(self):
                    for d in (1, 2, 4):
                        time.sleep(d)
        """) == []

    def test_event_wait_clean(self):
        assert lint_src("""
            import threading
            class Worker:
                def __init__(self):
                    self._done = threading.Event()
                def run(self):
                    while not self._done.wait(0.1):
                        pass
        """) == []


class TestRuleCatalogue:
    def test_h7xx_in_rules_and_cli(self):
        for r in ("H701", "H702", "H703", "H704", "H705"):
            assert r in RULES
        out = subprocess.run(
            [sys.executable, "-m", "heat_tpu.analysis", "--list-rules"],
            capture_output=True, text=True, cwd=REPO_ROOT,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert out.returncode == 0
        for r in ("H701", "H702", "H703", "H704", "H705"):
            assert r in out.stdout

    def test_repo_is_h7xx_clean(self):
        # the shipped sources obey their own concurrency rules: no new
        # H7xx violations against the checked-in baseline
        sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
        from lint_gate import run_gate

        res = run_gate(quiet=True)
        h7 = [e for e in res["new"] if e["rule"].startswith("H7")]
        assert h7 == []


# ----------------------------------------------------------------------
# runtime sanitizer: lock-order cycles
# ----------------------------------------------------------------------
class TestLockCycle:
    def test_abba_cycle_detected_with_both_stacks(self, armed):
        A = tsan.register_lock("test.A")
        B = tsan.register_lock("test.B")

        def fwd():
            with A:
                with B:
                    pass

        def rev():
            with B:
                with A:
                    pass

        t1 = threading.Thread(target=fwd, daemon=True)
        t1.start(); t1.join()
        assert tsan.finding_count() == 0  # one order alone is fine
        t2 = threading.Thread(target=rev, daemon=True)
        t2.start(); t2.join()

        found = tsan.findings()
        assert [f["rule"] for f in found] == ["tsan.lock_cycle"]
        f = found[0]
        assert set(f["cycle"]) == {"test.A", "test.B"}
        # both stacks attached: the closing edge and the reverse path
        assert f["closing_edge"]["held_stack"] and f["closing_edge"]["acquire_stack"]
        assert f["reverse_path"] and f["reverse_path"][0]["acquire_stack"]
        stacks = " ".join(
            f["closing_edge"]["acquire_stack"] + f["reverse_path"][0]["acquire_stack"]
        )
        assert "test_concurrency.py" in stacks

    def test_cycle_reported_once(self, armed):
        A = tsan.register_lock("test.A")
        B = tsan.register_lock("test.B")
        for _ in range(3):
            with A:
                with B:
                    pass
            with B:
                with A:
                    pass
        assert tsan.finding_count() == 1

    def test_consistent_order_clean(self, armed):
        A = tsan.register_lock("test.A")
        B = tsan.register_lock("test.B")

        def go():
            for _ in range(50):
                with A:
                    with B:
                        pass

        threads = [threading.Thread(target=go, daemon=True) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert tsan.finding_count() == 0
        assert ("test.A", "test.B") in tsan.lock_graph()

    def test_three_lock_cycle(self, armed):
        A = tsan.register_lock("test.A")
        B = tsan.register_lock("test.B")
        C = tsan.register_lock("test.C")
        with A:
            with B:
                pass
        with B:
            with C:
                pass
        with C:
            with A:
                pass
        found = [f for f in tsan.findings() if f["rule"] == "tsan.lock_cycle"]
        assert len(found) == 1
        assert set(found[0]["cycle"]) == {"test.A", "test.B", "test.C"}

    def test_raise_mode(self):
        tsan.clear_findings()
        tsan.arm("raise")
        try:
            A = tsan.register_lock("test.A")
            B = tsan.register_lock("test.B")
            with A:
                with B:
                    pass
            with pytest.raises(ProgramLintError, match="lock-order cycle"):
                with B:
                    with A:
                        pass
        finally:
            tsan.disarm()
            tsan.clear_findings()

    def test_counters_flow_into_registry(self, armed):
        before = tm.counter("analysis.diags.tsan.lock_cycle").value
        A = tsan.register_lock("test.A")
        B = tsan.register_lock("test.B")
        with A:
            with B:
                pass
        with B:
            with A:
                pass
        assert tm.counter("analysis.diags.tsan.lock_cycle").value == before + 1
        recent = [d.rule for d in __import__("heat_tpu").analysis.recent_diagnostics()]
        assert "tsan.lock_cycle" in recent


# ----------------------------------------------------------------------
# runtime sanitizer: guarded-structure access
# ----------------------------------------------------------------------
class TestUnguardedAccess:
    def test_off_thread_unguarded_flags_with_both_stacks(self, armed):
        tsan.register_structure("test.struct", "test.A")
        tsan.note_access("test.struct")  # main thread: sanctioned

        def bad():
            tsan.note_access("test.struct")

        t = threading.Thread(target=bad, daemon=True, name="rogue")
        t.start(); t.join()
        found = [f for f in tsan.findings() if f["rule"] == "tsan.unguarded_access"]
        assert len(found) == 1
        f = found[0]
        assert f["structure"] == "test.struct" and f["lock"] == "test.A"
        assert f["thread"] == "rogue"
        assert f["access_stack"] and "test_concurrency.py" in f["access_stack"][0]
        assert f["last_access_stack"]  # the main-thread access above

    def test_off_thread_with_lock_clean(self, armed):
        A = tsan.register_lock("test.A")
        tsan.register_structure("test.struct", "test.A")

        def good():
            with A:
                tsan.note_access("test.struct")

        t = threading.Thread(target=good, daemon=True)
        t.start(); t.join()
        assert tsan.finding_count() == 0

    def test_reported_once_per_site(self, armed):
        tsan.register_structure("test.struct", "test.A")

        def bad():
            for _ in range(5):
                tsan.note_access("test.struct")

        t = threading.Thread(target=bad, daemon=True)
        t.start(); t.join()
        assert tsan.finding_count() == 1

    def test_unregistered_structure_refused(self, armed):
        with pytest.raises(KeyError):
            tsan.note_access("never.registered.struct")

    def test_disarmed_is_free_and_silent(self):
        assert not tsan.enabled()
        tsan.note_access("never.registered.struct")  # no check while off
        assert tsan.finding_count() == 0


# ----------------------------------------------------------------------
# the real threaded surfaces are clean under the armed sanitizer
# ----------------------------------------------------------------------
class TestRealSurfacesClean:
    def test_metrics_registry_hammer(self, armed):
        stop = threading.Event()
        errors = []

        def hammer(i):
            try:
                c = tm.counter(f"test.tsan.c{i % 4}")
                g = tm.gauge(f"test.tsan.g{i % 4}")
                h = tm.histogram(f"test.tsan.h{i % 4}")
                while not stop.is_set():
                    c.inc()
                    g.set(i)
                    h.observe(0.5 + i)
            except Exception as e:  # surfaced below
                errors.append(e)

        def reader():
            try:
                while not stop.is_set():
                    tm.snapshot()
                    tm.expose()
                    telemetry.reset_all("spans")
            except Exception as e:
                errors.append(e)

        threads = [
            threading.Thread(target=hammer, args=(i,), daemon=True) for i in range(6)
        ] + [threading.Thread(target=reader, daemon=True) for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert errors == []
        assert tsan.finding_count() == 0, tsan.findings()

    def test_live_fit_scraped_from_threads(self, armed):
        ht.random.seed(0)
        x = ht.random.randn(2048, 8, split=0).astype(ht.float32)
        stop = threading.Event()
        errors = []

        def scraper():
            try:
                while not stop.is_set():
                    dispatch.cache_keys()
                    dispatch.cost_summary()
                    telemetry.get_spans()
                    tm.snapshot()
            except Exception as e:
                errors.append(e)

        t = threading.Thread(target=scraper, daemon=True)
        t.start()
        try:
            km = ht.cluster.KMeans(
                n_clusters=4, init="random", max_iter=8, random_state=0
            )
            km.fit(x)
        finally:
            stop.set()
            t.join(timeout=5)
        assert errors == []
        assert tsan.finding_count() == 0, tsan.findings()

    def test_async_checkpointer_clean(self, armed, tmp_path):
        from heat_tpu.utils.checkpoint import Checkpointer

        ack = Checkpointer(str(tmp_path)).as_async()
        state = {"w": np.arange(64, dtype=np.float32), "step": 0}
        for i in range(3):
            ack.save(i, state)
        ack.wait()
        ack.close()
        assert tsan.finding_count() == 0, tsan.findings()

    def test_fault_injector_cross_thread_deterministic(self, armed):
        from heat_tpu.resilience.errors import TransientFault
        from heat_tpu.resilience.faults import fault_plan, inject

        with fault_plan({"io.write": [2]}) as inj:
            hits = []

            def worker():
                for _ in range(2):
                    try:
                        inject("io.write")
                        hits.append(0)
                    except TransientFault:
                        hits.append(1)

            threads = [threading.Thread(target=worker, daemon=True) for _ in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert sum(hits) == 1  # exactly call index 2 fired, any thread
            assert inj.hits["io.write"] == 4
        assert tsan.finding_count() == 0, tsan.findings()


# ----------------------------------------------------------------------
# telemetry server start/stop races
# ----------------------------------------------------------------------
class TestServerRaces:
    def test_double_start_idempotent(self):
        tserver.stop_server()
        s1 = tserver.start_server(0)
        try:
            s2 = tserver.start_server(0)
            assert s1 is s2
        finally:
            tserver.stop_server()
        assert not tserver.server_running()

    def test_stop_during_inflight_requests(self):
        import urllib.request

        tserver.stop_server()
        srv = tserver.start_server(0)
        url = srv.url
        stop = threading.Event()
        errors = []

        def scrape():
            while not stop.is_set():
                try:
                    urllib.request.urlopen(f"{url}/varz", timeout=2).read()
                except OSError:
                    pass  # connection refused after stop: expected
                except Exception as e:
                    errors.append(e)

        threads = [threading.Thread(target=scrape, daemon=True) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        tserver.stop_server()  # must not raise mid-scrape
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert errors == []
        # a fresh start still works after the racy stop
        s = tserver.start_server(0)
        try:
            body = urllib.request.urlopen(f"{s.url}/metrics", timeout=5).read()
            assert b"heat_tpu" in body
        finally:
            tserver.stop_server()

    def test_concurrent_stops_single_close(self):
        tserver.stop_server()
        tserver.start_server(0)
        errors = []

        def stopper():
            try:
                tserver.stop_server()
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=stopper, daemon=True) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=5)
        assert errors == [] and not tserver.server_running()

    def test_close_idempotent(self):
        tserver.stop_server()
        srv = tserver.start_server(0)
        tserver.stop_server()
        srv.close()  # second close of an already-stopped server: no-op
        assert srv.url.startswith("http://")  # address survives close

    def test_crashed_handler_keeps_serving(self, monkeypatch):
        import urllib.error
        import urllib.request

        tserver.stop_server()
        srv = tserver.start_server(0)
        try:
            def boom():
                raise RuntimeError("handler bug")

            monkeypatch.setattr(tserver, "health_report", boom)
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(f"{srv.url}/healthz", timeout=5)
            assert exc.value.code == 500
            monkeypatch.undo()
            # the crashed handler neither killed the server nor left the
            # module lock held: both paths below need it
            body = urllib.request.urlopen(f"{srv.url}/healthz", timeout=5).read()
            assert b"status" in body
        finally:
            tserver.stop_server()


# ----------------------------------------------------------------------
# flight-recorder re-entrancy
# ----------------------------------------------------------------------
class TestFlightRecorderConcurrency:
    def test_concurrent_thread_crashes_one_bundle_each(self, tmp_path):
        flight_recorder.install(str(tmp_path))
        try:
            barrier = threading.Barrier(2, timeout=5)

            def crash(tag):
                barrier.wait()
                raise RuntimeError(f"concurrent crash {tag}")

            threads = [
                threading.Thread(target=crash, args=(i,), daemon=True, name=f"crash-{i}")
                for i in range(2)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
        finally:
            flight_recorder.uninstall()
        bundles = sorted(tmp_path.glob("flight_*.json"))
        assert len(bundles) == 2
        reasons = set()
        for b in bundles:
            doc = tinspect.load_bundle(str(b))  # checksum-verified
            assert doc["exception"]["type"] == "RuntimeError"
            reasons.add(doc["reason"])
        assert all(r.startswith("thread_crash:crash-") for r in reasons)
        assert len(reasons) == 2  # one bundle per crashing thread

    def test_bundle_carries_tsan_findings(self, armed, tmp_path):
        A = tsan.register_lock("test.A")
        B = tsan.register_lock("test.B")
        with A:
            with B:
                pass
        with B:
            with A:
                pass
        path = flight_recorder.dump_bundle(
            ValueError("probe"), reason="manual", directory=str(tmp_path)
        )
        doc = tinspect.load_bundle(path)
        assert doc["tsan"]["mode"] == "warn"
        assert [f["rule"] for f in doc["tsan"]["findings"]] == ["tsan.lock_cycle"]
        text = tinspect.format_bundle(doc)
        assert "tsan.lock_cycle" in text

    def test_dump_paths_distinct_per_thread(self, tmp_path):
        paths = []

        def dump():
            paths.append(
                flight_recorder.dump_bundle(
                    RuntimeError("x"), reason="manual", directory=str(tmp_path)
                )
            )

        threads = [threading.Thread(target=dump, daemon=True) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert len(paths) == 3 and len(set(paths)) == 3


# ----------------------------------------------------------------------
# sanitized subprocess: env arming + exit dump
# ----------------------------------------------------------------------
class TestTsanEnvAndDump:
    def test_env_armed_subprocess_dumps_findings(self, tmp_path):
        dump = tmp_path / "tsan.json"
        code = textwrap.dedent("""
            import threading, warnings
            from heat_tpu.analysis import tsan
            assert tsan.enabled() and tsan.mode() == "warn"
            A = tsan.register_lock("test.A")
            B = tsan.register_lock("test.B")
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                with A:
                    with B: pass
                with B:
                    with A: pass
            assert tsan.finding_count() == 1
        """)
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=REPO_ROOT,
            env={
                **os.environ,
                "JAX_PLATFORMS": "cpu",
                "HEAT_TPU_TSAN": "1",
                "HEAT_TPU_TSAN_DUMP": str(dump),
            },
        )
        assert out.returncode == 0, out.stderr
        doc = json.loads(dump.read_text())
        assert doc["mode"] == "warn"
        assert [f["rule"] for f in doc["findings"]] == ["tsan.lock_cycle"]

    def test_clean_subprocess_dumps_empty(self, tmp_path):
        dump = tmp_path / "tsan.json"
        code = (
            "import heat_tpu as ht\n"
            "ht.random.seed(0)\n"
            "x = ht.random.randn(512, 4, split=0).astype(ht.float32)\n"
            "ht.cluster.KMeans(n_clusters=2, init='random', max_iter=3,"
            " random_state=0).fit(x)\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, cwd=REPO_ROOT,
            env={
                **os.environ,
                "JAX_PLATFORMS": "cpu",
                "HEAT_TPU_TSAN": "1",
                "HEAT_TPU_TSAN_DUMP": str(dump),
            },
        )
        assert out.returncode == 0, out.stderr
        assert json.loads(dump.read_text())["findings"] == []


# ----------------------------------------------------------------------
# the shard_map kernels of the perf grid run on this jax
# ----------------------------------------------------------------------
class TestShardMapKernels:
    def test_bench_ci_kernels_alive(self):
        # two shard_map kernels of the BENCH_CI grid
        import scipy.sparse as sp

        ht.random.seed(0)
        xs = ht.random.randn(1 << 10, split=0).astype(ht.float32)
        s, _ = ht.sort(xs)
        sn = np.asarray(s._dense() if hasattr(s, "_dense") else s)
        assert (np.diff(sn) >= 0).all()

        A = sp.random(128, 128, density=0.05, random_state=0, format="csr")
        sa = ht.sparse.sparse_csr_matrix(A, split=0)
        xd = ht.random.randn(128, 4, split=0)
        out = sa @ xd
        assert out.shape == (128, 4)


# ----------------------------------------------------------------------
# loader lifecycle under the registered lock
# ----------------------------------------------------------------------
class TestLoaderLifecycle:
    def test_concurrent_close_race(self, armed):
        from heat_tpu.utils.data.partial_dataset import PartialH5DataLoaderIter

        class _Synthetic:
            dataset_names = ["d0"]
            length = 12
            load_length = 4
            transforms = None
            comm = None

            def read_window(self, start, stop):
                return [np.arange(start, stop, dtype=np.float32)]

        it = PartialH5DataLoaderIter(_Synthetic())
        next(it)
        threads = [
            threading.Thread(target=it.close, daemon=True) for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert it._thread is None
        assert tsan.finding_count() == 0, tsan.findings()
