"""Resilience-layer tests (ISSUE 2 tentpole).

The contract under test (docs/resilience.md):

* fault plans fire deterministically at scripted per-site call indices
  (and with a seeded probability), with per-site hit counters;
* RetryPolicy retries typed-retryable failures on the exact backoff
  schedule, never retries permanent faults, and supports a no-sleep
  deterministic test mode;
* every io writer is atomic — a crash mid-write is never visible to a
  reader — and a corrupt file fails loudly with ChecksumError on load;
* a transient injected fault on save is survived by the retry layer;
* the filesystem-native Checkpointer commits whole steps atomically and
  verifies checksums on restore;
* kmeans / lasso / pca fits killed at iteration/stage k and resumed from
  their checkpoints reproduce the uninterrupted result exactly;
* guard_finite turns NaN divergence into a structured DivergenceError
  carrying the last finite iterate;
* a dispatch compile failure falls back to eager execution once instead
  of crashing the op.
"""

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import resilience as rz
from heat_tpu.core import dispatch
from heat_tpu.utils.checkpoint import Checkpointer


@pytest.fixture(autouse=True)
def _no_sleep(monkeypatch):
    # deterministic no-sleep retries for every test in this module
    monkeypatch.setenv("HEAT_TPU_RETRY_NO_SLEEP", "1")


# ----------------------------------------------------------------------
# fault plans
# ----------------------------------------------------------------------
class TestFaultPlans:
    def test_at_index_and_kinds(self):
        with rz.fault_plan({"io.write": [0, {"at": 2, "kind": "permanent"}]}) as inj:
            with pytest.raises(rz.TransientFault) as e:
                rz.inject("io.write")
            assert e.value.site == "io.write" and e.value.index == 0
            rz.inject("io.write")  # index 1: clean
            with pytest.raises(rz.PermanentFault):
                rz.inject("io.write")
        assert inj.hits["io.write"] == 3
        assert inj.injected["io.write"] == [(0, "transient"), (2, "permanent")]
        # deactivated on exit
        rz.inject("io.write")

    def test_glob_pattern_and_isolation(self):
        with rz.fault_plan({"io.*": [{"at": 0, "kind": "transient"}]}) as inj:
            with pytest.raises(rz.TransientFault):
                rz.inject("io.read")
            rz.inject("comm.collective")  # unmatched site: clean
        assert inj.hits == {"io.read": 1, "comm.collective": 1}

    def test_probability_deterministic_per_seed(self):
        def run(seed):
            fired = []
            with rz.fault_plan({"s": [{"p": 0.3, "kind": "transient"}]}, seed=seed) as inj:
                for i in range(50):
                    try:
                        rz.inject("s")
                    except rz.TransientFault:
                        fired.append(i)
            return fired

        a, b, c = run(0), run(0), run(1)
        assert a == b  # same seed + call sequence -> identical injections
        assert a != c  # different seed -> different schedule
        assert a  # p=0.3 over 50 calls fires at least once

    def test_times_cap(self):
        with rz.fault_plan({"s": [{"p": 1.0, "kind": "transient", "times": 2}]}) as inj:
            for _ in range(2):
                with pytest.raises(rz.TransientFault):
                    rz.inject("s")
            rz.inject("s")  # cap reached: clean
        assert len(inj.injected["s"]) == 2

    def test_env_plan_hook(self, monkeypatch):
        from heat_tpu.resilience import faults

        plan = {"plan": {"env.site": [{"at": 0, "kind": "permanent"}]}, "seed": 3}
        monkeypatch.setenv(faults.PLAN_ENV, json.dumps(plan))

        inj = faults.refresh_env_plan()
        try:
            assert inj is not None
            with pytest.raises(rz.PermanentFault):
                rz.inject("env.site")
        finally:
            faults._ACTIVE = None  # deactivate the process-global plan

    def test_bad_rules_rejected(self):
        with pytest.raises(ValueError):
            rz.fault_plan({"s": [{"at": 0, "kind": "wat"}]})
        with pytest.raises(ValueError):
            rz.fault_plan({"s": [{"kind": "transient"}]})
        with pytest.raises(ValueError):
            rz.fault_plan({"s": [{"p": 1.5}]})


# ----------------------------------------------------------------------
# retry policy
# ----------------------------------------------------------------------
class TestRetryPolicy:
    def test_backoff_schedule(self):
        pol = rz.RetryPolicy(max_attempts=5, base_delay=0.1, max_delay=0.5, backoff=2.0, no_sleep=True)
        assert pol.schedule() == [0.1, 0.2, 0.4, 0.5]

    def test_succeeds_after_transients_records_delays(self):
        pol = rz.RetryPolicy(max_attempts=4, base_delay=0.05, no_sleep=True)
        attempts = []

        def flaky():
            attempts.append(1)
            if len(attempts) < 3:
                raise rz.TransientFault("flake")
            return "ok"

        assert pol.call(flaky) == "ok"
        assert len(attempts) == 3
        assert pol.last_delays == [0.05, 0.1]

    def test_gives_up_after_max_attempts(self):
        pol = rz.RetryPolicy(max_attempts=3, no_sleep=True)
        calls = []

        def always():
            calls.append(1)
            raise rz.TransientFault("down")

        with pytest.raises(rz.TransientFault):
            pol.call(always)
        assert len(calls) == 3

    def test_permanent_and_checksum_never_retried(self):
        pol = rz.RetryPolicy(max_attempts=5, no_sleep=True, retryable=(Exception,))
        for exc in (rz.PermanentFault("no"), rz.ChecksumError("f", 1, 2)):
            calls = []

            def fail(exc=exc):
                calls.append(1)
                raise exc

            with pytest.raises(type(exc)):
                pol.call(fail)
            assert len(calls) == 1  # zero retries

    def test_typed_filter(self):
        pol = rz.RetryPolicy(max_attempts=3, no_sleep=True, retryable=(OSError,))
        calls = []

        def typeerr():
            calls.append(1)
            raise TypeError("not retryable")

        with pytest.raises(TypeError):
            pol.call(typeerr)
        assert len(calls) == 1

    def test_attempt_timeout(self):
        import time as _time

        pol = rz.RetryPolicy(max_attempts=2, no_sleep=True, attempt_timeout=0.1)
        # the sleep only needs to outlive the 0.1s attempt budget with
        # margin; the executor's shutdown joins the sleeping worker, so
        # every extra second here is paid twice (once per attempt)
        with pytest.raises(rz.RetryTimeout):
            pol.call(lambda: _time.sleep(0.75))

    def test_decorator_and_stats(self):
        rz.reset_retry_stats()
        pol = rz.RetryPolicy(max_attempts=3, no_sleep=True)
        state = {"n": 0}

        @pol
        def op():
            state["n"] += 1
            if state["n"] < 2:
                raise rz.TransientFault("once")
            return 7

        assert op() == 7
        s = rz.retry_stats()
        assert s["retries"] == 1 and s["succeeded_after_retry"] == 1 and s["gave_up"] == 0


# ----------------------------------------------------------------------
# atomic io + checksums
# ----------------------------------------------------------------------
class TestAtomicIO:
    def test_torn_write_never_visible(self, tmp_path):
        p = str(tmp_path / "data.bin")
        with rz.atomic_write(p) as tmp:
            with open(tmp, "wb") as f:
                f.write(b"generation one")
        with pytest.raises(RuntimeError):
            with rz.atomic_write(p) as tmp:
                with open(tmp, "wb") as f:
                    f.write(b"gen")  # partial second generation
                raise RuntimeError("crash mid-write")
        # reader sees the previous complete generation; no temp litter
        with open(p, "rb") as f:
            assert f.read() == b"generation one"
        assert sorted(os.listdir(tmp_path)) == ["data.bin", "data.bin.crc32"]
        assert rz.verify_checksum(p) is True

    def test_checksum_mismatch_fails_loudly(self, tmp_path):
        p = str(tmp_path / "x.npy")
        ht.save(ht.arange(32, dtype=ht.float32), p)
        with open(p, "r+b") as f:  # corrupt one byte of the payload
            f.seek(-1, 2)
            f.write(b"\xff")
        with pytest.raises(rz.ChecksumError) as e:
            ht.load(p)
        assert "checksum mismatch" in str(e.value)

    def test_save_load_roundtrip_with_sidecars(self, tmp_path):
        a = ht.arange(24, dtype=ht.float32, split=0).reshape(6, 4)
        for name in ("r.csv", "r.npy", "r.npz", "r.txt", "r.h5"):
            p = str(tmp_path / name)
            if name.endswith(".h5"):
                if not ht.io.supports_hdf5():
                    continue
                ht.save(a, p, "data")
                out = ht.load(p, "data")
            else:
                ht.save(a, p)
                out = ht.load(p)
            assert os.path.exists(p + ".crc32"), name
            got = np.asarray(out._dense()).reshape(6, 4)
            np.testing.assert_allclose(got, np.arange(24, dtype=np.float32).reshape(6, 4))

    def test_transient_fault_on_save_is_survived(self, tmp_path):
        rz.reset_retry_stats()
        p = str(tmp_path / "x.npy")
        with rz.fault_plan({"io.write": [0]}) as inj:
            ht.save(ht.arange(8, dtype=ht.float32), p)
        assert inj.injected["io.write"] == [(0, "transient")]
        out = np.asarray(ht.load(p)._dense())
        np.testing.assert_allclose(out, np.arange(8, dtype=np.float32))
        s = rz.retry_stats()
        assert s["retries"] >= 1 and s["succeeded_after_retry"] >= 1

    def test_transient_fault_on_read_is_survived(self, tmp_path):
        p = str(tmp_path / "x.csv")
        ht.save(ht.arange(6, dtype=ht.float32).reshape(3, 2), p)
        with rz.fault_plan({"io.open": [0]}) as inj:
            out = ht.load(p)
        assert inj.injected["io.open"] == [(0, "transient")]
        assert np.asarray(out._dense()).shape == (3, 2)

    def test_permanent_fault_on_save_propagates(self, tmp_path):
        p = str(tmp_path / "x.npy")
        with rz.fault_plan({"io.write": [{"at": 0, "kind": "permanent"}]}):
            with pytest.raises(rz.PermanentFault):
                ht.save(ht.arange(8, dtype=ht.float32), p)
        assert not os.path.exists(p)  # nothing partial was committed

    def test_checksum_disabled_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_IO_CHECKSUM", "0")
        p = str(tmp_path / "x.npy")
        ht.save(ht.arange(4, dtype=ht.float32), p)
        assert not os.path.exists(p + ".crc32")
        ht.load(p)


# ----------------------------------------------------------------------
# filesystem-native checkpointer
# ----------------------------------------------------------------------
class TestCheckpointer:
    def test_nested_roundtrip_and_steps(self, tmp_path):
        import jax.numpy as jnp

        ck = Checkpointer(str(tmp_path / "ck"))
        state = {
            "params": {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)},
            "arr": ht.arange(10, dtype=ht.float32, split=0),
            "step": jnp.asarray(7),
            "meta": ["a", 2, (3.5, None)],
        }
        ck.save(0, state, extra_metadata={"epoch": 1})
        ck.save(5, state)
        assert ck.all_steps() == [0, 5] and ck.latest_step() == 5
        r = ck.restore(0)
        np.testing.assert_allclose(np.asarray(r["params"]["w"]), np.arange(6.0).reshape(2, 3))
        np.testing.assert_allclose(np.asarray(r["arr"]), np.arange(10.0))
        assert int(np.asarray(r["step"])) == 7
        assert r["meta"] == ["a", 2, (3.5, None)]  # tuple/list fidelity
        assert ck.metadata(0) == {"epoch": 1}

    def test_max_to_keep_prunes(self, tmp_path):
        ck = Checkpointer(str(tmp_path / "ck"), max_to_keep=2)
        for s in range(4):
            ck.save(s, {"v": np.asarray([s])})
        assert ck.all_steps() == [2, 3]

    def test_kill_during_save_leaves_no_partial_step(self, tmp_path):
        d = str(tmp_path / "ck")
        ck = Checkpointer(d)
        ck.save(1, {"v": np.arange(4)})
        # permanent fault inside the step write: the staged dir must be
        # cleaned up and step 1 must stay the latest complete checkpoint
        with rz.fault_plan({"checkpoint.write": [{"at": 0, "kind": "permanent"}]}):
            with pytest.raises(rz.PermanentFault):
                ck.save(2, {"v": np.arange(8)})
        assert ck.all_steps() == [1]
        assert not [n for n in os.listdir(d) if n.startswith(".tmp")]
        np.testing.assert_allclose(np.asarray(ck.restore(1)["v"]), np.arange(4))

    def test_transient_save_fault_retried(self, tmp_path):
        ck = Checkpointer(str(tmp_path / "ck"))
        with rz.fault_plan({"checkpoint.save": [0]}) as inj:
            ck.save(3, {"v": np.arange(3)})
        assert inj.injected["checkpoint.save"] == [(0, "transient")]
        assert ck.latest_step() == 3

    @pytest.mark.parametrize("where", ["zip_header", "payload", "tail"])
    def test_corrupt_checkpoint_raises_checksum_error(self, tmp_path, where):
        d = str(tmp_path / "ck")
        ck = Checkpointer(d)
        ck.save(0, {"v": np.arange(16, dtype=np.float64)})
        npz = os.path.join(d, "step_0", "arrays.npz")
        size = os.path.getsize(npz)
        offset = {"zip_header": 20, "payload": size // 2, "tail": size - 2}[where]
        with open(npz, "r+b") as f:
            # COMPLEMENT the bytes: a fixed pattern can equal what is there
            # (offset 20 is zip64's 0xffffffff size placeholder under
            # numpy >= 2, so writing 0xff 0xff there changed nothing)
            f.seek(offset)
            old = f.read(2)
            f.seek(offset)
            f.write(bytes(b ^ 0xFF for b in old))
        with pytest.raises(rz.ChecksumError):
            ck.restore(0)


# ----------------------------------------------------------------------
# resumable estimator fits
# ----------------------------------------------------------------------
def _data(n=240, f=6, seed=13):
    ht.random.seed(seed)
    return ht.random.randn(n, f, split=0).astype(ht.float32)


class TestResumableFits:
    def test_kmeans_chunked_matches_plain(self, tmp_path):
        x = _data()
        kw = dict(n_clusters=4, init="random", max_iter=40, tol=1e-4, random_state=3)
        plain = ht.cluster.KMeans(**kw).fit(x)
        ck = ht.cluster.KMeans(**kw, checkpoint_every=5, checkpoint_dir=str(tmp_path)).fit(x)
        assert np.array_equal(
            np.asarray(plain.cluster_centers_._dense()), np.asarray(ck.cluster_centers_._dense())
        )
        assert plain.n_iter_ == ck.n_iter_
        assert Checkpointer(str(tmp_path)).latest_step() == ck.n_iter_

    @pytest.mark.parametrize("est", ["kmeans", "kmedians", "kmedoids"])
    def test_kcluster_kill_and_resume_exact(self, tmp_path, est):
        x = _data()
        mk = {
            "kmeans": lambda **kw: ht.cluster.KMeans(n_clusters=4, init="random", max_iter=40,
                                                     tol=1e-4, random_state=3, **kw),
            "kmedians": lambda **kw: ht.cluster.KMedians(n_clusters=4, init="random", max_iter=40,
                                                         tol=1e-4, random_state=3, **kw),
            "kmedoids": lambda **kw: ht.cluster.KMedoids(n_clusters=4, init="random", max_iter=40,
                                                         random_state=3, **kw),
        }[est]
        plain = mk().fit(x)
        d = str(tmp_path / "ck")
        with rz.fault_plan({f"{est}.iter": [{"at": 1, "kind": "permanent"}]}):
            try:
                mk(checkpoint_every=2, checkpoint_dir=d).fit(x)
                interrupted = False  # converged before the scripted chunk
            except rz.PermanentFault:
                interrupted = True
        resumed = mk(checkpoint_every=2, resume_from=d).fit(x)
        assert np.array_equal(
            np.asarray(plain.cluster_centers_._dense()),
            np.asarray(resumed.cluster_centers_._dense()),
        ), f"{est} resumed centers differ (interrupted={interrupted})"
        assert np.array_equal(
            np.asarray(plain.labels_._dense()), np.asarray(resumed.labels_._dense())
        )
        assert plain.n_iter_ == resumed.n_iter_

    def test_lasso_kill_and_resume_exact(self, tmp_path):
        x = _data(128, 6, seed=9)
        w = ht.array(np.asarray([1.5, 0.0, -2.0, 0.0, 0.5, 0.0], np.float32).reshape(-1, 1))
        y = x @ w
        kw = dict(lam=0.05, max_iter=50, tol=1e-7)
        plain = ht.regression.Lasso(**kw).fit(x, y)
        d = str(tmp_path / "ck")
        with rz.fault_plan({"lasso.iter": [{"at": 1, "kind": "permanent"}]}):
            with pytest.raises(rz.PermanentFault):
                ht.regression.Lasso(**kw, checkpoint_every=3, checkpoint_dir=d).fit(x, y)
        resumed = ht.regression.Lasso(**kw, checkpoint_every=3, resume_from=d).fit(x, y)
        assert np.array_equal(
            np.asarray(plain.theta._dense()), np.asarray(resumed.theta._dense())
        )
        assert plain.n_iter == resumed.n_iter

    @pytest.mark.parametrize("solver", ["hierarchical", "randomized"])
    def test_pca_kill_between_stages_and_resume_exact(self, tmp_path, solver):
        x = _data(64, 12, seed=11)
        kw = dict(n_components=4, svd_solver=solver, random_state=5)
        plain = ht.decomposition.PCA(**kw).fit(x)
        d = str(tmp_path / "ck")
        # stage index 1 is the solver: the mean checkpoint exists, the fit dies
        with rz.fault_plan({"pca.stage": [{"at": 1, "kind": "permanent"}]}):
            with pytest.raises(rz.PermanentFault):
                ht.decomposition.PCA(**kw, checkpoint_every=1, checkpoint_dir=d).fit(x)
        assert Checkpointer(d).all_steps() == [0]  # mean stage committed
        resumed = ht.decomposition.PCA(**kw, checkpoint_every=1, resume_from=d).fit(x)
        for attr in ("components_", "singular_values_", "explained_variance_"):
            assert np.array_equal(
                np.asarray(getattr(plain, attr)._dense()),
                np.asarray(getattr(resumed, attr)._dense()),
            ), attr
        # a fully fitted checkpoint restores without recomputation
        restored = ht.decomposition.PCA(**kw, resume_from=d).fit(x)
        assert np.array_equal(
            np.asarray(plain.components_._dense()), np.asarray(restored.components_._dense())
        )
        assert restored.n_components_ == plain.n_components_

    def test_kmeans_subprocess_kill_and_resume(self, tmp_path):
        """Real host preemption: the child process is os._exit-killed by
        the env fault plan at chunk 2 of the fit; the parent resumes from
        the surviving checkpoint and must match the uninterrupted run."""
        d = str(tmp_path / "ck")
        child = (
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "jax.config.update('jax_enable_x64', True)\n"  # mirror conftest
            "import heat_tpu as ht\n"
            "ht.random.seed(13)\n"
            "x = ht.random.randn(240, 6, split=0).astype(ht.float32)\n"
            f"ht.cluster.KMeans(n_clusters=4, init='random', max_iter=40, tol=1e-4,\n"
            f"                  random_state=3, checkpoint_every=2, checkpoint_dir={d!r}).fit(x)\n"
        )
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["HEAT_TPU_FAULT_PLAN"] = json.dumps(
            {"plan": {"kmeans.iter": [{"at": 1, "kind": "kill", "exit_code": 137}]}}
        )
        proc = subprocess.run(
            [sys.executable, "-c", child], env=env, capture_output=True, timeout=300
        )
        assert proc.returncode == 137, proc.stderr.decode()[-2000:]
        assert Checkpointer(d).latest_step() is not None  # chunk 1 survived
        x = _data()
        plain = ht.cluster.KMeans(
            n_clusters=4, init="random", max_iter=40, tol=1e-4, random_state=3
        ).fit(x)
        resumed = ht.cluster.KMeans(
            n_clusters=4, init="random", max_iter=40, tol=1e-4, random_state=3,
            checkpoint_every=2, resume_from=d,
        ).fit(x)
        assert np.array_equal(
            np.asarray(plain.cluster_centers_._dense()),
            np.asarray(resumed.cluster_centers_._dense()),
        )

    def test_checkpoint_every_requires_dir(self):
        with pytest.raises(ValueError):
            ht.cluster.KMeans(n_clusters=2, checkpoint_every=5)
        with pytest.raises(ValueError):
            ht.regression.Lasso(checkpoint_every=0, checkpoint_dir="/tmp/x")


# ----------------------------------------------------------------------
# divergence guard
# ----------------------------------------------------------------------
class TestGuardFinite:
    def test_passthrough_and_raise(self):
        a = np.asarray([1.0, 2.0])
        assert rz.guard_finite(a, "v") is a
        with pytest.raises(rz.DivergenceError) as e:
            rz.guard_finite(np.asarray([1.0, np.inf]), "centers",
                            iteration=7, last_good=a, last_good_iteration=6)
        assert e.value.iteration == 7
        assert e.value.last_good_iteration == 6
        np.testing.assert_allclose(e.value.last_good, a)

    def test_integer_arrays_are_finite(self):
        assert rz.all_finite(np.arange(5))

    def test_kmeans_divergence_detected(self, tmp_path):
        bad = ht.array(np.full((32, 4), np.nan, np.float32), split=0)
        with pytest.raises(rz.DivergenceError) as e:
            ht.cluster.KMeans(
                n_clusters=2, init="random", max_iter=10, random_state=0,
                checkpoint_every=2, checkpoint_dir=str(tmp_path),
            ).fit(bad)
        assert e.value.iteration is not None
        assert e.value.last_good is not None  # structured last-good payload


# ----------------------------------------------------------------------
# dispatch compile-failure fallback + comm/init sites
# ----------------------------------------------------------------------
class TestDispatchFallback:
    def test_injected_compile_fault_falls_back_to_eager(self):
        a = ht.arange(16, dtype=ht.float32, split=0)
        dispatch.clear_cache()
        before = dispatch.cache_stats()["compile_fallbacks"]
        with rz.fault_plan({"dispatch.compile": [0]}):
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                out = float((a + 5.0).sum())
        assert out == float(np.arange(16, dtype=np.float32).sum() + 5.0 * 16)
        stats = dispatch.cache_stats()
        assert stats["compile_fallbacks"] == before + 1
        assert any("falling back to eager" in str(x.message) for x in w)
        # the broken entry was dropped: the op recompiles cleanly after
        assert float((a + 5.0).sum()) == out

    def test_genuine_errors_still_raise(self):
        a = ht.arange(8, dtype=ht.float32, split=0)
        b = ht.arange(6, dtype=ht.float32, split=0)
        with pytest.raises(Exception):
            (a + b).sum()  # shape mismatch surfaces from the eager path too

    def test_init_retries_transient_bootstrap_fault(self):
        with rz.fault_plan({"comm.init": [0]}) as inj:
            ht.parallel.init()  # transient at attempt 0, clean no-op retry
        assert inj.injected["comm.init"] == [(0, "transient")]
        assert inj.hits["comm.init"] >= 2
        assert ht.parallel.is_initialized()

    def test_collective_site_evaluated(self):
        comm = ht.get_comm()
        with rz.fault_plan({}) as inj:
            # trace-time evaluation of the injection point, no fault scripted
            try:
                import jax

                jax.eval_shape(
                    lambda v: comm.psum(v),
                    jax.ShapeDtypeStruct((4,), np.float32),
                )
            except Exception:
                pass  # psum outside shard_map may reject; the site still counts
        assert inj.hits.get("comm.collective", 0) >= 1


class TestResilienceStats:
    def test_merged_counters(self):
        rz.reset_retry_stats()
        rz.reset_fault_stats()
        with rz.fault_plan({"s": [0]}):
            with pytest.raises(rz.TransientFault):
                rz.inject("s")
        s = rz.resilience_stats()
        assert s["faults_injected"] == 1 and s["sites_evaluated"] == 1
        assert "retries" in s and "gave_up" in s
