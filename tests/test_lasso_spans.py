"""Spans and counters of ``Lasso.fit`` (PR 39), the pattern of the KMedians
fit's (``tests/test_kmedians_spans.py``): the root with the plan's fields and
its ``passes``, the loop's span inside it, one external dispatch a launch,
nothing read back inside ``fit``, the scopes in the compiled text, and the
benchmark's five readers of them.  All on the CPU: counts, names and
containment, never a time.
"""

import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core import dispatch
from heat_tpu.parallel.comm import Communication
from heat_tpu.regression import lasso

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.run import load_py  # noqa: E402

ROWS, COLS = 600, 7
ROOT, LOOP = "ht.regression.Lasso.fit", "lasso.loop"


@pytest.fixture()
def one_device():
    ht.use_comm(Communication(jax.devices()[:1]))
    prev = telemetry.set_tracing(True)
    try:
        yield
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()
        ht.use_comm(ht.WORLD)


def _data(rows=ROWS, cols=COLS):
    rng = np.random.default_rng(7)
    a = rng.standard_normal((rows, cols)).astype(np.float32)
    y = (a[:, :1] * 1.5 - a[:, 2:3] + 0.5 + 0.1 * rng.standard_normal((rows, 1))).astype(np.float32)
    return ht.array(a, split=0), ht.array(y, split=0)


def _fit(x, y, **kw):
    return ht.regression.Lasso(lam=5.0, max_iter=9, tol=-1.0, **kw).fit(x, y)


def _end(rec):
    return rec.start_ns + rec.duration_ns


@pytest.mark.parametrize("rows, cols, form, passes", [(ROWS, COLS, "gram", 2), (4099, 128, "gram", 1), (12, 30, "residual", 19)],
                         ids=["gram", "gram_through_the_kernel", "residual"])
def test_fit_leaves_its_spans(one_device, rows, cols, form, passes):
    """The root carries the plan: which form runs, and the reads of the table
    a fit makes (the Gram's and the moments'; ONE where the kernel takes the
    table, a tile of rows and more at a width of 128, and reads the moments
    from the Gram's tiles (PR 40); on a wide table the sums of squares', and a
    sweep's residual and its columns)."""
    x, y = _data(rows, cols)
    _fit(x, y)  # the first call compiles
    telemetry.clear_spans()
    _fit(x, y)
    spans = sorted(telemetry.get_spans(), key=lambda r: r.start_ns)
    assert [r.name for r in spans] == [ROOT, LOOP]
    root, loop = spans
    assert root.depth == 0 and root.attrs == {"rows": rows, "features": cols, "split": 0, "max_iter": 9,
                                              "form": form, "passes": passes}
    assert loop.depth == 1 and loop.attrs == {"chunked": False}
    assert root.start_ns <= loop.start_ns and _end(loop) <= _end(root)
    assert {r.thread_id for r in spans} == {threading.get_ident()}


def test_one_external_dispatch_a_fit_and_nothing_read_back(one_device):
    x, y = _data()
    _fit(x, y)
    before = dispatch.cache_stats()["external_dispatches"]
    with jax.transfer_guard_device_to_host("disallow"):
        model = _fit(x, y)
    assert dispatch.cache_stats()["external_dispatches"] - before == 1  # the passes and the sweeps are one program
    assert isinstance(model._n_iter, jax.Array)  # the sweep count stays on the device until a caller looks
    assert model.n_iter == 9 and isinstance(model._n_iter, int)


def test_a_checkpointed_fit_prepares_once_and_runs_the_chunks_inside_its_loop(one_device, tmp_path):
    x, y = _data()
    before = dispatch.cache_stats()["external_dispatches"]
    _fit(x, y, checkpoint_every=4, checkpoint_dir=str(tmp_path))
    # the normal equations once, theta into the sweeps' frame, chunks of 4, 4 and 1 sweeps, and theta out of the frame
    assert dispatch.cache_stats()["external_dispatches"] - before == 2 + 3 + 1
    by_name = {r.name: r for r in telemetry.get_spans()}
    assert by_name[LOOP].attrs == {"chunked": True} and by_name[ROOT].attrs["passes"] == 2


def test_tracing_off_leaves_nothing_and_changes_no_result(one_device):
    x, y = _data()
    traced = _fit(x, y)
    telemetry.clear_spans()
    telemetry.set_tracing(False)
    plain = _fit(x, y)
    assert telemetry.get_spans() == []
    assert np.array_equal(traced.theta.numpy(), plain.theta.numpy())


def test_the_scopes_name_the_phases_in_the_compiled_text(one_device):
    x, y = _data()
    plan = dict(n=ROWS, gram=True, syrk_ok=True, comm=None, max_iter=9, phase="fit")
    text = lasso._program.lower(x.larray_padded, y.larray_padded[:, 0], (), jnp.float32(5), jnp.float32(-1),
                                jnp.zeros((COLS + 1,), jnp.float32), **plan).compile().as_text()
    for scope in ("lasso.gram", "lasso.moments", "lasso.cd"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("metric,want", [("lasso_passes", 2), ("lasso_fit_host_ms", None)])
def test_the_benchmarks_span_readers(one_device, metric, want):
    x, y = _data()
    _fit(x, y)
    telemetry.clear_spans()
    for _ in range(3):
        _fit(x, y)
    reader = load_py("layer_metrics", metric)
    run = {"solves": 3, "notes": {}}
    value = reader.read(run)
    assert run["notes"] == {} and (value == want if want is not None else value > 0)
    if metric == "lasso_fit_host_ms":
        root = [r for r in telemetry.get_spans() if r.name == ROOT]
        assert value == pytest.approx(sum(r.duration_ns for r in root) / 3e6)
    # a program without the spans (the parent's), or a window the ring does not hold: nothing, and the reason
    telemetry.clear_spans()
    run = {"solves": 3, "notes": {}}
    assert reader.read(run) is None and metric in run["notes"]


def test_the_benchmarks_trace_readers(one_device):
    """``lasso_gram_ms`` and ``lasso_gram_roofline_pct`` read the kernel by its
    name in the reduced device trace, the pass's bytes from the driver's work
    model: the share cannot pass 100 while the kernel takes the memory's time
    or more.  ``lasso_cd_us_per_update`` reads the descent's kernel by ITS
    name, over the updates the roots count (9 sweeps of 8 coordinates here),
    and nothing of the moments' pass; ``lasso_xla_ms`` is what the device's
    busy time holds beside the two kernels.  No such operation (the CPU, a
    descent of XLA operations): nothing, and the reason."""
    x, y = _data()
    _fit(x, y)
    telemetry.clear_spans()
    for _ in range(3):
        _fit(x, y)
    work = load_py("drivers", "lasso_fit").work({"rows": ROWS, "features": COLS, "max_iter": 9})
    pass_s = work["gram_pass_bytes"] / 819e9
    moments_s = 1.5 * pass_s
    trace = {"busy_s": 3 * (1.25 * pass_s + moments_s + 72 * 5e-6),
             "top_ops": [["%dynamic-slice_reduce_fusion.2 fusion (f32[128], f32[128], f32[128])", 3 * moments_s],
                         ["%gram_syrk.1 custom-call:tpu_custom_call f32[128,128]", 3 * pass_s * 1.25],
                         ["%lasso_cd.1 custom-call:tpu_custom_call (f32[1,128], s32[1,1], f32[1,1])", 3 * 72 * 5e-6]]}
    run = {"solves": 3, "notes": {}, "trace": trace, "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}, "work": work}
    assert load_py("layer_metrics", "lasso_gram_ms").read(run) == pytest.approx(1000 * pass_s * 1.25)
    assert load_py("layer_metrics", "lasso_gram_roofline_pct").read(run) == pytest.approx(80.0)
    assert load_py("layer_metrics", "lasso_cd_us_per_update").read(run) == pytest.approx(5.0)
    assert load_py("layer_metrics", "lasso_xla_ms").read(run) == pytest.approx(1000 * moments_s)
    assert run["notes"] == {"lasso_updates_a_solve": 72.0}
    # a faster moments' pass moves lasso_xla_ms and leaves the descent's reading where it was
    faster = {**run, "notes": {}, "trace": {**trace, "busy_s": trace["busy_s"] - 3 * moments_s / 2}}
    assert load_py("layer_metrics", "lasso_cd_us_per_update").read(faster) == pytest.approx(5.0)
    assert load_py("layer_metrics", "lasso_xla_ms").read(faster) == pytest.approx(500 * moments_s)


@pytest.mark.parametrize("metric, left", [("lasso_gram_ms", 1), ("lasso_gram_roofline_pct", 1), ("lasso_cd_us_per_update", 2),
                                          ("lasso_xla_ms", 2), ("lasso_xla_ms", 1)])
def test_a_trace_reader_without_its_kernel_reads_nothing(one_device, metric, left):
    """``top_ops`` without the kernel a reader looks for (the first ``left``
    of: the moments' fusion, ``gram_syrk``, ``lasso_cd``): None and the
    reason, never a remainder of what was not read."""
    x, y = _data()
    for _ in range(2):
        _fit(x, y)
    ops = [["%dynamic-slice_reduce_fusion.2 fusion", 0.3], ["%gram_syrk.1 custom-call:tpu_custom_call f32[128,128]", 0.2],
           ["%lasso_cd.1 custom-call:tpu_custom_call", 0.1]]
    work = load_py("drivers", "lasso_fit").work({"rows": ROWS, "features": COLS, "max_iter": 9})
    run = {"solves": 2, "notes": {}, "trace": {"busy_s": 1.0, "top_ops": ops[:left]}, "peaks": {"hbm_bytes_per_s": 819e9}, "work": work}
    assert load_py("layer_metrics", metric).read(run) is None and metric in run["notes"]
