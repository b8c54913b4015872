"""Chip smoke test: the main paths of heat_tpu, once, on the attached TPU.

    python chip_smoke.py [--seed N]          # one chip, every one-chip phase
    python chip_smoke.py --four-chips        # the cross-chip phase alone

One process owns the chip(s); no phase starts a child.  Every phase goes
through the public ``ht.*`` API at a size a user would call real, checks
its result against a plain NumPy / ``jax.numpy`` computation of the same
thing, and prints ONE JSON line.  The first failed check raises — nothing
turns a failed phase into a record — so the exit code is non-zero and the
closing line is never printed.  Without a TPU the script refuses before
any phase.  The last line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

All data and weights come from ``--seed``; the HDF5 input is written at
run time.  Wall times printed here are smoke wall times (compiles
included), not benchmark numbers.

The phase functions take their sizes as arguments so that
``tests/test_chip_smoke_rehearsal.py`` can run each of them tiny on the
virtual CPU mesh; ``platform`` is what every result array must live on.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond, what: str, **facts) -> None:
    if not cond:
        raise SmokeFailure(f"{what}: {facts}")


def max_rel_err(got, want) -> float:
    """max|got - want| over max|want| (one scale for the whole array)."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))), 1e-300))


def _buffers(a):
    """The device buffers behind a DNDarray (planes for a planar one) or
    a jax array — without materializing anything new."""
    planar = getattr(a, "_planar", None)
    if planar is not None:
        return list(planar)
    return [a.larray_padded if hasattr(a, "larray_padded") else a]


def check_on(platform: str, **arrays) -> list:
    """Every named result lives on ``platform`` devices only; returns the
    sorted device ids seen."""
    seen = set()
    for name, a in arrays.items():
        for buf in _buffers(a):
            devs = buf.devices()
            check(
                all(d.platform == platform for d in devs),
                f"result {name!r} is not on {platform}",
                devices=[str(d) for d in devs],
            )
            seen |= {d.id for d in devs}
    return sorted(seen)


# ----------------------------------------------------------------------
# header
# ----------------------------------------------------------------------
def phase_header(cache_dir: str) -> dict:
    import jax

    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "default_backend": jax.default_backend(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "compile_cache_dir": cache_dir,
        "compile_cache_placed_by_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
    }


# ----------------------------------------------------------------------
# array core
# ----------------------------------------------------------------------
def phase_array_core(seed: int, platform: str, n: int = 2**24, f: int = 16,
                     sort_n: int = 2**24) -> dict:
    import heat_tpu as ht
    from heat_tpu.core import dispatch

    a = ht.arange(10, split=0)
    check(a.numpy().tolist() == list(range(10)), "arange(10, split=0)")
    check(float(a.sum().item()) == 45.0, "arange(10).sum()")

    ht.random.seed(seed)
    x = ht.random.randn(n, f, split=0)
    check(x.shape == (n, f) and x.dtype == ht.float32, "randn shape/dtype", shape=x.shape)
    xn = x.numpy()  # the reference computes on exactly these values

    def chain(v):
        return ht.sqrt(ht.abs(v) * 2.0 + 1.0) - 0.5

    y = chain(x)
    total = float(y.sum().item())
    col_mean = y.mean(axis=0).numpy()
    yn = np.sqrt(np.abs(xn) * np.float32(2.0) + np.float32(1.0)) - np.float32(0.5)
    want_total = float(yn.sum(dtype=np.float64))
    want_mean = yn.mean(axis=0, dtype=np.float64)
    step = max(n // 1024, 1)
    chain_sample = max_rel_err(y[::step].numpy(), yn[::step])
    sum_err = abs(total - want_total) / abs(want_total)
    mean_err = max_rel_err(col_mean, want_mean)
    check(chain_sample < 1e-6, "elementwise chain vs numpy (row subsample)", err=chain_sample)
    check(sum_err < 1e-4, "sum of the chain vs numpy float64", err=sum_err)
    check(mean_err < 1e-4, "mean(axis=0) of the chain vs numpy", err=mean_err)
    devices = check_on(platform, x=x, y=y)
    del yn

    # warm: the same chain + reductions again must hit the executable cache
    s0 = dispatch.cache_stats()
    y2 = chain(x)
    float(y2.sum().item())
    y2.mean(axis=0).numpy()
    s1 = dispatch.cache_stats()
    lookups = (s1["hits"] - s0["hits"]) + (s1["misses"] - s0["misses"])
    warm_hit_rate = (s1["hits"] - s0["hits"]) / lookups if lookups else 0.0
    check(lookups > 0 and s1["misses"] == s0["misses"],
          "warm dispatch recompiled", before=s0, after=s1)
    del y, y2

    # resplit_: same values, new split axis
    col_sum_want = xn.sum(axis=0, dtype=np.float64)
    x.resplit_(1)
    check(x.split == 1, "resplit_(1) split", split=x.split)
    resplit_err = max_rel_err(x.sum(axis=0).numpy(), col_sum_want)
    row = n // 3
    check(np.array_equal(x[row].numpy(), xn[row]), "row after resplit_(1)")
    check(resplit_err < 1e-4, "column sums after resplit_(1)", err=resplit_err)
    check_on(platform, x_resplit=x)
    del x, xn

    v = ht.random.randn(sort_n, split=0)
    vn = v.numpy()
    sv, si = ht.sort(v)
    svn, sin_ = sv.numpy(), si.numpy()
    check(np.array_equal(svn, np.sort(vn)), "sort values vs numpy.sort")
    check(np.array_equal(vn[sin_], svn), "sort indices gather the sorted values")
    check_on(platform, sorted=sv, sort_index=si)

    return {
        "rows": n, "features": f, "bytes": n * f * 4, "sort_n": sort_n,
        "chain_row_subsample_rel_err": chain_sample, "sum_rel_err": sum_err,
        "mean_rel_err": mean_err, "resplit_colsum_rel_err": resplit_err,
        "warm_dispatch_hit_rate": warm_hit_rate, "warm_lookups": lookups,
        "devices": devices,
    }


# ----------------------------------------------------------------------
# ingest + fit
# ----------------------------------------------------------------------
def make_blobs(seed: int, n: int, f: int, k: int):
    """Seeded k-blob data with per-feature scale and offset (so that the
    scaler has work), plus initial centers a fixed perturbation away from
    the truth.  float32, built in bulk."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(0.0, 3.0, size=(k, f)).astype(np.float32)
    labels = rng.integers(0, k, size=n)
    x = rng.standard_normal((n, f), dtype=np.float32)
    x += centers[labels]
    scale = np.linspace(0.5, 4.0, f, dtype=np.float32)
    offset = np.linspace(-10.0, 10.0, f, dtype=np.float32)
    x *= scale
    x += offset
    init = centers + rng.normal(0.0, 1.5, size=(k, f)).astype(np.float32)
    return x, init * scale + offset


def numpy_lloyd(x: np.ndarray, centers: np.ndarray, max_iter: int, tol: float):
    """Plain Lloyd from ``centers``: argmin assignment, float64 cluster
    means, stop when the squared center shift is <= tol.  Returns
    (centers, n_iter, inertia against the final centers)."""
    c = centers.astype(np.float32)
    k, f = c.shape
    n_iter = 0
    for _ in range(max_iter):
        d = (c * c).sum(axis=1)[None, :] - 2.0 * (x @ c.T)
        lab = d.argmin(axis=1)
        cnt = np.bincount(lab, minlength=k)
        sums = np.stack(
            [np.bincount(lab, weights=x[:, j], minlength=k) for j in range(f)], axis=1
        )
        new = np.where(cnt[:, None] > 0, sums / np.maximum(cnt, 1)[:, None], c).astype(np.float32)
        shift = float(((new - c) ** 2).sum())
        c = new
        n_iter += 1
        if shift <= tol:
            break
    d = (c * c).sum(axis=1)[None, :] - 2.0 * (x @ c.T)
    inertia = float(((x.astype(np.float64) ** 2).sum(axis=1) + d.min(axis=1)).sum())
    return c, n_iter, inertia


def phase_ingest_fit(seed: int, platform: str, n: int = 2**24, f: int = 16, k: int = 8,
                     max_iter: int = 5) -> dict:
    import h5py

    import heat_tpu as ht

    raw, init = make_blobs(seed, n, f, k)
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        path = os.path.join(tmp, "points.h5")
        with h5py.File(path, "w") as h:
            h.create_dataset("data", data=raw)
        x = ht.load_hdf5(path, "data", split=0)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    check(x.shape == (n, f) and x.split == 0, "load_hdf5 shape/split", shape=x.shape)

    scaler = ht.preprocessing.StandardScaler()
    xs = scaler.fit_transform(x)
    mean64 = raw.mean(axis=0, dtype=np.float64)
    std64 = raw.std(axis=0, dtype=np.float64)
    mean_err = max_rel_err(scaler.mean_.numpy(), mean64)
    var_err = max_rel_err(scaler.var_.numpy(), std64**2)
    step = max(n // 4096, 1)
    want_rows = ((raw[::step] - mean64) / std64).astype(np.float32)
    rows_err = max_rel_err(xs[::step].numpy(), want_rows)
    check(mean_err < 1e-4, "scaler mean_ vs numpy float64", err=mean_err)
    check(var_err < 1e-3, "scaler var_ vs numpy float64", err=var_err)
    check(rows_err < 1e-4, "scaled rows vs numpy (row subsample)", err=rows_err)

    # the reference fits the SAME standardized values the device holds
    xsn = xs.numpy()
    init_s = ((init - mean64) / std64).astype(np.float32)
    km = ht.cluster.KMeans(n_clusters=k, init=ht.array(init_s), max_iter=max_iter, tol=0.0)
    km.fit(xs)
    centers = km.cluster_centers_.numpy()
    inertia = float(km.inertia_)
    n_iter = int(km.n_iter_)
    want_c, want_iter, want_inertia = numpy_lloyd(xsn, init_s, max_iter, 0.0)
    centers_err = float(np.max(np.abs(centers - want_c)))
    inertia_err = abs(inertia - want_inertia) / want_inertia
    check(np.isfinite(centers).all(), "centers finite")
    check(centers_err < 5e-3, "KMeans centers vs NumPy Lloyd (standardized units)",
          err=centers_err, n_iter=n_iter, want_iter=want_iter)
    check(inertia_err < 5e-3, "KMeans inertia vs NumPy Lloyd", err=inertia_err,
          got=inertia, want=want_inertia)
    labels = km.labels_
    check(labels.shape == (n,), "labels shape", shape=labels.shape)
    devices = check_on(platform, loaded=x, scaled=xs, centers=km.cluster_centers_, labels=labels)
    return {
        "rows": n, "features": f, "clusters": k, "max_iter": max_iter,
        "n_iter": n_iter, "numpy_n_iter": want_iter,
        "scaler_mean_rel_err": mean_err, "scaler_var_rel_err": var_err,
        "scaled_rows_rel_err": rows_err, "centers_max_abs_err": centers_err,
        "inertia_rel_err": inertia_err, "devices": devices,
    }


# ----------------------------------------------------------------------
# hSVD
# ----------------------------------------------------------------------
def host_gram_float64(a: np.ndarray, block: int = 1 << 18) -> np.ndarray:
    n = a.shape[1]
    g = np.zeros((n, n), np.float64)
    for start in range(0, a.shape[0], block):
        blk = a[start : start + block].astype(np.float64)
        g += blk.T @ blk
    return g


def phase_hsvd(seed: int, platform: str, m: int = 2**22, n: int = 128, rank: int = 10) -> dict:
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.core import kernels
    from heat_tpu.core.linalg import svdtools

    ht.random.seed(seed + 1)
    # decaying column scales: a spectrum with a well separated top
    scales = np.geomspace(1.0, 1e-3, n).astype(np.float32)
    a = ht.random.randn(m, n, split=0) * ht.array(scales)
    u, s, v, rel_err = ht.linalg.hsvd_rank(a, rank, compute_sv=True)
    check(u.shape == (m, rank) and s.shape == (rank,) and v.shape == (n, rank),
          "hsvd factor shapes", u=u.shape, s=s.shape, v=v.shape)

    an = a.numpy()
    lam = np.linalg.eigvalsh(host_gram_float64(an))[::-1]
    want_s = np.sqrt(np.maximum(lam[:rank], 0.0))
    sv_err = float(np.max(np.abs(s.numpy() - want_s) / want_s))
    want_rel = float(np.sqrt(max(lam[rank:].sum(), 0.0) / lam.sum()))
    rel_gap = abs(float(rel_err) - want_rel)
    utu = ht.matmul(u.T, u).numpy()
    ortho_err = float(np.max(np.abs(utu - np.eye(rank))))
    step = max(m // 2048, 1)
    recon = (u[::step].numpy() * s.numpy()) @ v.numpy().T
    resid = an[::step] - recon
    # rows of A minus its rank-k projection keep exactly the discarded energy
    recon_rel = float(np.linalg.norm(resid) / np.linalg.norm(an[::step]))
    check(sv_err < 1e-4, "singular values vs float64 Gram eigenvalues", err=sv_err)
    check(rel_gap < 1e-3, "hsvd error estimate vs float64 Gram", got=float(rel_err), want=want_rel)
    check(ortho_err < 1e-3, "U^T U vs identity", err=ortho_err)
    check(abs(recon_rel - want_rel) < 2e-2, "rank-k reconstruction residual (row subsample)",
          got=recon_rel, want=want_rel)
    devices = check_on(platform, u=u, s=s, v=v)

    # which Gram ran: the same static gate _gram() evaluates, plus what
    # the kernel lowers to on this backend
    syrk_selected = (
        a.comm.size == 1
        and svdtools._gram_precision() is not jax.lax.Precision.HIGHEST
        and os.environ.get("HEAT_TPU_HSVD_SYRK", "1") == "1"
        and kernels.syrk_supported(m, n, jnp.float32)
    )
    syrk_compiled = None
    if syrk_selected:
        hlo = jax.jit(kernels.gram_syrk).lower(
            jax.ShapeDtypeStruct((m, n), jnp.float32)
        ).compile().as_text()
        syrk_compiled = (not kernels._interpret()) and "tpu_custom_call" in hlo
        if platform == "tpu":
            check(syrk_compiled, "gram_syrk did not lower to a compiled TPU kernel",
                  interpret=kernels._interpret())
    return {
        "shape": [m, n], "rank": rank, "sv_max_rel_err": sv_err,
        "rel_err_estimate": float(rel_err), "rel_err_float64": want_rel,
        "utu_max_abs_err": ortho_err, "recon_rel_resid_subsample": recon_rel,
        "gram_syrk_selected": bool(syrk_selected), "gram_syrk_compiled_kernel": syrk_compiled,
        "devices": devices,
    }


# ----------------------------------------------------------------------
# FFT
# ----------------------------------------------------------------------
class _planar_route:
    """``HEAT_TPU_PLANAR=1`` for the duration (the knob is read per call)."""

    def __enter__(self):
        self._old = os.environ.get("HEAT_TPU_PLANAR")
        os.environ["HEAT_TPU_PLANAR"] = "1"

    def __exit__(self, *exc):
        if self._old is None:
            del os.environ["HEAT_TPU_PLANAR"]
        else:
            os.environ["HEAT_TPU_PLANAR"] = self._old
        return False


def phase_fft(seed: int, platform: str, n: int = 512, ref_n: int = 128) -> dict:
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.fft import _leading

    # (heat_tpu.fft.fft, the attribute, is the function of that name)
    fftmod = importlib.import_module("heat_tpu.fft.fft")

    # complex64 in the process that holds the chip: put, multiply, fetch
    z = np.asarray(jax.device_put(np.full((4,), 1 + 2j, np.complex64)) ** 2)
    complex_native = bool(np.allclose(z, (1 + 2j) ** 2))
    check(complex_native, "complex64 compute + transfer on the device", got=z.tolist())

    ht.random.seed(seed + 2)
    small = ht.random.randn(ref_n, ref_n, ref_n, split=0)
    want_small = np.fft.fftn(small.numpy().astype(np.float64))
    big = ht.random.randn(n, n, n, split=0)
    energy = float((big * big).sum().item())
    total = float(big.sum().item())
    out = {"n": n, "ref_n": ref_n, "complex64_native": complex_native}

    # default route
    check(not fftmod._use_planar(), "HEAT_TPU_PLANAR is set; the default route is not default")
    ys = ht.fft.fftn(small)
    yb = ht.fft.fftn(big)
    check(yb._planar is None and yb.dtype == ht.complex64, "default route output",
          dtype=str(yb.dtype), planar=yb._planar is not None)
    d_ref = max_rel_err(ys.numpy(), want_small)
    d_energy = float((ht.real(yb) ** 2).sum().item() + (ht.imag(yb) ** 2).sum().item()) / n**3
    d_parseval = abs(d_energy - energy) / energy
    d_dc = abs(complex(yb[0, 0, 0].item()).real - total) / max(abs(total), 1.0)
    check(d_ref < 1e-4, f"default fftn vs numpy at {ref_n}^3", err=d_ref)
    check(d_parseval < 1e-4, f"default fftn Parseval at {n}^3", err=d_parseval)
    check(d_dc < 1e-2, "default fftn DC bin vs sum(x)", err=d_dc)
    check_on(platform, default_small=ys, default_big=yb)
    out["default"] = {
        "route": "jnp.fft on native complex64 (no planes)",
        "ref_rel_err": d_ref, "parseval_rel_err": d_parseval, "dc_rel_err": d_dc,
    }

    # planar route
    with _planar_route():
        ps = ht.fft.fftn(small)
        pb = ht.fft.fftn(big)
        check(pb._planar is not None, "HEAT_TPU_PLANAR=1 did not give a planar result")
        p_ref = max_rel_err(ps.numpy(), want_small)
        re, im = pb._planar
        p_energy = (float(jnp.sum(re * re)) + float(jnp.sum(im * im))) / n**3
        p_parseval = abs(p_energy - energy) / energy
        check_on(platform, planar_small=ps, planar_big=pb)
        axes_ns = tuple((d, None) for d in range(3))
        hlo = fftmod._planar_prog("fft", None, axes_ns).lower(big.larray_padded, None).compile().as_text()
    # the two engines against each other at full size, on the device
    cross = float(
        jnp.max(jnp.abs(yb.larray_padded - jax.lax.complex(re, im)))
        / jnp.max(jnp.abs(yb.larray_padded))
    )
    m = n // 2
    stage_gate = _leading._use_fused_stage(n, n * m, n) and _leading._stage_tile(m) is not None
    ext_gate = _leading._use_pallas_ext(n, n)
    custom_calls = hlo.count("tpu_custom_call")
    check(p_ref < 1e-4, f"planar fftn vs numpy at {ref_n}^3", err=p_ref)
    check(p_parseval < 1e-4, f"planar fftn Parseval at {n}^3", err=p_parseval)
    check(cross < 1e-4, f"planar vs default fftn at {n}^3", err=cross)
    if platform == "tpu":
        check(stage_gate and ext_gate and custom_calls >= 2,
              "the leading engine's stage / extension kernels did not run compiled",
              stage_gate=stage_gate, ext_gate=ext_gate, tpu_custom_calls=custom_calls)
    out["planar"] = {
        "route": "planar engine: rfft3_leading (HEAT_TPU_PLANAR=1)",
        "ref_rel_err": p_ref, "parseval_rel_err": p_parseval,
        "vs_default_rel_err": cross, "stage_kernel_selected": bool(stage_gate),
        "extension_kernel_selected": bool(ext_gate), "tpu_custom_calls_in_program": custom_calls,
    }
    return out


# ----------------------------------------------------------------------
# training
# ----------------------------------------------------------------------
def make_digits(seed: int, n_batches: int, batch: int):
    """Seeded 28x28 'digits': ten fixed templates plus noise; learnable."""
    rng = np.random.default_rng(seed)
    templates = rng.standard_normal((10, 28, 28, 1)).astype(np.float32)
    labels = rng.integers(0, 10, size=(n_batches, batch)).astype(np.int32)
    images = templates[labels] + 0.5 * rng.standard_normal(
        (n_batches, batch, 28, 28, 1), dtype=np.float32
    )
    return images, labels


def tree_max_rel_err(got, want) -> float:
    import jax

    errs = jax.tree_util.tree_map(lambda g, w: max_rel_err(np.asarray(g), np.asarray(w)), got, want)
    return max(jax.tree_util.tree_leaves(errs))


def phase_training(seed: int, platform: str, batch: int = 256, steps: int = 20,
                   scan_steps: int = 16) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    import heat_tpu as ht
    from __graft_entry__ import _cnn

    model = _cnn()
    images, labels = make_digits(seed + 3, scan_steps, batch)

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    dp = ht.nn.DataParallel(model, optimizer=optax.adam(1e-3))
    x0, y0 = ht.array(images[0], split=0), ht.array(labels[0], split=0)
    dp.init(jax.random.PRNGKey(seed), x0)
    loss0, grads = dp.value_and_grad(loss_fn, x0, y0)

    # reference: jax.grad of the same flax model on plain arrays
    def ref_loss(p, x, y):
        return loss_fn(model.apply(p, x), y)

    want_loss, want_grads = jax.jit(jax.value_and_grad(ref_loss))(
        dp.params, jnp.asarray(images[0]), jnp.asarray(labels[0])
    )
    grad_err = tree_max_rel_err(grads, want_grads)
    loss_err = abs(float(loss0) - float(want_loss)) / abs(float(want_loss))
    check(grad_err < 1e-3, "first-step gradients vs jax.grad of the flax model", err=grad_err)
    check(loss_err < 1e-4, "first-step loss vs the flax model", err=loss_err)

    step_losses = [
        dp.step(loss_fn, ht.array(images[i % scan_steps], split=0),
                ht.array(labels[i % scan_steps], split=0))
        for i in range(steps)
    ]
    scan_losses = np.asarray(dp.train_steps(loss_fn, jnp.asarray(images), jnp.asarray(labels)))
    losses = np.asarray(step_losses + scan_losses.tolist())
    check(scan_losses.shape == (scan_steps,), "train_steps losses shape", shape=scan_losses.shape)
    check(np.isfinite(losses).all(), "training losses finite", losses=losses.tolist())
    check(losses[-1] < 0.9 * losses[0], "loss did not fall", first=float(losses[0]),
          last=float(losses[-1]))
    devices = check_on(platform, **{f"param{i}": p for i, p in enumerate(jax.tree_util.tree_leaves(dp.params))})
    return {
        "model": "CNN of __graft_entry__._cnn", "batch": batch, "steps": steps,
        "scan_steps": scan_steps, "grad_max_rel_err": grad_err, "loss_rel_err": loss_err,
        "first_loss": float(losses[0]), "last_loss": float(losses[-1]), "devices": devices,
    }


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------
def phase_attention(seed: int, platform: str, seq: int = 8192, heads: int = 16, dim: int = 128,
                    ref_seq: int = 1024) -> dict:
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.nn import attention as attn

    ht.random.seed(seed + 4)
    q, k, v = (ht.random.randn(seq, heads, dim) for _ in range(3))
    out = ht.nn.scaled_dot_product_attention(q, k, v, method="flash")
    od = out.larray_padded
    check(out.shape == (seq, heads, dim), "attention output shape", shape=out.shape)
    check(bool(jnp.isfinite(od).all()), "attention output finite")
    devices = check_on(platform, attention=out)

    rec = {"shape": [seq, heads, dim], "ref_seq": ref_seq, "devices": devices}
    if platform == "tpu":
        # proof of the kernel: method="flash" has no fallback, the kernel
        # is available here, what it lowers to is a TPU custom call, and
        # that compiled kernel reproduces the API's result
        check(attn._flash_available(), "flash kernel not available on this backend")
        scale = 1.0 / float(np.sqrt(dim))
        direct = jax.jit(lambda a, b, c: attn._local_flash(a, b, c, scale, False, seq))
        args = (q.larray_padded, k.larray_padded, v.larray_padded)
        hlo = direct.lower(*args).compile().as_text()
        kernel_diff = float(jnp.max(jnp.abs(direct(*args) - od)))
        check("tpu_custom_call" in hlo, "_local_flash did not lower to a TPU kernel")
        check(kernel_diff <= 1e-6, "API result differs from the compiled flash kernel",
              diff=kernel_diff)
        rec.update(flash_kernel=True, tpu_custom_calls=hlo.count("tpu_custom_call"),
                   api_vs_kernel_max_abs_diff=kernel_diff)
    else:
        rec.update(flash_kernel=False)

    qs, ks, vs = (t[:ref_seq] for t in (q, k, v))
    got = ht.nn.scaled_dot_product_attention(qs, ks, vs, method="flash").numpy()
    # split=None and method="ring" is the local einsum path (HIGHEST precision)
    want = ht.nn.scaled_dot_product_attention(qs, ks, vs, method="ring").numpy()
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    check(rel < 3e-2, f"flash vs einsum path at {ref_seq} tokens (Frobenius)", err=rel)
    rec["flash_vs_einsum_rel_fro_err"] = rel
    return rec


# ----------------------------------------------------------------------
# the path across chips
# ----------------------------------------------------------------------
def phase_four_chips(seed: int, platform: str, comm, n: int = 2**24, f: int = 16, k: int = 8,
                     qr_shape=(2**20, 128), fft_n: int = 256, sort_n: int = 2**24,
                     batch: int = 256, attn_shape=(4096, 16, 128)) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    import heat_tpu as ht
    from heat_tpu.parallel.comm import Communication
    from __graft_entry__ import _cnn

    p = comm.size
    check(p == 4, "the cross-chip phase needs a 4-device communication", size=p)
    rec = {"comm_size": p}
    single = Communication(comm.devices[:1])

    # placement: four shards on four distinct devices, bytes on each
    raw, init = make_blobs(seed, n, f, k)
    x = ht.array(raw, split=0, comm=comm)
    shards = x.larray_padded.addressable_shards
    shard_devs = sorted(s.device.id for s in shards)
    check(len(shards) == 4 and len(set(shard_devs)) == 4, "split=0 array is not on four devices",
          devices=shard_devs)
    check(all(s.data.shape == (n // 4, f) for s in shards), "shard shapes",
          shapes=[s.data.shape for s in shards])
    in_use = {}
    for d in comm.devices:
        stats = d.memory_stats()
        if stats is not None:  # the CPU backend reports none
            in_use[str(d.id)] = stats["bytes_in_use"]
            check(stats["bytes_in_use"] >= (n // 4) * f * 4,
                  "a device holds less than its shard", device=str(d), stats=stats)
    rec["placement"] = {"shard_devices": shard_devs, "bytes_in_use": in_use}

    # KMeans: the split fit against the same fit on one device
    init_d = ht.array(init, comm=comm)
    km4 = ht.cluster.KMeans(n_clusters=k, init=init_d, max_iter=5, tol=0.0).fit(x)
    x1 = ht.array(raw, split=0, comm=single)
    km1 = ht.cluster.KMeans(n_clusters=k, init=ht.array(init, comm=single), max_iter=5, tol=0.0).fit(x1)
    scale = float(np.abs(raw).max())
    c_err = float(np.max(np.abs(km4.cluster_centers_.numpy() - km1.cluster_centers_.numpy()))) / scale
    i_err = abs(float(km4.inertia_) - float(km1.inertia_)) / float(km1.inertia_)
    check(c_err < 1e-4, "KMeans centers: 4 devices vs 1", err=c_err)
    check(i_err < 1e-4, "KMeans inertia: 4 devices vs 1", err=i_err)
    check_on(platform, centers4=km4.cluster_centers_, labels4=km4.labels_)
    rec["kmeans"] = {"centers_rel_err_vs_one_device": c_err, "inertia_rel_err_vs_one_device": i_err,
                     "n_iter": int(km4.n_iter_)}
    del x1, km1

    # resplit_ and PSRS sort
    col_want = raw.sum(axis=0, dtype=np.float64)
    x.resplit_(1)
    r_shards = x.larray_padded.addressable_shards
    check(x.split == 1 and len({s.device.id for s in r_shards}) == 4, "resplit_(1) placement")
    r_err = max_rel_err(x.sum(axis=0).numpy(), col_want)
    check(r_err < 1e-4, "column sums after resplit_(1)", err=r_err)
    check(np.array_equal(x[n // 3].numpy(), raw[n // 3]), "row after resplit_(1)")
    rec["resplit"] = {"colsum_rel_err": r_err}
    del x

    vn = np.random.default_rng(seed + 5).standard_normal(sort_n, dtype=np.float32)
    sv, si = ht.sort(ht.array(vn, split=0, comm=comm))
    svn = sv.numpy()
    check(np.array_equal(svn, np.sort(vn)), "PSRS sort values vs numpy.sort")
    check(np.array_equal(vn[si.numpy()], svn), "PSRS sort indices")
    check_on(platform, sorted=sv)
    rec["sort"] = {"n": sort_n, "exact": True}

    # TS-QR
    m, w = qr_shape
    ht.random.seed(seed + 6)
    a = ht.random.randn(m, w, split=0, comm=comm)
    qf, rf = ht.qr(a)
    resid = float(ht.linalg.norm(ht.matmul(qf, rf) - a).item() / ht.linalg.norm(a).item())
    ortho = float(np.max(np.abs(ht.matmul(qf.T, qf).numpy() - np.eye(w))))
    check(qf.split == 0 and len(qf.larray_padded.addressable_shards) == 4, "Q placement")
    check(resid < 1e-5, "TS-QR residual |QR - A| / |A|", err=resid)
    check(ortho < 1e-4, "TS-QR |Q^T Q - I|", err=ortho)
    check_on(platform, q=qf, r=rf)
    rec["tsqr"] = {"shape": [m, w], "residual": resid, "orthogonality": ortho}
    del a, qf, rf

    # 3-D FFT through the all-to-all pencil
    g = ht.random.randn(fft_n, fft_n, fft_n, split=0, comm=comm)
    route = importlib.import_module("heat_tpu.fft.fft")._route(g, ((0, None), (1, None), (2, None)))
    check(route == "pencil", "the pencil path does not apply")
    y = ht.fft.fftn(g)
    f_err = max_rel_err(y.numpy(), np.fft.fftn(g.numpy().astype(np.float64)))
    check(f_err < 1e-4, f"pencil fftn vs numpy at {fft_n}^3", err=f_err)
    check_on(platform, fft=y)
    rec["fft_pencil"] = {"n": fft_n, "rel_err": f_err}
    del g, y

    # one DataParallel.step under each schedule against the single-device gradient
    model = _cnn()
    images, labels = make_digits(seed + 3, 1, batch)
    xb, yb = images[0], labels[0]
    lr = 0.1

    def loss_fn(logits, t):
        return optax.softmax_cross_entropy_with_integer_labels(logits, t).mean()

    dev0 = comm.devices[0]
    params = jax.device_put(
        jax.jit(model.init)(jax.random.PRNGKey(seed), jnp.zeros((1, 28, 28, 1), jnp.float32)),
        dev0,
    )
    want_loss, want_grads = jax.jit(
        jax.value_and_grad(lambda q_, a_, b_: loss_fn(model.apply(q_, a_), b_))
    )(params, jax.device_put(xb, dev0), jax.device_put(yb, dev0))
    rec["data_parallel"] = {}
    for schedule in ("implicit", "bucketed", "fused"):
        dp = ht.nn.DataParallel(model, comm=comm, optimizer=optax.sgd(lr), grad_reduction=schedule)
        dp.set_params(params)
        loss = dp.step(loss_fn, ht.array(xb, split=0, comm=comm), ht.array(yb, split=0, comm=comm))
        # sgd: grad = (before - after) / lr
        got = jax.tree_util.tree_map(
            lambda b, a_: (np.asarray(b) - np.asarray(a_)) / lr, params, dp.params
        )
        g_err = tree_max_rel_err(got, want_grads)
        l_err = abs(loss - float(want_loss)) / abs(float(want_loss))
        check(g_err < 2e-3, f"{schedule} gradient vs the single-device gradient", err=g_err)
        check(l_err < 1e-4, f"{schedule} loss vs the single-device loss", err=l_err)
        leaf = jax.tree_util.tree_leaves(dp.params)[0]
        check(len(leaf.devices()) == 4, f"{schedule} parameters are not replicated on four devices")
        rec["data_parallel"][schedule] = {"grad_max_rel_err": g_err, "loss_rel_err": l_err}

    # ring and ulysses attention on a sequence split over the chips
    ht.random.seed(seed + 7)
    qn, kn, vn_ = (ht.random.randn(*attn_shape, comm=comm).numpy() for _ in range(3))
    local = ht.nn.scaled_dot_product_attention(
        *(ht.array(t, comm=single) for t in (qn, kn, vn_)), method="ring"
    ).numpy()
    rec["attention"] = {"shape": list(attn_shape)}
    for method in ("ring", "ulysses"):
        o = ht.nn.scaled_dot_product_attention(
            *(ht.array(t, split=0, comm=comm) for t in (qn, kn, vn_)), method=method
        )
        check(len(o.larray_padded.addressable_shards) == 4, f"{method} output placement")
        a_err = max_rel_err(o.numpy(), local)
        check(a_err < 1e-4, f"{method} attention vs local attention", err=a_err)
        check_on(platform, **{method: o})
        rec["attention"][method + "_rel_err"] = a_err
    return rec


# ----------------------------------------------------------------------
# driver
# ----------------------------------------------------------------------
class _Counters:
    """What the process did behind the phases' backs: compile requests,
    persistent-cache hits, and child processes started."""

    def __init__(self):
        self.compile_requests = 0
        self.cache_hits = 0
        self.children = 0

    def install(self) -> None:
        import jax

        def on_event(event, **_):
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                self.compile_requests += 1
            elif event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        def on_audit(event, _args):
            if event in ("subprocess.Popen", "os.fork", "os.forkpty", "os.posix_spawn",
                         "os.system", "os.exec", "os.spawn"):
                self.children += 1

        jax.monitoring.register_event_listener(on_event)
        sys.addaudithook(on_audit)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run the cross-chip phase (and what it is compared with) on four chips, "
                         "and no other phase")
    args = ap.parse_args(argv)

    import jax

    import heat_tpu as ht
    from heat_tpu.core import dispatch
    from heat_tpu.core.compile_cache import use_compile_cache

    devs = jax.devices()
    want = 4 if args.four_chips else 1
    if devs[0].platform != "tpu" or len(devs) != want:
        print(
            f"chip_smoke: needs {want} TPU chip(s); JAX reports {len(devs)} x "
            f"{devs[0].platform!r} ({devs[0].device_kind}). Refusing to run.",
            file=sys.stderr,
        )
        return 2

    cache_dir = use_compile_cache()
    counters = _Counters()
    counters.install()

    def run(name, fn, *a, **kw):
        c0, h0, t0 = counters.compile_requests, counters.cache_hits, time.perf_counter()
        rec = fn(*a, **kw)
        rec = {
            "phase": name, "ok": True, "wall_s": round(time.perf_counter() - t0, 2),
            "compile_requests": counters.compile_requests - c0,
            "persistent_cache_hits": counters.cache_hits - h0, **rec,
        }
        print(json.dumps(rec), flush=True)

    run("header", phase_header, cache_dir)
    if args.four_chips:
        check(ht.get_comm().size == 4, "ht.get_comm().size", size=ht.get_comm().size)
        run("four_chips", phase_four_chips, args.seed, "tpu", ht.get_comm())
    else:
        run("array_core", phase_array_core, args.seed, "tpu")
        run("ingest_fit", phase_ingest_fit, args.seed, "tpu")
        run("hsvd", phase_hsvd, args.seed, "tpu")
        run("fft", phase_fft, args.seed, "tpu")
        run("training", phase_training, args.seed, "tpu")
        run("attention", phase_attention, args.seed, "tpu")

    stats = dispatch.cache_stats()
    check(stats["compile_fallbacks"] == 0, "a compile fell back to eager execution", stats=stats)
    check(counters.children == 0, "a phase started a child process", children=counters.children)
    print(json.dumps({
        "phase": "closing", "ok": True, "compile_fallbacks": stats["compile_fallbacks"],
        "dispatch_hit_rate": round(stats["hit_rate"], 4), "children_started": counters.children,
        "compile_requests": counters.compile_requests,
        "persistent_cache_hits": counters.cache_hits,
        "backend_compiles": counters.compile_requests - counters.cache_hits,
        "peak_bytes_in_use": {str(d.id): d.memory_stats()["peak_bytes_in_use"] for d in devs},
    }), flush=True)
    print(json.dumps({
        "ok": True,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
