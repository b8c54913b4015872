"""Benchmark driver: the full BASELINE grid on the attached chip.

Emits one JSON line per BASELINE config (smoke, KMeans, hSVD north star,
DP-SGD, 3-D FFT, dispatch-amortization, resilience counters, overlap-layer
stall/prefetch/bucket metrics, telemetry self-cost), then a final summary
line whose top-level fields are the
hSVD north star (so single-metric consumers keep working) with the whole
grid attached under ``"all"`` — BENCH_r{N}.json then records every config
each round and rounds stay comparable (BASELINE.md targets table).  Every
config record embeds the telemetry registry snapshot at its end
(``"telemetry"`` key, docs/observability.md).

Timing methodology: every measurement enqueues ``n_iter`` programs and
fetches one scalar at the end — the device executes in order, so one
fetch bounds all iterations and the host-device round trip (the "sync
floor") is amortized over the window instead of being subtracted per
call.

``vs_baseline`` for each config divides by the reference's per-process
compute path measured in-process: torch CPU doing the equivalent local
computation (the reference's per-rank torch kernels), on a subset where
the full size would be unreasonable on one CPU.  Every record carries
``vs_baseline_kind`` naming that baseline explicitly — the ratios are NOT
against BASELINE.json's "5x A100+MPI" north star (no A100-class baseline
exists in this repo).  A window that never clears the host-sync floor
raises :class:`MeasurementError` and is recorded as an error instead of a
number (the r2 DP-SGD 1e9 steps/s incident).

Roofline + dispersion (VERDICT r3 #2): the run opens with measured chip
anchors — peak f32/bf16 matmul GFLOP/s and streamed HBM GB/s — and every
record carries ``pct_of_peak_f32`` / ``pct_of_bw_*`` against them plus a
``timing`` block (windows, n_iter, per-window times, median/min spread),
so each number self-describes both its absolute quality and its noise.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _sync_floor() -> float:
    f = jax.jit(lambda x: x + 1.0)
    z = jnp.zeros(())
    float(f(z))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        float(f(z))
        best = min(best, time.perf_counter() - t0)
    return best


class MeasurementError(RuntimeError):
    """The timing window never rose above the host-sync floor — there is
    no measurement to report (publishing a clamp bound as throughput is
    exactly the r2 DP-SGD failure this type exists to prevent)."""


def _time_amortized(
    run_once,
    fetch_scalar,
    n_iter: int,
    sync_floor: float,
    windows: int = 3,
    min_floor_ratio: float = 50.0,
    max_iter: int = 4096,
):
    """(seconds per iteration, timing metadata): enqueue n_iter runs, one
    trailing fetch.

    Repeats the whole window ``windows`` times and keeps the best — host
    scheduling noise between runs can exceed a short iteration's compute,
    and the minimum is the standard noise-robust estimator.  The
    metadata carries every window's per-iteration time plus the
    median/min spread, so a published number self-describes its quality
    (VERDICT r3 weak #1: regression vs noise must be decidable from the
    artifacts alone).

    The window must dominate the sync floor: if ``elapsed`` is not at
    least ``min_floor_ratio`` floors, ``n_iter`` grows (x4) and the
    window re-runs, so the reported per-iteration time is a measurement
    rather than host noise.  If even ``max_iter`` iterations cannot clear
    the floor, raises :class:`MeasurementError` — the caller records an
    explicit error instead of a fabricated number."""
    def one_window():
        t0 = time.perf_counter()
        out = None
        for _ in range(n_iter):
            out = run_once()
        fetch_scalar(out)
        return time.perf_counter() - t0

    while True:
        # single probe window decides whether this n_iter clears the
        # floor; only a passing size pays for the full window set (with a
        # high sync floor the growth ladder otherwise multiplies the
        # whole bench by ~3x)
        probe = one_window()
        probe_window = max(probe - sync_floor, 0.0)
        under = probe_window < min_floor_ratio * sync_floor
        if under and n_iter < max_iter:
            n_iter = min(n_iter * 4, max_iter)
            continue
        # the passing probe is a regular window: seed the sample set with
        # it so the common no-growth case pays exactly `windows` windows
        samples = []
        if probe > sync_floor:
            samples.append(probe_window / n_iter)
        attempts = 0
        while len(samples) < windows and attempts < 3 * windows:
            attempts += 1
            elapsed = one_window()
            if elapsed > sync_floor:
                samples.append((elapsed - sync_floor) / n_iter)
            # a window at/below the sync floor is a host hiccup: skip it
            # and keep measuring (bounded retries — it must not
            # loop forever, and an underfull sample set fails the floor
            # checks below rather than publishing a 1-window "spread")
        best = min(samples) if samples else float("inf")
        window = best * n_iter
        ok = samples and window >= min_floor_ratio * sync_floor
        capped_ok = n_iter >= max_iter and samples and window > 2.0 * sync_floor
        if ok or capped_ok:
            med = float(np.median(samples))
            meta = {
                "windows": len(samples),
                "n_iter": n_iter,
                "window_s": round(window, 4),
                "per_iter_s": [round(s, 6) for s in samples],
                "median_per_iter_s": round(med, 6),
                "spread_pct": round(100.0 * (med - best) / best, 1) if best else 0.0,
                "sync_floor_s": round(sync_floor, 4),
            }
            return best, meta
        if n_iter >= max_iter:
            raise MeasurementError(
                f"window of {n_iter} iterations ({window:.4f}s) never cleared "
                f"{min_floor_ratio}x the sync floor ({sync_floor:.4f}s)"
            )
        n_iter = min(n_iter * 4, max_iter)


#: every ``vs_baseline`` below divides by this baseline — label it so the
#: ratios cannot be misread as the BASELINE.json "5x A100+MPI" north star
#: (no A100-class measurement exists in this repo)
BASELINE_KIND = "torch_cpu_single_process_subset"


# ---------------------------------------------------------------- roofline


def bench_roofline(ht, sync_floor):
    """Chip roofline anchors, measured once per bench run (VERDICT r3 #2):
    peak matmul FLOP/s (f32 and bf16-input/f32-accumulate — the MXU
    paths) and streamed HBM bandwidth (read+write elementwise kernel).
    Every other record divides by these so "is X GFLOP/s good?" is
    answerable from the artifact alone."""
    n = 4096
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
    mm = jax.jit(lambda x, y: x @ y)  # DEFAULT policy: bf16 passes on TPU
    float(mm(a, b)[0, 0])
    per, meta_f32 = _time_amortized(lambda: mm(a, b), lambda o: float(o[0, 0]), 5, sync_floor)
    peak_f32 = 2.0 * n**3 / per / 1e9

    mmh = jax.jit(
        lambda x, y: jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)
    )  # the 6-pass f32-accurate policy the linalg layer forces for f32
    float(mmh(a, b)[0, 0])
    per_h, meta_hi = _time_amortized(lambda: mmh(a, b), lambda o: float(o[0, 0]), 5, sync_floor)
    peak_f32_highest = 2.0 * n**3 / per_h / 1e9

    ab, bb = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    mmb = jax.jit(lambda x, y: jnp.matmul(x, y, preferred_element_type=jnp.float32))
    float(mmb(ab, bb)[0, 0])
    per_b, meta_bf16 = _time_amortized(lambda: mmb(ab, bb), lambda o: float(o[0, 0]), 5, sync_floor)
    peak_bf16 = 2.0 * n**3 / per_b / 1e9

    m = 1 << 27  # 512 MiB read + 512 MiB write in f32
    x = jax.random.normal(jax.random.PRNGKey(2), (m,), jnp.float32)
    stream = jax.jit(lambda v: v * 1.000001 + 0.5)
    float(stream(x)[0])
    per_s, meta_bw = _time_amortized(lambda: stream(x), lambda o: float(o[0]), 5, sync_floor)
    bw = 2.0 * 4.0 * m / per_s / 1e9

    # per-program dispatch floor: the serial cost of launching one
    # trivial program is the latency regime's roofline — tiny-step
    # metrics (dpsgd) anchor against it, not against matmul peak
    # (VERDICT r4 weak #8)
    f0 = jax.jit(lambda v: v + 1.0)
    z0 = jnp.zeros(())
    float(f0(z0))
    per_d, meta_disp = _time_amortized(lambda: f0(z0), lambda o: float(o), 256, sync_floor)

    return {
        "metric": "roofline",
        "value": round(peak_f32, 1),
        "unit": "GFLOP/s_f32_peak",
        "vs_baseline": 1.0,
        "vs_baseline_kind": "self",
        "peak_f32_matmul_gflops": round(peak_f32, 1),
        "peak_f32_highest_matmul_gflops": round(peak_f32_highest, 1),
        "peak_bf16_matmul_gflops": round(peak_bf16, 1),
        "hbm_stream_gbytes_per_s": round(bw, 1),
        "dispatch_floor_ms": round(per_d * 1e3, 4),
        "timing": {
            "f32": meta_f32, "f32_highest": meta_hi, "bf16": meta_bf16,
            "stream": meta_bw, "dispatch": meta_disp,
        },
    }


# ---------------------------------------------------------------- configs


def bench_smoke(ht, sync_floor, roofline=None):
    """Config 1: factory smoke — ht.arange on the mesh, ms per call."""
    n_iter = 20
    per, meta = _time_amortized(
        lambda: ht.arange(10, split=0),
        lambda a: float(a.sum()),
        n_iter,
        sync_floor,
    )
    return {
        "metric": "smoke_arange10_ms",
        "value": round(per * 1e3, 3),
        "unit": "ms",
        "vs_baseline": 1.0,
        "vs_baseline_kind": "self",
        "timing": meta,
    }


def bench_kmeans(ht, sync_floor, roofline=None):
    """Config 2: KMeans throughput, points/s through the Lloyd loop.

    Carries 5 windows of dispersion metadata (VERDICT r3 weak #1: the
    r2->r3 4.26->1.84 Gpts/s swing was undecidable): the Lloyd code was
    unchanged between those rounds (git diff 876c1a7..4d9a94a touches
    only a property refactor), and the r2 harness subtracted the host
    sync floor from a 2-fit window without requiring floor dominance —
    a systematic inflation.  From r4 on, the window list in ``timing``
    settles regression-vs-noise questions directly.

    Honest scale (ISSUE 16): on an accelerator the point set fills HBM —
    2^27 x 16 f32 = 8 GiB, the reference's config-2 regime (the former
    2^22 probe measured 1/250th of it) — while CPU smoke sessions keep
    the 2^22 size so the grid stays runnable; the metric name carries
    the size, so the two regimes never mix in one trend series."""
    big = jax.default_backend() == "tpu"
    log_n = 27 if big else 22
    n, f, k = 1 << log_n, 16, 8
    ht.random.seed(1)
    x = ht.random.randn(n, f, split=0)
    x = x.astype(ht.float32)
    float(x.sum())

    def make_fit(iters):
        def fit():
            km = ht.cluster.KMeans(
                n_clusters=k, init="random", max_iter=iters, tol=-1.0, random_state=0
            )
            km.fit(x)
            return km

        return fit

    # convergence loop (VERDICT r4 #3): the fit window must dwarf the
    # dispatch floor AND the window spread must settle under 10% before
    # the number is publishable — r4's 40.5% / 143% same-round spreads
    # could not detect a 2x regression.  Lloyd iterations per fit grow
    # until both hold (rate is iteration-normalized, so the metric is
    # unchanged by the workload growth).
    iters = 100
    while True:
        fit = make_fit(iters)
        fit()  # compile this iteration count
        per, meta = _time_amortized(
            fit, lambda km: float(km.cluster_centers_.sum()), 1, sync_floor, windows=5
        )
        if meta["spread_pct"] < 10.0 or iters >= 800:
            break
        iters *= 2
    pts_per_s = n * iters / per

    # independent second measurement, INTERLEAVED with the first: eight
    # windows alternate between sample A and sample B, so a monotone
    # drift of the host's sync floor degrades both samples equally and
    # the agreement flag tests PROGRAM reproducibility — two sequential
    # measurement blocks, the r5a formulation, disagreed 7% on a
    # 0.1%-spread metric purely because the floor shifted between the
    # blocks.
    n_it = meta["n_iter"]
    wins_a, wins_b = [], []
    attempts = 0
    while (len(wins_a) < 4 or len(wins_b) < 4) and attempts < 16:
        attempts += 1
        t0 = time.perf_counter()
        out = None
        for _ in range(n_it):
            out = fit()
        float(out.cluster_centers_.sum())
        elapsed = time.perf_counter() - t0
        # every KEPT window must satisfy the same acceptance rule
        # _time_amortized enforces: 50x floor dominance, or — when the
        # first block itself passed via the capped path (n_iter at the
        # 4096 cap under a high sync floor) — the capped >2x bound; a
        # degenerate near-floor window would otherwise publish a wildly
        # inflated min (the r2 DP-SGD failure class), while demanding
        # 50x from a session that can only deliver 2x would burn all 16
        # attempts and guarantee an underfull repeat
        floor_ratio = 2.0 if n_it >= 4096 else 50.0
        if elapsed - sync_floor < floor_ratio * sync_floor:
            continue  # underfull / hiccup window, skip (bounded retries)
        (wins_a if attempts % 2 == 1 else wins_b).append(
            (elapsed - sync_floor) / n_it
        )
    all_wins = wins_a + wins_b
    underfull = not wins_a or not wins_b
    meta2 = {
        "windows_a": len(wins_a),
        "windows_b": len(wins_b),
        "interleaved": True,
        "underfull": underfull,
        "per_iter_s_a": [round(s, 6) for s in wins_a],
        "per_iter_s_b": [round(s, 6) for s in wins_b],
    }
    if underfull:
        # no second sample exists — a reproducibility claim must not
        # ship on the back of a fallback value (the first block's
        # number stands, flagged unconfirmed)
        agreement = False
        v2 = float("nan")
    else:
        v1, v2 = n * iters / min(wins_a), n * iters / min(wins_b)
        spread_ab = 100.0 * (float(np.median(all_wins)) - min(all_wins)) / min(all_wins)
        meta2["spread_pct"] = round(spread_ab, 1)
        # the tolerance absorbs BOTH samples' own dispersion (the old
        # sequential formulation used both blocks' spreads too)
        tol = max(meta["spread_pct"], spread_ab, 5.0) / 100.0
        agreement = abs(v1 - v2) <= tol * max(v1, v2)
        # publish from the interleaved windows so the shipped value is
        # the quantity the agreement flag actually covers (the first
        # block's role is the workload-convergence loop; a floor drift
        # between it and the interleaved block must not ship an
        # unreproducible number)
        pts_per_s = n * iters / min(all_wins)

    # reference per-process path: torch CPU one Lloyd iteration (cdist+argmin
    # +scatter mean, cluster/kmeans.py torch kernels) on a subset
    import torch

    nb = 1 << 18
    xb = torch.randn(nb, f)
    cb = torch.randn(k, f)

    def lloyd_once():
        d = torch.cdist(xb, cb)
        lab = d.argmin(1)
        return torch.stack([xb[lab == i].mean(0) for i in range(k)])

    lloyd_once()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        c = lloyd_once()
        _ = c.sum().item()
        best = min(best, time.perf_counter() - t0)
    base_pts = nb / best
    rec = {
        "metric": f"kmeans_2^{log_n}x16_k8_pts_per_s",
        "value": round(pts_per_s / 1e9, 3),
        "unit": "Gpts/s",
        "vs_baseline": round(pts_per_s / base_pts, 2),
        "lloyd_iters_per_fit": iters,
        "repeat_value_gpts": None if underfull else round(v2 / 1e9, 3),
        "repeat_agreement": agreement,
        "timing": meta,
        "timing_repeat": meta2,
    }
    if roofline:
        # one Lloyd iteration reads the point set once (bandwidth bound:
        # n*f*4 bytes) and does ~2*n*k*f distance flops
        per_iter = per / iters
        rec["pct_of_bw_point_read_model"] = round(
            100.0 * (n * f * 4.0 / per_iter / 1e9) / roofline["hbm_stream_gbytes_per_s"], 1
        )
        rec["pct_of_peak_f32"] = round(
            100.0 * (2.0 * n * k * f / per_iter / 1e9) / roofline["peak_f32_matmul_gflops"], 1
        )
    return rec


def bench_hsvd(ht, sync_floor, roofline=None):
    """Config 3 (north star): hierarchical SVD GFLOP/s per chip.

    ``vs_baseline`` divides by a torch-CPU single-process subset (labeled
    below) — NOT the BASELINE.json "5x A100+MPI" target, for which no
    measurement exists in this repo; ``pct_of_peak_f32`` against the
    measured matmul roofline is the honest absolute yardstick
    (VERDICT r3 #9)."""
    n, f, rank = 1 << 22, 128, 10
    n_iter = 5
    ht.random.seed(0)
    x = ht.random.randn(n, f, split=0)
    float(x.sum())

    def factorize():
        u, s, v, err = ht.linalg.hsvd_rank(x, rank, compute_sv=True, safetyshift=5)
        return s

    float(factorize().sum())
    per, meta = _time_amortized(factorize, lambda s: float(s.sum()), n_iter, sync_floor)
    gflops = 2.0 * n * f * f / per / 1e9

    import torch

    n_b = 1 << 18
    xb = torch.randn(n_b, f)

    def tfact():
        u, s, v = torch.linalg.svd(xb, full_matrices=False)
        return u[:, :rank] * s[:rank]

    tfact()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        us = tfact()
        _ = us.sum().item()
        best = min(best, time.perf_counter() - t0)
    base = 2.0 * n_b * f * f / best / 1e9
    rec = {
        "metric": "hsvd_rank10_gflops_per_chip_2^22x128",
        "value": round(gflops, 1),
        "unit": "GFLOP/s",
        "vs_baseline": round(gflops / base, 2),
        "timing": meta,
    }

    # Multi-level merge tree (ISSUE 16): its first measured number.  The
    # split=0 probe above runs p=1 — one truncated-Gram leaf, merge tree
    # never touched.  split=1 spreads the columns over the mesh, so the
    # factorization runs ``comm.size`` leaf blocks plus ceil(log) merge
    # levels; the A/B toggles HEAT_TPU_HSVD_BATCHED, which stacks the
    # equal-shape blocks of each level through ONE batched
    # gram+eigh+project instead of a sequential per-block loop
    # (numerically identical per block — svdtools._truncated_us_stacked).
    import os

    nm = 1 << 20
    xm = ht.random.randn(nm, f, split=1)
    float(xm.sum())

    def fact_tree():
        ut, st, vt, errt = ht.linalg.hsvd_rank(xm, rank, compute_sv=True, safetyshift=5)
        return st

    tree = {"leaves": int(xm.comm.size)}
    for label, flag in (("sequential", "0"), ("batched", "1")):
        os.environ["HEAT_TPU_HSVD_BATCHED"] = flag
        try:
            float(fact_tree().sum())  # retrace under the knob
            per_t, meta_t = _time_amortized(
                fact_tree, lambda st: float(st.sum()), n_iter, sync_floor
            )
        finally:
            os.environ.pop("HEAT_TPU_HSVD_BATCHED", None)
        tree[label] = {
            "gflops": round(2.0 * nm * f * f / per_t / 1e9, 1),
            "timing": meta_t,
        }
    seq_g = tree["sequential"]["gflops"]
    tree["batched_speedup"] = (
        round(tree["batched"]["gflops"] / seq_g, 3) if seq_g else None
    )
    rec["merge_tree_2^20x128_split1"] = tree
    if roofline:
        rec["pct_of_peak_f32"] = round(100.0 * gflops / roofline["peak_f32_matmul_gflops"], 1)
        # hsvd forces HIGHEST for f32 accuracy: the like-for-like ceiling
        rec["pct_of_peak_f32_highest"] = round(
            100.0 * gflops / roofline["peak_f32_highest_matmul_gflops"], 1
        )
    return rec


def bench_dpsgd(ht, sync_floor, roofline=None):
    """Config 4: data-parallel CNN training steps/s (examples/nn analog)."""
    import optax
    import flax.linen as lnn

    class CNN(lnn.Module):
        @lnn.compact
        def __call__(self, x):
            x = lnn.relu(lnn.Conv(16, (3, 3))(x))
            x = lnn.avg_pool(x, (2, 2), strides=(2, 2))
            x = lnn.relu(lnn.Conv(32, (3, 3))(x))
            x = lnn.avg_pool(x, (2, 2), strides=(2, 2))
            x = x.reshape((x.shape[0], -1))
            return lnn.Dense(10)(lnn.relu(lnn.Dense(64)(x)))

    batch = 256
    n_stack = 16  # steps per device program (train_steps scan)
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(n_stack, batch, 28, 28, 1)), jnp.float32)
    ys = jnp.asarray(rng.integers(0, 10, size=(n_stack, batch)), jnp.int32)
    xb, yb = xs[0], ys[0]

    dp = ht.nn.DataParallel(CNN(), optimizer=optax.adam(1e-3))
    dp.init(jax.random.PRNGKey(0), xb)

    def loss_fn(pred, target):
        return optax.softmax_cross_entropy_with_integer_labels(pred, target).mean()

    # steady-state training stages a queue of batches in HBM and scans
    # them in ONE program (DataParallel.train_steps): per-step host
    # dispatch amortizes over the stack, so the metric measures the
    # device, not the host's launch latency
    dp.train_steps(loss_fn, xs, ys)  # compile + cache the scanned epoch
    xs, ys = dp._stage_stack(xs, ys)  # stage once; timed loop re-uses
    n_iter = 4

    def run_once():
        return dp.train_steps(loss_fn, xs, ys)

    per_stack, meta = _time_amortized(
        run_once, lambda l: float(l[-1]), n_iter, sync_floor
    )
    per = per_stack / n_stack
    steps_per_s = 1.0 / per
    try:  # XLA's own flop count for one scanned stack, if exposed
        cost = dp._epoch_fn.lower(
            dp.params, dp._opt_state, xs, ys
        ).compile().cost_analysis()
        step_flops = float(
            (cost[0] if isinstance(cost, (list, tuple)) else cost).get("flops", 0.0)
        ) / n_stack
    except Exception:
        step_flops = 0.0

    # reference per-process path: the same CNN step in torch on CPU
    import torch
    import torch.nn as tnn

    tmodel = tnn.Sequential(
        tnn.Conv2d(1, 16, 3, padding=1), tnn.ReLU(), tnn.AvgPool2d(2),
        tnn.Conv2d(16, 32, 3, padding=1), tnn.ReLU(), tnn.AvgPool2d(2),
        tnn.Flatten(), tnn.Linear(32 * 49, 64), tnn.ReLU(), tnn.Linear(64, 10),
    )
    topt = torch.optim.Adam(tmodel.parameters(), lr=1e-3)
    txb = torch.randn(batch, 1, 28, 28)
    tyb = torch.randint(0, 10, (batch,))

    def tstep():
        topt.zero_grad()
        loss = tnn.functional.cross_entropy(tmodel(txb), tyb)
        loss.backward()
        topt.step()
        return loss

    tstep()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _ = tstep().item()
        best = min(best, time.perf_counter() - t0)
    rec = {
        "metric": "dpsgd_cnn_batch256_steps_per_s",
        "value": round(steps_per_s, 2),
        "unit": "steps/s",
        "vs_baseline": round(steps_per_s * best, 2),
        "steps_per_dispatch": n_stack,
        "timing": meta,
    }
    if roofline:
        # the scanned stack amortizes dispatch n_stack ways, so the step
        # is device-bound and pct_of_peak_f32 is the regime anchor.
        # pct_of_dispatch_floor (floor / amortized step) records how far
        # the metric now sits ABOVE the one-dispatch-per-step ceiling —
        # values > 100 mean launch latency no longer bounds it (r4 weak #8).
        if roofline.get("dispatch_floor_ms"):
            rec["pct_of_dispatch_floor"] = round(
                100.0 * (roofline["dispatch_floor_ms"] / 1e3) / per, 1
            )
        if step_flops:
            rec["pct_of_peak_f32"] = round(
                100.0 * (step_flops / per / 1e9) / roofline["peak_f32_matmul_gflops"], 1
            )
    return rec


def _fft_scalar(r) -> float:
    """One scalar that depends on the transform, without materializing a
    host complex array: planar-backed results read their planes."""
    if r._planar is not None:
        re, im = r._planar
        return float(jnp.sqrt(re[(0,) * re.ndim] ** 2 + im[(0,) * im.ndim] ** 2))
    return float(jnp.abs(r.larray_padded[(0,) * r.ndim]))


def bench_fft3d(ht, sync_floor, roofline=None):
    """Config 5: 3-D FFT throughput, standard 5 N log2 N flop count.

    Times ``ht.fft.fftn`` on whichever engine the call takes: ``jnp.fft``
    on native complex by default, the planar (re, im) real-pair engine
    (heat_tpu/fft/_planar.py) under HEAT_TPU_PLANAR=1.  512^3 so device
    compute dominates the per-program dispatch floor; a Parseval
    check outside the timed region guards that the measured program is
    really the transform (the full spectrum is verified against
    np.fft.fftn at 128^3 in tests/test_io_random_fft.py)."""
    s = 512
    n = s**3
    ht.random.seed(2)
    x = ht.random.randn(s, s, s, split=0).astype(ht.float32)
    float(x.sum())

    def fft():
        return ht.fft.fftn(x)

    r = fft()
    on_chip = r._planar is not None or (
        next(iter(r.larray_padded.devices())).platform != "cpu"
    )
    # Parseval: sum|X|^2 == N * sum|x|^2 (on device, outside the timing)
    if r._planar is not None:
        re, im = r._planar
        spec_energy = float(jnp.sum(re * re + im * im))
    else:
        spec_energy = float(jnp.sum(jnp.abs(r.larray_padded) ** 2))
    sig_energy = float((x * x).sum())
    parseval = abs(spec_energy / (n * sig_energy) - 1.0)
    if parseval > 1e-2:
        raise MeasurementError(f"Parseval check failed: {parseval:.3e}")

    per, meta = _time_amortized(fft, _fft_scalar, 2, sync_floor)
    gflops = 5.0 * n * np.log2(n) / per / 1e9

    # Complex-input transform (ISSUE 16): fftn of the spectrum r — a full
    # complex 512^3 with nonzero planes — drives the pair-block leading
    # engine, which moves both planes through ONE relayout per stage
    # instead of two per-plane passes.  The acceptance yardstick is the
    # ratio to the real-input time above (was ~2.1x with the per-plane
    # stages; the pair-block path targets <= 1.3x).
    def fft_c():
        return ht.fft.fftn(r)

    float(_fft_scalar(fft_c()))
    per_c, meta_c = _time_amortized(fft_c, _fft_scalar, 2, sync_floor)
    complex_rec = {
        "gflops": round(5.0 * n * np.log2(n) / per_c / 1e9, 1),
        "ratio_vs_real": round(per_c / per, 3),
        "timing": meta_c,
    }

    import torch

    # GFLOP/s-normalized rates compare across sizes: the 128^3 subset
    # baseline avoids minutes of single-core 512^3 FFTs + ~2 GiB host RAM
    sb = 128
    xb = torch.randn(sb, sb, sb)
    torch.fft.fftn(xb)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        r2 = torch.fft.fftn(xb)
        _ = r2.real.sum().item()
        best = min(best, time.perf_counter() - t0)
    base = 5.0 * sb**3 * np.log2(sb**3) / best / 1e9
    rec = {
        "metric": "fft3d_512^3_gflops",
        "value": round(gflops, 1),
        "unit": "GFLOP/s",
        "vs_baseline": round(gflops / base, 2),
        "on_chip": on_chip,
        "parseval_err": round(parseval, 6),
        "timing": meta,
        "complex_input_512^3": complex_rec,
    }
    if roofline:
        # a 3-axis transform must touch both f32 planes at least once per
        # axis pass: >= 3 * (read+write) * (re+im) * 4 bytes = 48N bytes.
        # The achieved fraction of stream bandwidth under that minimal
        # model is the roofline tie (an FFT is bandwidth-, not flop-bound).
        # The MINIMAL model is the honest denominator: bandwidth on XLA's
        # scheduled bytes rewards wasteful schedules (VERDICT r4 weak #1),
        # so scheduled bytes are recorded as a diagnostic only.
        eff_bw = 48.0 * n / per / 1e9
        rec["eff_bw_gbytes_minimal_model"] = round(eff_bw, 1)
        rec["pct_of_bw_minimal_model"] = round(
            100.0 * eff_bw / roofline["hbm_stream_gbytes_per_s"], 1
        )
        try:
            from heat_tpu.fft.fft import _planar_prog

            prog = _planar_prog("fft", None, ((0, None), (1, None), (2, None)))
            re_in = x._dense()
            ca = prog.lower(re_in, None).compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            rec["bytes_scheduled_gb"] = round(float(ca.get("bytes accessed", 0.0)) / 1e9, 2)
        except Exception:
            pass
    return rec


def bench_dispatch(ht, sync_floor, roofline=None):
    """Config 6: dispatch-layer amortization smoke metrics (ISSUE 1).

    ``dispatch_cache_hit_rate`` — fraction of executable-cache lookups
    served without a retrace across two passes of a fixed mixed op
    sequence (the iterative-ML shape: identical shapes every pass;
    anything below ~0.5 here means repeated shapes are recompiling).
    ``dispatches_per_kmeans_iter`` — launches per Lloyd iteration for a
    20-iteration fit (the on-device while_loop should hold this far
    below 1.0).  ``fused_ops_per_dispatch`` — elementwise/reduce ops
    folded per launch for the fixed sequence; > 1 means chain fusion is
    collapsing op chains.  Emitted every round so BENCH_r{N}.json tracks
    dispatch amortization alongside throughput."""
    from heat_tpu.core import dispatch

    ht.random.seed(5)
    n = 1 << 16
    a = ht.random.randn(n, split=0).astype(ht.float32)
    b = ht.random.randn(n, split=0).astype(ht.float32)
    c = ht.random.randn(n, split=0).astype(ht.float32)

    def sequence():
        s1 = float(((a * b + c) / 2.0 - b).sum())
        s2 = float(ht.exp(a * 0.5).mean())
        return s1 + s2

    sequence()  # compile pass
    dispatch.reset_stats()
    sequence()  # measured pass: should be all hits
    seq = dispatch.cache_stats()

    # fused-chain latency through the warm cache (device-bound number)
    per, meta = _time_amortized(
        lambda: ((a * b + c) / 2.0 - b).sum(),
        lambda r: float(r),
        32,
        sync_floor,
    )

    x = ht.random.randn(1 << 12, 8, split=0).astype(ht.float32)
    km_iters = 20
    km = ht.cluster.KMeans(n_clusters=4, init="random", max_iter=km_iters,
                           tol=-1.0, random_state=0)
    km.fit(x)  # compile
    dispatch.reset_stats()
    km = ht.cluster.KMeans(n_clusters=4, init="random", max_iter=km_iters,
                           tol=-1.0, random_state=0)
    km.fit(x)
    ks = dispatch.cache_stats()
    km_dispatches = ks["dispatches"] + ks["external_dispatches"]

    return {
        "metric": "dispatch_cache_hit_rate",
        "value": round(seq["hit_rate"], 3),
        "unit": "fraction",
        "vs_baseline": 1.0,
        "vs_baseline_kind": "self",
        "dispatch_cache_hit_rate": round(seq["hit_rate"], 3),
        "dispatches_per_kmeans_iter": round(km_dispatches / km_iters, 3),
        "kmeans_fit_dispatches": km_dispatches,
        "fused_ops_per_dispatch": round(
            seq["fused_ops"] / seq["dispatches"], 2
        ) if seq["dispatches"] else 0.0,
        "donations": seq["donations"],
        "fused_chain_5op_ms": round(per * 1e3, 4),
        "timing": meta,
    }


def bench_resilience(ht, sync_floor, roofline=None):
    """Config 7: resilience-layer counters + checkpoint overhead (ISSUE 2).

    ``checkpoint_save_ms``/``checkpoint_restore_ms`` — wall time of one
    filesystem-native Checkpointer save/restore of a representative
    (1k x 256 f32 centers + scalars) fit state, the per-chunk overhead a
    ``checkpoint_every=N`` fit pays; the perf gate watches these so a
    checkpoint-layer regression (lost atomicity batching, sidecar
    recomputation) is caught.  ``retries``/``faults_injected``/
    ``faults_survived`` — counters from a scripted transient-fault save
    (fault plan: one transient on ``io.write``), proving the retry path
    is live in the shipped wheel, not just under pytest.  The headline
    value is checkpoint_save_ms."""
    import os
    import shutil
    import tempfile

    from heat_tpu import resilience as rz
    from heat_tpu.utils.checkpoint import Checkpointer

    rz.reset_retry_stats()
    rz.reset_fault_stats()
    state = {
        "state": np.random.default_rng(0).standard_normal((1024, 256)).astype(np.float32),
        "n_iter": 17,
        "shift": 1e-3,
        "converged": False,
    }
    d = tempfile.mkdtemp(prefix="heat_tpu_bench_ck_")
    try:
        ck = Checkpointer(d)
        save_s = float("inf")
        for i in range(5):
            t0 = time.perf_counter()
            ck.save(i, state)
            save_s = min(save_s, time.perf_counter() - t0)
        restore_s = float("inf")
        for i in range(5):
            t0 = time.perf_counter()
            out = ck.restore(i)
            restore_s = min(restore_s, time.perf_counter() - t0)
        assert out["n_iter"] == 17

        # scripted transient save fault: one retry must absorb it
        os.environ["HEAT_TPU_RETRY_NO_SLEEP"] = "1"
        try:
            with rz.fault_plan({"io.write": [0]}):
                ht.save(
                    ht.arange(1024, dtype=ht.float32),
                    os.path.join(d, "fault_probe.npy"),
                )
        finally:
            os.environ.pop("HEAT_TPU_RETRY_NO_SLEEP", None)
        counters = rz.resilience_stats()

        # elastic worker-loss recovery (ISSUE 8): one subprocess fit
        # killed mid-fit by the fault plan, reshaped one device smaller,
        # resumed from the surviving checkpoint; the recorded latency is
        # loss detection -> resumed worker's first heartbeat (the same
        # quantity scripts/perf_ci.py gates with max_seconds)
        elastic_recovery_s = None
        elastic_world = None
        try:
            import json as _json
            import sys as _sys

            from heat_tpu.elastic.process import (
                ProcessSupervisor,
                kmeans_worker_source,
            )

            eck = os.path.join(d, "elastic")
            kill_plan = _json.dumps(
                {"plan": {"kmeans.iter": [{"at": 1, "kind": "kill", "exit_code": 137}]}}
            )

            def _ebuild(ws, resume, attempt):
                src = kmeans_worker_source(eck, resume_from=resume, x64=False)
                return (
                    [_sys.executable, "-c", src],
                    {"HEAT_TPU_FAULT_PLAN": kill_plan if attempt == 0 else ""},
                )

            eout = ProcessSupervisor(
                _ebuild, eck, world_size=4, shrink_by=1, max_recoveries=2,
                poll_s=0.2, attempt_timeout_s=280,
            ).run()
            elastic_recovery_s = round(eout["recovery_s"][0], 2)
            elastic_world = f"{4}->{eout['world_size']}"
        except Exception as e:  # lint: allow H501(optional bench section records its error)
            elastic_recovery_s = f"error: {type(e).__name__}: {e}"[:120]
    finally:
        shutil.rmtree(d, ignore_errors=True)

    return {
        "metric": "resilience_checkpoint_save_ms",
        "value": round(save_s * 1e3, 3),
        "unit": "ms",
        "vs_baseline": 1.0,
        "vs_baseline_kind": "self",
        "checkpoint_save_ms": round(save_s * 1e3, 3),
        "checkpoint_restore_ms": round(restore_s * 1e3, 3),
        "checkpoint_state_mb": round(state["state"].nbytes / 2**20, 1),
        "retries": counters["retries"],
        "faults_injected": counters["faults_injected"],
        "faults_survived": counters["faults_survived"],
        "retry_gave_up": counters["gave_up"],
        "elastic_recovery_s": elastic_recovery_s,
        "elastic_world": elastic_world,
    }


def bench_overlap(ht, sync_floor, roofline=None):
    """Config 8: overlap-layer metrics (ISSUE 3).

    ``ckpt_stall_ms`` — wall time the caller spends inside an async
    ``AsyncCheckpointer.save`` (snapshot + enqueue) for the
    representative 1024x256 f32 fit state, i.e. the per-chunk stall a
    ``checkpoint_every=N`` fit now pays, vs ``checkpoint_save_ms`` — the
    full synchronous write the fit used to pay; ``stall_vs_sync`` is
    their ratio (the acceptance gate wants < 0.3).  ``prefetch_hit_rate``
    — fraction of batches staged on device ahead of the consumer by
    ``prefetch_to_device`` over a synthetic windowed stream.
    ``grad_buckets`` — collective buckets a bucketed-schedule
    DataParallel step issues for a small MLP.  The headline value is the
    async stall."""
    import os
    import shutil
    import tempfile

    import optax

    from heat_tpu.utils import overlap as ov
    from heat_tpu.utils.checkpoint import Checkpointer
    from heat_tpu.utils.data import prefetch_to_device

    ov.reset_overlap_stats()
    state = {
        "state": np.random.default_rng(0).standard_normal((1024, 256)).astype(np.float32),
        "n_iter": 17,
        "shift": 1e-3,
        "converged": False,
    }
    d = tempfile.mkdtemp(prefix="heat_tpu_bench_ov_")
    try:
        ck = Checkpointer(os.path.join(d, "sync"))
        sync_s = float("inf")
        for i in range(5):
            t0 = time.perf_counter()
            ck.save(i, state)
            sync_s = min(sync_s, time.perf_counter() - t0)

        ack = Checkpointer(os.path.join(d, "async")).as_async()
        stall_s = float("inf")
        for i in range(5):
            t0 = time.perf_counter()
            ack.save(i, state)  # snapshot + enqueue: the loop-visible cost
            stall_s = min(stall_s, time.perf_counter() - t0)
            ack.wait()  # drain outside the stall window (the fit's chunk
            # compute covers this in production)
        ack.close()

        # prefetch hit rate over a synthetic windowed stream with a
        # small device op standing in for the consuming train step
        windows = (np.full((256, 8), i, np.float32) for i in range(32))
        consume = jax.jit(lambda b: b.sum())
        for b in prefetch_to_device(windows, size=2):
            consume(b)
        stats = ov.overlap_stats()

        # bucketed-schedule DataParallel step on a small MLP
        rng = np.random.default_rng(1)
        params = {
            "w1": jnp.asarray(rng.normal(size=(64, 128)) * 0.1, jnp.float32),
            "b1": jnp.zeros((128,), jnp.float32),
            "w2": jnp.asarray(rng.normal(size=(128, 8)) * 0.1, jnp.float32),
            "b2": jnp.zeros((8,), jnp.float32),
        }
        apply = lambda p, xb: jnp.tanh(xb @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
        loss_fn = lambda pred, tgt: jnp.mean((pred - tgt) ** 2)
        xb = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
        yb = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
        os.environ["HEAT_TPU_GRAD_BUCKET_MB"] = "0.01"  # visible bucketing at toy scale
        try:
            dp = ht.nn.DataParallel(
                apply, optimizer=ht.optim.DataParallelOptimizer(optax.sgd(0.1))
            )
            dp.set_params(params)
            dp.step(loss_fn, xb, yb)
        finally:
            os.environ.pop("HEAT_TPU_GRAD_BUCKET_MB", None)
        grad_buckets = ov.overlap_stats()["grad_buckets"]
    finally:
        shutil.rmtree(d, ignore_errors=True)

    total = stats["prefetch_hits"] + stats["prefetch_misses"]
    return {
        "metric": "overlap_ckpt_stall_ms",
        "value": round(stall_s * 1e3, 3),
        "unit": "ms",
        "vs_baseline": round(sync_s / stall_s, 2) if stall_s else 0.0,
        "vs_baseline_kind": "sync_checkpoint_save_same_process",
        "ckpt_stall_ms": round(stall_s * 1e3, 3),
        "checkpoint_save_ms": round(sync_s * 1e3, 3),
        "stall_vs_sync": round(stall_s / sync_s, 3) if sync_s else 0.0,
        "async_saves": stats["async_saves"],
        "prefetch_hits": stats["prefetch_hits"],
        "prefetch_misses": stats["prefetch_misses"],
        "prefetch_hit_rate": round(stats["prefetch_hits"] / total, 3) if total else 0.0,
        "grad_buckets": grad_buckets,
    }


def bench_serving(ht, sync_floor, roofline=None):
    """Config 11: sustained-load serving (ISSUE 9).

    A fitted KMeans is saved, hot-loaded into an
    :class:`~heat_tpu.serving.InferenceService`, and hammered by client
    threads issuing requests of varied sizes while one over-quota tenant
    sheds against its token bucket.  Reported: admitted request rate and
    its p50/p99 latency, the coalesced batch-size distribution, the
    shed rate, and — the cache acceptance property — new executable
    compiles during steady state (must be 0: pad-to-bucket keeps the
    key set finite).  ``vs_baseline`` divides the served rate by the
    same request stream predicted *directly* (per-request shapes, no
    coalescing) — the naive serving loop the coalescer replaces."""
    import shutil
    import tempfile
    import threading

    from heat_tpu import serving as srv
    from heat_tpu.core import dispatch
    from heat_tpu.resilience import OverloadedError
    from heat_tpu.serving import model_io

    rng = np.random.default_rng(0)
    pts = rng.standard_normal((1 << 12, 16)).astype(np.float32)
    x = ht.array(pts, split=0)
    km = ht.cluster.KMeans(n_clusters=8, init="random", max_iter=5, random_state=0).fit(x)

    sizes = [1, 3, 7, 12, 18, 27, 33, 50, 64]
    n_requests = 400
    d = tempfile.mkdtemp(prefix="heat_tpu_bench_srv_")
    try:
        srv.save_model(km, d, version=1, name="km")
        svc = srv.InferenceService(max_delay_ms=1.0, max_batch=64)
        svc.load("km", d)
        for b in (1, 2, 4, 8, 16, 32, 64):  # warm every bucket
            svc.predict("km", pts[:b])

        # baseline: the same request stream, predicted directly one
        # request at a time (per-request shapes -> per-shape compiles)
        t0 = time.perf_counter()
        for i in range(n_requests // 4):
            n = sizes[i % len(sizes)]
            model_io.infer(km, ht.array(pts[i % 64 : i % 64 + n], split=None)).numpy()
        direct_rate = (n_requests // 4) / (time.perf_counter() - t0)

        # sustained load: 4 client threads, varied sizes; one noisy
        # tenant hammers an over-quota bucket concurrently
        svc.set_quota("noisy", rate=2.0, burst=4.0)
        stop = threading.Event()
        noisy_counts = {"ok": 0, "shed": 0}

        def noisy():
            while not stop.is_set():
                try:
                    svc.predict("km", pts[:2], tenant="noisy", timeout=30)
                    noisy_counts["ok"] += 1
                except OverloadedError:
                    noisy_counts["shed"] += 1
                time.sleep(0.002)

        nt = threading.Thread(target=noisy, name="bench-noisy-tenant", daemon=True)
        s0 = dispatch.cache_stats()
        lat_lock = threading.Lock()
        latencies = []

        def client(worker):
            for i in range(n_requests // 4):
                n = sizes[(worker + i) % len(sizes)]
                off = (worker * 61 + i * 7) % 64
                t1 = time.perf_counter()
                svc.predict("km", pts[off : off + n], timeout=30)
                dt = time.perf_counter() - t1
                with lat_lock:
                    latencies.append(dt)

        nt.start()
        t0 = time.perf_counter()
        clients = [
            threading.Thread(target=client, args=(w,), name=f"bench-client-{w}", daemon=True)
            for w in range(4)
        ]
        for t in clients:
            t.start()
        for t in clients:
            t.join()
        wall = time.perf_counter() - t0
        stop.set()
        nt.join()
        s1 = dispatch.cache_stats()
        svc.close()

        lat = np.sort(np.asarray(latencies))
        batch_rows = ht.telemetry.metrics.histogram("serving.batch_rows")
        shed_total = noisy_counts["shed"]
        served_rate = len(latencies) / wall
        new_compiles = s1["misses"] - s0["misses"]
        steady_lookups = (s1["hits"] - s0["hits"]) + new_compiles
        return {
            "metric": "serving_req_per_s",
            "value": round(served_rate, 1),
            "unit": "req/s",
            "vs_baseline": round(served_rate / direct_rate, 2) if direct_rate else 0.0,
            "vs_baseline_kind": "uncoalesced_direct_predict_same_process",
            "p50_ms": round(float(lat[len(lat) // 2]) * 1e3, 3),
            "p99_ms": round(float(lat[int(len(lat) * 0.99)]) * 1e3, 3),
            "requests": len(latencies),
            "steady_state_new_compiles": new_compiles,
            "steady_state_hit_rate": round(
                (s1["hits"] - s0["hits"]) / steady_lookups, 4
            ) if steady_lookups else 1.0,
            "coalesced_batch_rows": {
                "count": batch_rows.count,
                "p50": batch_rows.quantile(0.5),
                "p99": batch_rows.quantile(0.99),
                "max": batch_rows.max,
            },
            "noisy_tenant_shed": shed_total,
            "noisy_tenant_admitted": noisy_counts["ok"],
            "shed_rate": round(
                shed_total / (shed_total + noisy_counts["ok"]), 3
            ) if (shed_total + noisy_counts["ok"]) else 0.0,
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_canary(ht, sync_floor, roofline=None):
    """Config 11b: the canary decision plane under a sustained stream
    (ISSUE 15).

    An identical canary (v2 == v1) is hot-loaded ``activate=False`` with
    ``HEAT_TPU_SHADOW_FRACTION`` at 1.0 while client requests stream at
    varied sizes.  Reported: the **time-to-verdict** — how long the
    decision engine takes to accumulate ``HEAT_TPU_CANARY_MIN_ROWS``
    shadow rows and auto-promote under this stream (the operational
    question: "how long does a canary bake?"), the shadow lane's
    batch/drop counters, the canary-vs-primary latency ratio measured on
    the same mirrored batches, and the steady-state compile count (must
    be 0: the shadow path rides the primary's bucket keys)."""
    import shutil
    import tempfile

    from heat_tpu import serving as srv
    from heat_tpu.core import dispatch
    from heat_tpu.serving import canary as cnry
    from heat_tpu.telemetry import metrics as tmet

    rng = np.random.default_rng(3)
    pts = rng.standard_normal((1 << 12, 16)).astype(np.float32)
    x = ht.array(pts, split=0)
    km = ht.cluster.KMeans(n_clusters=8, init="random", max_iter=5, random_state=0).fit(x)

    sizes = [1, 3, 7, 12, 18, 27, 33, 50, 64]
    d = tempfile.mkdtemp(prefix="heat_tpu_bench_canary_")
    try:
        srv.save_model(km, d, version=1, name="km")
        srv.save_model(km, d, version=2, name="km")
        svc = srv.InferenceService(max_delay_ms=1.0, max_batch=64)
        svc.load("km", d, version=1)
        for b in (1, 2, 4, 8, 16, 32, 64):  # warm every bucket
            svc.predict("km", pts[:b])

        s0 = dispatch.cache_stats()
        c0 = {
            k: tmet.counter(f"canary.{k}").value
            for k in ("sampled", "sampled_rows", "dropped", "comparisons")
        }
        svc.load("km", d, version=2, activate=False)  # the canary
        svc.canary.fraction = 1.0
        svc.canary.min_rows = 256
        t0 = time.perf_counter()
        deadline = t0 + 60.0
        i = 0
        while time.perf_counter() < deadline:
            n = sizes[i % len(sizes)]
            svc.predict("km", pts[(i * 7) % 64 : (i * 7) % 64 + n])
            i += 1
            st = cnry.status("km")
            if st is not None and st["decision"] is not None:
                break
        decision_s = time.perf_counter() - t0
        svc.canary.wait_idle(30)
        st = cnry.status("km") or {}
        s1 = dispatch.cache_stats()
        c1 = {
            k: tmet.counter(f"canary.{k}").value
            for k in ("sampled", "sampled_rows", "dropped", "comparisons")
        }
        dec = st.get("decision") or {}
        svc.close()
        return {
            "metric": "canary_decision_s",
            "value": round(decision_s, 3),
            "unit": "s",
            "vs_baseline": 0.0,
            "vs_baseline_kind": "time_to_verdict_at_min_rows_256",
            "verdict": dec.get("verdict"),
            "action": dec.get("action"),
            "requests_to_verdict": i,
            "shadow_batches": c1["sampled"] - c0["sampled"],
            "shadow_rows": c1["sampled_rows"] - c0["sampled_rows"],
            "shadow_dropped": c1["dropped"] - c0["dropped"],
            "comparisons": c1["comparisons"] - c0["comparisons"],
            "mismatch_pct": st.get("mismatch_pct"),
            "canary_latency_ratio": st.get("latency_ratio"),
            "steady_state_new_compiles": s1["misses"] - s0["misses"],
        }
    finally:
        cnry.reset_canary_state()
        shutil.rmtree(d, ignore_errors=True)


def fleet_scenario(
    scale_window_s=4.0,
    clients=12,
    kill_window_s=3.0,
    kill_clients=4,
    queue_depth=3,
    delay_ms=60.0,
    steady_requests=40,
):
    """The fleet-serving measurement harness (shared by ``bench_fleet``
    and ``scripts/perf_ci.py``): real replica subprocesses behind a real
    :class:`~heat_tpu.fleet.FleetRouter`, four phases.

    * **scale-out** — closed-loop clients drive single-row predicts
      through the router at 1 then 4 replicas.  Each replica's capacity
      is its bounded admission queue over the coalescing residency
      (Little's law), so the aggregate rate measures the ROUTER's work —
      bounded-load spillover past the hash-favorite plus queue-shed
      failover — not the host's core count: a router that stops
      spreading pins the ratio to ~1x whatever the hardware.
    * **cold start** — a fresh replica boots from the AOT executable
      cache + pre-warm manifest the first replica populated; measured:
      artifact hits at ready, the FIRST request's latency vs the
      replica's own steady p99, and compiles after ready (must be 0 —
      executable-cache hit rate 1.0 from request one).
    * **replica kill** — SIGKILL the rendezvous-favorite replica under
      live load; every client request must still answer 200/429 (the
      router's bounded-retry failover absorbs the loss) — failed
      requests are the gated count, cap 0.
    * **drain** — SIGTERM one replica; it must finish in-flight work
      and exit 0.

    Returns the raw numbers dict; callers shape it into records/gates.
    """
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    import heat_tpu as ht
    from heat_tpu import serving as srv
    from heat_tpu.fleet import FleetRouter, LocalReplicaSet

    base = tempfile.mkdtemp(prefix="heat_tpu_bench_fleet_")
    rng = np.random.default_rng(0)
    pts = rng.standard_normal((256, 16)).astype(np.float32)
    km = ht.cluster.KMeans(
        n_clusters=8, init="random", max_iter=5, random_state=0
    ).fit(ht.array(pts, split=0))
    mdir = f"{base}/km"
    srv.save_model(km, mdir, version=1, name="km")
    manifest = f"{base}/prewarm.json"
    with open(manifest, "w") as f:
        _json.dump({"version": 1, "entries": [
            {"model": "km", "bucket": b, "features": 16, "dtype": "float32"}
            for b in (1, 2, 4, 8, 16)
        ]}, f)
    body = _json.dumps({"model": "km", "inputs": pts[:1].tolist()}).encode()

    rs = LocalReplicaSet(
        {"km": mdir}, base, aot_cache=f"{base}/aot", prewarm=manifest,
        max_batch=64, max_delay_ms=delay_ms, queue_depth=queue_depth,
    )
    router = FleetRouter(health_period_s=0.25, load_factor=1.2)

    def drive(window_s, n_clients):
        stop = threading.Event()
        lock = threading.Lock()
        counts = {"ok": 0, "shed": 0, "failed": 0}

        def client():
            while not stop.is_set():
                status, _out, _ct, headers = router.handle(
                    "POST", "/v1/predict", body
                )
                with lock:
                    if status == 200:
                        counts["ok"] += 1
                    elif status == 429:
                        counts["shed"] += 1
                    else:
                        counts["failed"] += 1
                if status == 429:
                    ra = float(headers.get("Retry-After", 0.02) or 0.02)
                    time.sleep(min(ra, 0.2))

        threads = [
            threading.Thread(target=client, daemon=True) for _ in range(n_clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(window_s)
        stop.set()
        for t in threads:
            t.join(timeout=15)
        return counts, counts["ok"] / (time.perf_counter() - t0)

    def direct(url, n):
        lats = []
        for _ in range(n):
            t0 = time.perf_counter()
            req = urllib.request.Request(
                url + "/v1/predict", data=body, method="POST",
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=15) as resp:
                resp.read()
            lats.append((time.perf_counter() - t0) * 1e3)
        return np.sort(np.asarray(lats))

    out = {}
    try:
        # phase 1: first replica (compiles + populates the AOT cache)
        t0 = time.monotonic()
        u1 = rs.spawn()
        out["spawn_first_s"] = round(time.monotonic() - t0, 2)
        router.add_replica(u1)
        router.poll_health()
        counts1, rate1 = drive(scale_window_s, clients)
        out["rate_1_replica"] = round(rate1, 1)
        out["shed_1_replica"] = counts1["shed"]
        out["failed_1_replica"] = counts1["failed"]

        # phase 2: cold start from the populated AOT cache
        t0 = time.monotonic()
        u2 = rs.spawn()
        out["spawn_cold_s"] = round(time.monotonic() - t0, 2)
        ready_doc = _json.load(urllib.request.urlopen(u2 + "/readyz", timeout=10))
        out["cold_aot_hits"] = ready_doc["aot"]["hits"]
        misses_ready = ready_doc["dispatch"]["misses"]
        t0 = time.perf_counter()
        req = urllib.request.Request(
            u2 + "/v1/predict", data=body, method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=15) as resp:
            resp.read()
        out["cold_first_request_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        steady = direct(u2, steady_requests)
        out["steady_p50_ms"] = round(float(steady[len(steady) // 2]), 2)
        out["steady_p99_ms"] = round(float(steady[int(len(steady) * 0.99)]), 2)
        out["cold_vs_steady_p99"] = round(
            out["cold_first_request_ms"] / out["steady_p99_ms"], 3
        )
        after = _json.load(urllib.request.urlopen(u2 + "/readyz", timeout=10))
        out["cold_compiles_after_ready"] = after["dispatch"]["misses"] - misses_ready

        # phase 3: scale out to 4 replicas, same offered load
        router.add_replica(u2)
        u3, u4 = rs.spawn(), rs.spawn()
        router.add_replica(u3)
        router.add_replica(u4)
        router.poll_health()
        counts4, rate4 = drive(scale_window_s, clients)
        out["rate_4_replicas"] = round(rate4, 1)
        out["shed_4_replicas"] = counts4["shed"]
        out["failed_4_replicas"] = counts4["failed"]
        out["scaleout_ratio"] = round(rate4 / rate1, 2) if rate1 else 0.0

        # phase 4: SIGKILL the hash-favorite under live load
        victim = router.preferred("km") or u1
        stop = threading.Event()
        lock = threading.Lock()
        kill_counts = {"ok": 0, "shed": 0, "failed": 0}

        def kill_client():
            while not stop.is_set():
                status, _o, _c, _h = router.handle("POST", "/v1/predict", body)
                with lock:
                    if status == 200:
                        kill_counts["ok"] += 1
                    elif status == 429:
                        kill_counts["shed"] += 1
                    else:
                        kill_counts["failed"] += 1

        threads = [
            threading.Thread(target=kill_client, daemon=True)
            for _ in range(kill_clients)
        ]
        failovers_before = router.statusz()["failovers"]
        for t in threads:
            t.start()
        time.sleep(kill_window_s / 3.0)
        rs.kill(victim)
        time.sleep(2.0 * kill_window_s / 3.0)
        stop.set()
        for t in threads:
            t.join(timeout=15)
        out["kill_requests_ok"] = kill_counts["ok"]
        out["kill_requests_shed"] = kill_counts["shed"]
        out["kill_failed_requests"] = kill_counts["failed"]
        out["kill_failovers"] = router.statusz()["failovers"] - failovers_before

        # phase 5: graceful drain must exit 0
        survivor = next(u for u in rs.urls())
        router.drain_replica(survivor)
        out["drain_rc"] = rs.drain_stop(survivor)
        return out
    finally:
        router.close()
        rs.close()
        shutil.rmtree(base, ignore_errors=True)


def bench_fleet(ht, sync_floor, roofline=None):
    """Config 12: fleet-scale serving (ISSUE 13).

    Real replica subprocesses behind the fleet router: req/s at 1 -> 4
    replicas (with the scale-out ratio the perf gate enforces at >= 3x),
    the AOT-cache cold start (fresh replica's first request vs its
    steady p99, compiles after ready), the replica-kill-under-live-load
    scenario (failed client requests, gated at 0), and the graceful
    drain exit code.  See :func:`fleet_scenario` for methodology."""
    raw = fleet_scenario()
    return {
        "metric": "fleet_req_per_s_4x",
        "value": raw["rate_4_replicas"],
        "unit": "req/s",
        "vs_baseline": raw["scaleout_ratio"],
        "vs_baseline_kind": "same_router_single_replica",
        **raw,
    }


def bench_telemetry(ht, sync_floor, roofline=None):
    """Config 9: telemetry-layer self-cost (ISSUE 4 + ISSUE 6).

    ``span_ns_enabled``/``span_ns_disabled`` — per-span wall cost of the
    host-side tracer with recording on vs off (disabled must be ~two
    attribute reads; enabled buys a ring append + TraceAnnotation).
    ``snapshot_us`` — cost of one full-registry ``telemetry.snapshot()``
    with every domain registered, the price a heartbeat scraper pays.
    Introspection-layer additions (ISSUE 6): ``scrape_metrics_us`` /
    ``scrape_varz_us`` — one full HTTP GET against the live endpoint on
    an ephemeral port (socket + handler + serialization, the cost ONE
    Prometheus scrape imposes on the process); ``recorder_overhead_ns``
    — per-span cost with the crash flight recorder ARMED vs not (the
    recorder is a passive excepthook, so this must be ~1.0x);
    ``cost_accounting_miss_us`` — per-miss dispatch cost with
    ``HEAT_TPU_COST_ANALYSIS`` on vs off, plus the recorded flops.
    The headline value is the enabled span cost — the number that bounds
    how densely the stack can afford to be instrumented."""
    import shutil
    import tempfile
    import urllib.request

    from heat_tpu import telemetry
    from heat_tpu.core import dispatch
    from heat_tpu.telemetry import flight_recorder
    from heat_tpu.telemetry import server as tserver

    def span_ns(n: int = 50_000) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with telemetry.span("bench.telemetry.probe"):
                pass
        return (time.perf_counter() - t0) / n * 1e9

    prev = telemetry.set_tracing(True)
    try:
        span_ns(2_000)  # warm
        enabled_ns = min(span_ns() for _ in range(3))
        telemetry.set_tracing(False)
        disabled_ns = min(span_ns() for _ in range(3))
        # flight recorder armed vs not: the recorder is an excepthook +
        # bundle dir, so the steady-state delta must be noise (~1.0x)
        telemetry.set_tracing(True)
        d = tempfile.mkdtemp(prefix="heat_tpu_bench_fr_")
        try:
            flight_recorder.install(d)
            recorder_ns = min(span_ns() for _ in range(3))
        finally:
            flight_recorder.uninstall()
            shutil.rmtree(d, ignore_errors=True)
    finally:
        telemetry.set_tracing(prev)
        telemetry.clear_spans()

    n_snap = 500
    telemetry.snapshot()  # warm
    t0 = time.perf_counter()
    for _ in range(n_snap):
        telemetry.snapshot()
    snapshot_us = (time.perf_counter() - t0) / n_snap * 1e6

    # live-endpoint scrape cost: ephemeral port, same-process HTTP GET
    srv = tserver.start_server(0)
    try:
        def scrape_us(route: str, n: int = 50) -> float:
            urllib.request.urlopen(f"{srv.url}{route}", timeout=10).read()  # warm
            t0 = time.perf_counter()
            for _ in range(n):
                urllib.request.urlopen(f"{srv.url}{route}", timeout=10).read()
            return (time.perf_counter() - t0) / n * 1e6

        scrape_metrics_us = min(scrape_us("/metrics") for _ in range(3))
        scrape_varz_us = min(scrape_us("/varz") for _ in range(3))
    finally:
        tserver.stop_server()

    # per-executable cost accounting: dispatch-miss cost with the
    # analysis on vs off, and the flops it records
    import jax.numpy as jnp

    buf = jnp.ones((256,), jnp.float32)

    def miss_us(n: int = 32) -> float:
        dispatch.clear_cache()
        ops = [(lambda v: (lambda a, b: a + b * v))(i) for i in range(n)]
        t0 = time.perf_counter()
        for op in ops:
            dispatch.eager_apply(op, (buf, buf))
        return (time.perf_counter() - t0) / n * 1e6

    prev_cost = dispatch.set_cost_accounting(False)
    try:
        cost_off_us = min(miss_us() for _ in range(2))
        dispatch.set_cost_accounting(True)
        cost_on_us = min(miss_us() for _ in range(2))
        cost = dispatch.cost_summary()
        flops_recorded = cost["flops_total"]
    finally:
        dispatch.set_cost_accounting(prev_cost)
        dispatch.clear_cache()

    return {
        "metric": "telemetry_span_ns",
        "value": round(enabled_ns, 1),
        "unit": "ns",
        "vs_baseline": round(disabled_ns / enabled_ns, 4) if enabled_ns else 0.0,
        "vs_baseline_kind": "tracing_disabled_same_process",
        "span_ns_enabled": round(enabled_ns, 1),
        "span_ns_disabled": round(disabled_ns, 1),
        "snapshot_us": round(snapshot_us, 2),
        "metrics_registered": len(telemetry.REGISTRY.names()),
        "scrape_metrics_us": round(scrape_metrics_us, 1),
        "scrape_varz_us": round(scrape_varz_us, 1),
        "recorder_overhead_x": round(recorder_ns / enabled_ns, 3) if enabled_ns else 0.0,
        "cost_accounting_miss_us": round(cost_on_us, 2),
        "cost_accounting_off_miss_us": round(cost_off_us, 2),
        "cost_accounting_flops_recorded": flops_recorded,
    }


def bench_analysis(ht, sync_floor, roofline=None):
    """Config 10: SPMD program-analyzer self-cost (ISSUE 5).

    ``analyze_off_miss_us``/``analyze_off_hit_ns`` — per-dispatch cost of
    the compile-path hook with ``HEAT_TPU_ANALYZE=0`` (the default): the
    off-mode hook is one lazy-import lookup + a string compare per cache
    MISS and provably nothing per hit (the ``if fresh`` guard), so both
    numbers track the plain dispatch floor.
    ``analyze_on_miss_ms`` — full analyzer cost per fresh compile in warn
    mode (re-lower + re-compile + HLO walk), the price a CI job pays to
    see J101-J105 diagnostics.  Headline value is the off-mode hit cost —
    the number that bounds what production dispatch pays for having the
    analyzer wired in at all."""
    import jax.numpy as jnp
    import numpy as np

    from heat_tpu import analysis
    from heat_tpu.analysis import diagnostics
    from heat_tpu.core import dispatch

    buf = jnp.ones((256,), jnp.float32)

    def miss_us(n=64):
        """Mean per-call cost of n distinct-key misses (fresh scalars)."""
        dispatch.clear_cache()
        ops = [(lambda v: (lambda a, b: a + b * v))(i) for i in range(n)]
        t0 = time.perf_counter()
        for op in ops:
            dispatch.eager_apply(op, (buf, buf))
        return (time.perf_counter() - t0) / n * 1e6

    def hit_ns(n=20_000):
        dispatch.eager_apply(jnp.add, (buf, buf))  # warm
        t0 = time.perf_counter()
        for _ in range(n):
            dispatch.eager_apply(jnp.add, (buf, buf))
        return (time.perf_counter() - t0) / n * 1e9

    prev = diagnostics.set_analysis_mode("0")
    try:
        off_miss = min(miss_us() for _ in range(3))
        off_hit = min(hit_ns() for _ in range(3))
        diagnostics.set_analysis_mode("warn")
        analysis.clear_diagnostics()
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            on_miss = min(miss_us() for _ in range(2))
        diags = len(analysis.recent_diagnostics())
    finally:
        diagnostics.set_analysis_mode(prev)
        analysis.clear_diagnostics()
        dispatch.clear_cache()

    return {
        "metric": "analysis_off_hit_ns",
        "value": round(off_hit, 1),
        "unit": "ns",
        "vs_baseline": round(on_miss / off_miss, 2) if off_miss else 0.0,
        "vs_baseline_kind": "warn_mode_miss_vs_off_mode_miss",
        "analyze_off_hit_ns": round(off_hit, 1),
        "analyze_off_miss_us": round(off_miss, 2),
        "analyze_on_miss_ms": round(on_miss / 1e3, 3),
        "warn_mode_diags": diags,
        "analyzer_mode_default": diagnostics.analysis_mode(),
    }


def bench_streaming(ht, sync_floor, roofline=None):
    """Config 12b: streaming continuous learning (ISSUE 17).

    Two operational numbers.  **Sustained ingest** — a producer thread
    appends to a durable :class:`FileSegmentLog` while a streaming
    KMeans consumes full windows through the prefetched consumer with
    exactly-once offset commits riding every 8th window; reported MB/s
    is bytes folded into the model over the whole concurrent run
    (append + atomic segment commits + checksum-verified reads + device
    staging + minibatch update + offset checkpoints, end to end).
    **Model staleness** — how stale a served model gets before the
    continuous-learning loop replaces it: covariate drift is injected
    under live traffic and the clock runs from the first drifted batch
    served to the refreshed canary auto-promoting (drift detection +
    online re-fit + save with fresh baseline + shadow compare + promote).
    """
    import os
    import shutil
    import tempfile
    import threading

    from heat_tpu import serving as srv
    from heat_tpu.serving import canary as cnry
    from heat_tpu.streaming import FileSegmentLog, RefreshDriver, StreamingKMeans
    from heat_tpu.telemetry import alerts as _al
    from heat_tpu.telemetry import sketch as _sk

    # -- sustained ingest ------------------------------------------------
    window, feat, n_windows = 256, 16, 160
    total_bytes = n_windows * window * feat * 4
    d = tempfile.mkdtemp(prefix="heat_tpu_bench_streaming_")
    try:
        log = FileSegmentLog(os.path.join(d, "log"), segment_rows=2048)

        def produce():
            rng = np.random.default_rng(0)
            for _ in range(n_windows // 8):
                log.append(rng.standard_normal((window * 8, feat)).astype(np.float32))

        producer = threading.Thread(target=produce, daemon=True)
        ck = os.path.join(d, "ck")
        km = StreamingKMeans(n_clusters=8, window_rows=window, commit_every=8,
                             checkpoint_dir=ck, resume_from=ck)
        t0 = time.perf_counter()
        producer.start()
        while log.size < window:
            time.sleep(0.001)  # seed window: the init state peeks it
        while km.n_windows_ < n_windows:  # dry head pauses the fit; resume it
            before = km.n_windows_
            km.fit_stream(log, max_windows=n_windows)
            if km.n_windows_ == before:
                time.sleep(0.001)  # producer hasn't landed a full window yet
        ingest_s = time.perf_counter() - t0
        producer.join(timeout=30)
        ingest_mbs = total_bytes / 1e6 / ingest_s

        # -- model staleness ---------------------------------------------
        centers = np.array([[0.0] * feat, [40.0] * feat, [80.0] * feat], np.float32)

        def rows_of(n, rng, shift=0.0):
            labels = np.arange(n) % 3
            return (centers[labels]
                    + rng.standard_normal((n, feat)).astype(np.float32) * 0.5
                    + np.float32(shift)).astype(np.float32)

        log2 = FileSegmentLog(os.path.join(d, "log2"), segment_rows=1024)
        log2.append(rows_of(64 * 8, np.random.default_rng(1)))
        ck2 = os.path.join(d, "ck2")
        km2 = StreamingKMeans(n_clusters=3, window_rows=64, commit_every=1,
                              checkpoint_dir=ck2, resume_from=ck2)
        km2.fit_stream(log2)
        sk = _sk.ModelSketch("stream_km", feat)
        sk.update(km2.recent_window_)
        md = os.path.join(d, "models")
        srv.save_model(km2.to_estimator(), md, version=1, name="stream_km",
                       baseline=sk.doc())
        svc = srv.InferenceService(max_delay_ms=1.0, max_batch=64)
        svc.load("stream_km", md, version=1)
        svc.canary.fraction = 1.0
        svc.canary.min_rows = 48

        def fitter():
            log2.append(rows_of(64 * 4, np.random.default_rng(2), shift=4.0))
            fresh = StreamingKMeans(n_clusters=3, window_rows=64, commit_every=1,
                                    checkpoint_dir=ck2, resume_from=ck2)
            return fresh.fit_stream(log2)

        drv = RefreshDriver(svc, "stream_km", md, fitter)
        rng = np.random.default_rng(9)
        t1 = time.perf_counter()
        deadline = t1 + 120.0
        refreshed_at = None
        while time.perf_counter() < deadline:
            svc.predict("stream_km", rows_of(8, rng, shift=4.0))
            out = drv.check()
            if out == "refreshed" and refreshed_at is None:
                refreshed_at = time.perf_counter() - t1
            if svc.registry.active_version("stream_km") == 2:
                break
        staleness_s = time.perf_counter() - t1
        promoted = svc.registry.active_version("stream_km") == 2
        svc.close()
        return {
            "metric": "streaming_ingest_mbs",
            "value": round(ingest_mbs, 2),
            "unit": "MB/s",
            "vs_baseline": 0.0,
            "vs_baseline_kind": "durable_log_to_model_sustained",
            "ingest_windows": n_windows,
            "ingest_bytes": total_bytes,
            "ingest_s": round(ingest_s, 3),
            "staleness_s": round(staleness_s, 3),
            "refresh_s": round(refreshed_at, 3) if refreshed_at is not None else None,
            "staleness_promoted": promoted,
        }
    finally:
        cnry.reset_canary_state()
        _al.clear_alerts()
        _sk.SKETCHES.clear()
        shutil.rmtree(d, ignore_errors=True)


def bench_qos(ht, sync_floor, roofline=None):
    """Config 13: multi-tenant QoS scheduling (ISSUE 18).

    A latency-class tenant's small-request stream is measured solo and
    then again with four batch-class clients flooding 64-row requests
    through the same service — the strict-priority depth gate plus the
    EDF batch pick must keep the latency tail pinned near its solo
    shape while the batch lane absorbs the shedding.  Reported: solo
    and contended latency p50/p99, the noisy-neighbor p99 inflation
    (``vs_baseline`` = contended p99 / solo p99 — the number the
    ``qos_noisy_neighbor`` CI gate caps at 1.10), latency-class sheds
    (must be 0), batch-lane admit/shed traffic, per-lane depth
    surfaces, and the per-tenant cost accounts folded by the request
    stream (``/tenantz``: the accounts must sum to the service total).
    """
    import shutil
    import tempfile
    import threading

    from heat_tpu import serving as srv
    from heat_tpu.resilience import OverloadedError
    from heat_tpu.telemetry import tenants as ttenants

    rng = np.random.default_rng(18)
    pts = rng.standard_normal((1 << 12, 16)).astype(np.float32)
    x = ht.array(pts, split=0)
    km = ht.cluster.KMeans(n_clusters=8, init="random", max_iter=5, random_state=0).fit(x)

    d = tempfile.mkdtemp(prefix="heat_tpu_bench_qos_")
    svc = None
    try:
        ttenants.reset()
        srv.save_model(km, d, version=1, name="km")
        svc = srv.InferenceService(max_delay_ms=1.0, max_batch=64)
        svc.load("km", d)
        svc.set_class("slo", "latency")
        svc.set_class("bulk", "batch")
        for b in (1, 2, 4, 8, 16, 32, 64):  # warm every bucket
            svc.predict("km", pts[:b])

        sizes = (1, 3, 7, 12)  # the latency-class small-request mix
        sheds = {"latency": 0, "batch_ok": 0, "batch_shed": 0}

        def lat_stream(n=200):
            lat = []
            for i in range(n):
                t0 = time.perf_counter()
                try:
                    svc.predict("km", pts[: sizes[i % len(sizes)]],
                                tenant="slo", timeout=30)
                except OverloadedError:
                    sheds["latency"] += 1
                    continue
                lat.append(time.perf_counter() - t0)
            return np.sort(np.asarray(lat))

        solo = lat_stream()

        stop = threading.Event()

        def bulk():
            while not stop.is_set():
                try:
                    svc.predict("km", pts[:64], tenant="bulk", timeout=30)
                    sheds["batch_ok"] += 1
                except OverloadedError as e:
                    # honor the lane-aware Retry-After hint (a batch
                    # client hammering a full lane measures its own
                    # retry storm, not the scheduler)
                    sheds["batch_shed"] += 1
                    time.sleep(min(max(e.retry_after_s or 0.01, 0.005), 0.05))

        floods = [threading.Thread(target=bulk, name=f"bench-qos-bulk-{i}",
                                   daemon=True) for i in range(4)]
        for t in floods:
            t.start()
        time.sleep(0.1)  # flood to steady state
        contended = lat_stream()
        lanes = svc.admission.lane_depths()
        stop.set()
        for t in floods:
            t.join()

        # drain the account hook (it fires on the batcher thread after
        # callers wake), then read the per-tenant cost ledger
        deadline = time.time() + 5.0
        rep = ttenants.tenantz_report()
        while time.time() < deadline:
            rep = ttenants.tenantz_report()
            by = {(r["tenant"], r["class"]) for r in rep["tenants"]}
            if ("slo", "latency") in by and ("bulk", "batch") in by:
                break
            time.sleep(0.01)
        acct_rows = sum(r["rows"] for r in rep["tenants"])
        solo_p99 = float(solo[int(len(solo) * 0.99)])
        cont_p99 = float(contended[int(len(contended) * 0.99)])
        return {
            "metric": "qos_latency_p99_ms",
            "value": round(cont_p99 * 1e3, 3),
            "unit": "ms",
            "vs_baseline": round(cont_p99 / solo_p99, 3) if solo_p99 else 0.0,
            "vs_baseline_kind": "same_stream_solo_no_batch_flood",
            "solo_p50_ms": round(float(solo[len(solo) // 2]) * 1e3, 3),
            "solo_p99_ms": round(solo_p99 * 1e3, 3),
            "contended_p50_ms": round(float(contended[len(contended) // 2]) * 1e3, 3),
            "contended_p99_ms": round(cont_p99 * 1e3, 3),
            "latency_shed": sheds["latency"],
            "batch_admitted": sheds["batch_ok"],
            "batch_shed": sheds["batch_shed"],
            "lane_limits": {c: lanes[c]["limit"] for c in lanes},
            "tenant_accounts": {
                f"{r['tenant']}/{r['class']}": r["rows"] for r in rep["tenants"]
            },
            "accounts_rows_total": acct_rows,
            "accounts_match_total": acct_rows == rep["total"]["rows"],
        }
    finally:
        if svc is not None:
            svc.close()
        ttenants.reset()
        shutil.rmtree(d, ignore_errors=True)


def main() -> int:
    import heat_tpu as ht
    from heat_tpu.core.compile_cache import use_compile_cache

    use_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform, "kind": dev.device_kind,
                                 "count": len(jax.devices())}}), flush=True)
    sync_floor = _sync_floor()
    results = []
    failed = []
    try:
        roofline = bench_roofline(ht, sync_floor)
        results.append(roofline)
        print(json.dumps(roofline), flush=True)
    except Exception as e:  # keep the grid going; the exit code reports it
        roofline = None
        failed.append("bench_roofline")
        print(json.dumps({"metric": "roofline", "error": f"{type(e).__name__}: {e}"[:200]}), flush=True)
    for bench in (bench_smoke, bench_kmeans, bench_hsvd, bench_dpsgd, bench_fft3d,
                  bench_dispatch, bench_resilience, bench_overlap, bench_telemetry,
                  bench_analysis, bench_serving, bench_canary, bench_streaming,
                  bench_qos, bench_fleet):
        try:
            r = bench(ht, sync_floor, roofline)
            r.setdefault("vs_baseline_kind", BASELINE_KIND)
        except Exception as e:  # record the failure, keep the grid going
            failed.append(bench.__name__)
            r = {
                "metric": bench.__name__,
                "value": -1,
                "unit": "error",
                "vs_baseline": 0.0,
                "error": f"{type(e).__name__}: {e}"[:200],
            }
        # every config embeds the registry state at its end: the bench
        # artifact doubles as a telemetry regression record (comm bytes,
        # compile time, cache traffic per config)
        r["telemetry"] = ht.telemetry.snapshot(include_zero=False)
        results.append(r)
        print(json.dumps(r), flush=True)

    headline = next(r for r in results if r["metric"].startswith("hsvd"))
    summary = dict(headline)
    summary["all"] = results
    print(json.dumps(summary), flush=True)
    if failed:
        print(f"bench.py: {len(failed)} bench(es) raised: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
