"""Perf-trajectory history: make the gate metrics visible BETWEEN runs.

``perf_gate.py`` answers "did this run regress vs the committed
record?"; nothing answered "how has sort_psrs moved over the last ten
PRs?" — the trajectory was invisible because every BENCH_CI regeneration
overwrites the previous one.  This script appends each BENCH_CI run's
headline gate numbers to ``BENCH_HISTORY.jsonl`` (one JSON record per
run, written through the resilience atomic+CRC32 writer so the log can
never tear) and renders the trend into ``docs/perf_history.md``:

    python scripts/perf_ci.py > BENCH_CI.json      # (CI does this)
    python scripts/bench_history.py                # append + render

Appends are idempotent: re-running against an unchanged BENCH_CI.json
(same metrics) is a no-op, so the history records *runs*, not
invocations.  Each record carries the run's git revision and UTC
timestamp.

**Trend gate** (ROADMAP 5c): single-run gating (`perf_gate.py`) gives
each run ``spread_pct`` + margin of slack, so a regression that arrives
in 2%-per-PR steps never trips it.  :func:`trend_verdicts` computes
per-metric **k-run rolling medians** over the history and flags a
metric whose latest median has moved against its *direction of good*
(anchored ratios up = good, seconds/overhead/count down = good) by more
than ``DRIFT_PCT`` vs the median of the k runs before — sustained
drift, immune to the single-run noise the medians absorb.  The verdict
column renders into ``docs/perf_history.md`` and ``perf_ci.py`` embeds
:func:`trend_check` as the hard-cap ``perf_trend`` gate (count of
DRIFT verdicts must stay 0).  A metric with fewer than ``2k`` recorded
runs reports ``warming`` and cannot fail the gate.

**Backfill** (``--backfill``): seeds the warm-up window from the
archived chip-bench runs (``BENCH_r0*.json``) so the archived metrics'
medians are defined from day one; archive records are stamped
``archived`` and never re-appended.
"""

import argparse
import datetime
import glob
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: how many trailing runs the rendered markdown table shows per metric
SHOWN_RUNS = 8

#: rolling-median window (runs) of the trend gate
ROLL_K = 5

#: sustained move (percent, against the metric's direction of good)
#: between the two adjacent k-run medians that counts as drift
DRIFT_PCT = 10.0

#: drift threshold for ``overhead_pct``-kind metrics, in absolute
#: percentage POINTS between the two adjacent k-run medians.  Paired
#: overhead statistics hover at 0 by construction (their per-run <3%
#: hard caps are the primary gate), so a RELATIVE move against a ~0-pp
#: median is unbounded noise — a measured −0.18pp → 0.46pp window
#: rotation reads as "+356%" while both medians sit far inside every
#: cap that actually defends the property.  Half the hard cap: a
#: sustained 1.5-pp median creep is a real signal the caps would only
#: catch one noisy run at a time.
DRIFT_POINTS = 1.5

#: gate-record key -> direction of good: +1 = bigger is better (anchored
#: ratios), -1 = smaller is better (wall time, overhead, counts), 0 =
#: informational (anchors themselves — runner speed is not a regression)
KIND_DIRECTION = {
    "rel_to_anchor": +1,
    "overhead_pct": -1,
    "seconds": -1,
    "count": -1,
    "value": 0,
    # floored/capped values (perf_gate min_value / max_value kinds):
    # the fleet scale-out ratio is better bigger, the cold-start ratio
    # better smaller — unlike bare informational "value" records
    "value_min": +1,
    "value_max": -1,
}


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=REPO, capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "?"
    except Exception:  # lint: allow H501(history works outside a git checkout)
        return "?"


def headline(rec: dict):
    """One number per gate metric — the quantity its gate kind watches:
    anchored kernels report ``rel_to_anchor``, overhead gates
    ``overhead_pct``, latency caps ``seconds``, count caps ``count``,
    anchors their ``value``; broken kernels record ``None``."""
    if not isinstance(rec, dict):
        return None
    for key in ("rel_to_anchor", "overhead_pct", "count", "value", "seconds"):
        if key in rec:
            return rec[key]
    return None  # error entry


def headline_kind(rec: dict):
    """Which gate-record key :func:`headline` reported (drives the
    trend gate's direction of good); None for error entries.  A
    ``value`` under a perf_gate floor/cap reports as ``value_min`` /
    ``value_max`` so the trend layer knows its direction of good."""
    if not isinstance(rec, dict):
        return None
    for key in ("rel_to_anchor", "overhead_pct", "count", "value", "seconds"):
        if key in rec:
            if key == "value" and "min_value" in rec:
                return "value_min"
            if key == "value" and "max_value" in rec:
                return "value_max"
            return key
    return None


def extract_record(bench: dict, rev: str, timestamp: str) -> dict:
    return {
        "recorded_at": timestamp,
        "git_rev": rev,
        "metrics": {
            name: headline(rec)
            for name, rec in sorted(bench.items())
            if isinstance(rec, dict)
        },
        "kinds": {
            name: headline_kind(rec)
            for name, rec in sorted(bench.items())
            if isinstance(rec, dict) and headline_kind(rec) is not None
        },
    }


def load_history(path: str) -> list:
    """Checksum-verified history records (empty when no log yet)."""
    from heat_tpu.resilience.atomic import verify_checksum

    if not os.path.exists(path):
        return []
    verify_checksum(path)
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return records


def _write_history(path: str, records: list) -> None:
    from heat_tpu.resilience.atomic import atomic_write

    with atomic_write(path) as tmp:
        with open(tmp, "w") as f:
            for rec in records:
                f.write(json.dumps(rec, sort_keys=True) + "\n")


def append_history(path: str, record: dict) -> bool:
    """Append one run record (atomic rewrite + CRC sidecar); returns
    False when the last record already carries identical metrics (an
    idempotent re-run against the same BENCH_CI.json)."""
    records = load_history(path)
    if records and records[-1].get("metrics") == record["metrics"]:
        return False
    records.append(record)
    _write_history(path, records)
    return True


# ----------------------------------------------------------------------
# backfill from the archived chip-bench runs
# ----------------------------------------------------------------------
def archive_records(repo: str = REPO) -> list:
    """History records reconstructed from the ``BENCH_r0*.json``
    archives (the chip-bench rounds): each archive's parsed metric set
    becomes one ``archived``-stamped record.  Archives without parsed
    metrics (raw log captures) are skipped — backfill is honest about
    what the archives actually hold."""
    out = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r0*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = doc.get("parsed") if isinstance(doc, dict) else None
        if not isinstance(parsed, dict):
            continue
        entries = parsed.get("all")
        if not isinstance(entries, list):
            entries = [parsed] if parsed.get("metric") else []
        metrics = {
            e["metric"]: e.get("value")
            for e in entries
            if isinstance(e, dict) and e.get("metric")
        }
        if not metrics:
            continue
        out.append(
            {
                "recorded_at": None,
                "git_rev": os.path.splitext(os.path.basename(path))[0],
                "archived": True,
                "metrics": metrics,
                # chip metrics are throughputs: bigger is better
                "kinds": {name: "rel_to_anchor" for name in metrics},
            }
        )
    return out


def backfill_history(path: str, repo: str = REPO) -> int:
    """Prepend the archived chip-bench records to the history (before
    every live record, ordered by round).  Idempotent: archives already
    present (by ``git_rev``) are skipped.  Returns how many were
    added."""
    records = load_history(path)
    have = {r.get("git_rev") for r in records if r.get("archived")}
    fresh = [r for r in archive_records(repo) if r["git_rev"] not in have]
    if not fresh:
        return 0
    live = [r for r in records if not r.get("archived")]
    old = [r for r in records if r.get("archived")]
    merged = sorted(old + fresh, key=lambda r: r["git_rev"]) + live
    _write_history(path, merged)
    return len(fresh)


# ----------------------------------------------------------------------
# the trend gate: k-run rolling medians, direction-aware drift verdicts
# ----------------------------------------------------------------------
def _median(vals: list) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def metric_series(records: list, name: str) -> list:
    """The metric's numeric history, oldest first (missing/error runs
    skipped — a run where the kernel was broken must not poison the
    median)."""
    out = []
    for r in records:
        v = (r.get("metrics") or {}).get(name)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out.append(float(v))
    return out


def metric_kind(records: list, name: str):
    """The metric's gate kind from the newest record that stamped it."""
    for r in reversed(records):
        kind = (r.get("kinds") or {}).get(name)
        if kind is not None:
            return kind
    return None


def metric_direction(records: list, name: str) -> int:
    """The metric's direction of good from the newest record that
    stamped its kind (0 = informational/unknown: never gated)."""
    return KIND_DIRECTION.get(metric_kind(records, name), 0)


def trend_verdict(series: list, direction: int, k: int = ROLL_K,
                  drift_pct: float = DRIFT_PCT, kind: str = None) -> dict:
    """One metric's verdict: compare the median of the newest ``k``
    runs against the median of the ``k`` runs before them.

    Two noise guards, both forced by measured window rotations on this
    runner (the per-run gates in perf_gate.py stay the primary defense
    either way):

    * ``overhead_pct`` metrics drift on the ABSOLUTE move in
      percentage points (``DRIFT_POINTS``) — their medians hover at 0,
      so a relative threshold divides by noise;
    * every other kind scales the threshold to the previous window's
      own min..max span: a committed window spanning ~25% run to run
      cannot certify a 10% median move as signal (perf_gate's
      median-minus-spread principle at window scale), while genuine
      route regressions (5–20×) clear any plausible span.

    Returns ``{"verdict", "median_now", "median_prev", "move_pct"}``
    where verdict is ``ok`` / ``DRIFT`` / ``warming`` (fewer than
    ``2k`` runs) / ``n/a`` (informational metric).  ``move_pct`` is
    signed (positive = value went up); for ``overhead_pct`` metrics it
    is absolute percentage points, relative percent otherwise."""
    if direction == 0:
        return {"verdict": "n/a", "median_now": None, "median_prev": None,
                "move_pct": None}
    if len(series) < 2 * k:
        med = _median(series[-k:]) if series else None
        return {"verdict": "warming", "median_now": med, "median_prev": None,
                "move_pct": None}
    med_now = _median(series[-k:])
    prev_win = series[-2 * k: -k]
    med_prev = _median(prev_win)
    if kind == "overhead_pct":
        move = med_now - med_prev  # percentage points
        threshold = DRIFT_POINTS
    else:
        move = 100.0 * (med_now - med_prev) / abs(med_prev) if med_prev else 0.0
        spread = (100.0 * (max(prev_win) - min(prev_win)) / abs(med_prev)
                  if med_prev else 0.0)
        threshold = max(drift_pct, spread)
    # drift = the median moved AGAINST the direction of good: ratios
    # falling, or seconds/overhead/counts rising
    bad = (-move if direction > 0 else move) > threshold
    return {
        "verdict": "DRIFT" if bad else "ok",
        "median_now": med_now,
        "median_prev": med_prev,
        "move_pct": round(move, 2),
    }


def trend_verdicts(records: list, k: int = ROLL_K,
                   drift_pct: float = DRIFT_PCT) -> dict:
    """Every metric's trend verdict over the history (name -> verdict
    doc, sorted)."""
    names = sorted({n for r in records for n in (r.get("metrics") or {})})
    out = {}
    for name in names:
        out[name] = trend_verdict(
            metric_series(records, name),
            metric_direction(records, name),
            k=k, drift_pct=drift_pct,
            kind=metric_kind(records, name),
        )
    return out


def trend_check(history_path: str, current_metrics: dict = None,
                current_kinds: dict = None, k: int = ROLL_K,
                drift_pct: float = DRIFT_PCT) -> dict:
    """The ``perf_ci.py``-embeddable hard-cap record: DRIFT verdicts
    over the history *with the current run appended* must stay 0.

    ``current_metrics``/``current_kinds`` are this run's (un-appended)
    headline numbers — the gate judges the run being built, not the
    last committed one.  Metrics still warming (fewer than ``2k``
    runs) cannot fail."""
    records = load_history(history_path)
    if current_metrics:
        records = records + [
            {"metrics": dict(current_metrics), "kinds": dict(current_kinds or {})}
        ]
    verdicts = trend_verdicts(records, k=k, drift_pct=drift_pct)
    drifts = {n: v for n, v in verdicts.items() if v["verdict"] == "DRIFT"}
    return {
        "count": len(drifts),
        "max_count": 0,
        "runs_recorded": len(records),
        "roll_k": k,
        "drift_pct": drift_pct,
        "warming": sum(1 for v in verdicts.values() if v["verdict"] == "warming"),
        "gated": sum(1 for v in verdicts.values() if v["verdict"] in ("ok", "DRIFT")),
        "items": [
            f"{n}: median {v['median_prev']:.6g} -> {v['median_now']:.6g} "
            f"({v['move_pct']:+.1f}%) over {k}-run windows"
            for n, v in sorted(drifts.items())
        ],
    }


def _fmt(v) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def render_markdown(records: list, out_path: str) -> None:
    """One row per gate metric, one column per trailing run (newest
    right), the latest-vs-previous delta, and the rolling-median trend
    verdict (ROADMAP 5c)."""
    shown = records[-SHOWN_RUNS:]
    names = sorted({n for r in records for n in r.get("metrics", {})})
    verdicts = trend_verdicts(records)
    n_archived = sum(1 for r in records if r.get("archived"))
    lines = [
        "# Perf history",
        "",
        "Generated from `BENCH_HISTORY.jsonl` by `scripts/bench_history.py`"
        " — do not edit.  Each column is one BENCH_CI regeneration (the"
        " headline number of every gate metric: anchored ratio, overhead %,"
        " seconds, or count — see the gate kinds in `scripts/perf_gate.py`);"
        " `Δ` compares the two newest runs.  `trend` is the rolling-median"
        f" verdict: the median of the newest {ROLL_K} runs vs the {ROLL_K}"
        f" before — a move worse than {DRIFT_PCT:g}% against the metric's"
        " direction of good is sustained **DRIFT** (enforced as the"
        " `perf_trend` hard-cap gate in `scripts/perf_ci.py`); metrics with"
        f" fewer than {2 * ROLL_K} runs are `warming`, anchors are `n/a`.",
        "",
        f"{len(records)} run(s) recorded"
        + (f" ({n_archived} backfilled from the BENCH_r0* archives)" if n_archived else "")
        + f"; showing the last {len(shown)}.",
        "",
    ]
    header = ["metric"] + [
        f"{r.get('git_rev', '?')}<br>{str(r.get('recorded_at') or 'archive')[:10]}"
        for r in shown
    ] + ["Δ", "trend"]
    lines.append("| " + " | ".join(header) + " |")
    lines.append("|" + "---|" * len(header))
    for name in names:
        vals = [r.get("metrics", {}).get(name) for r in shown]
        delta = "—"
        nums = [v for v in vals if isinstance(v, (int, float))]
        if len(nums) >= 2 and isinstance(vals[-1], (int, float)):
            prev = next(
                (v for v in reversed(vals[:-1]) if isinstance(v, (int, float))), None
            )
            if prev is not None:
                d = vals[-1] - prev
                delta = f"{d:+.4g}" + (
                    f" ({100.0 * d / prev:+.1f}%)" if prev else ""
                )
        v = verdicts.get(name) or {}
        verdict = v.get("verdict", "—")
        if verdict == "DRIFT":
            verdict = f"**DRIFT** ({v['move_pct']:+.1f}%)"
        elif verdict == "ok" and v.get("move_pct") is not None:
            verdict = f"ok ({v['move_pct']:+.1f}%)"
        lines.append(
            "| `" + name + "` | " + " | ".join(_fmt(x) for x in vals)
            + f" | {delta} | {verdict} |"
        )
    lines += [
        "",
        "## Regime anchors",
        "",
        "The anchored kernels publish `rel_to_anchor` ="
        " bytes-moved-model / time / stream-anchor — a dimensionless"
        " fraction of the kernel's *minimal regime traffic* at the"
        " runner's own measured bandwidth, not a bare one-pass ratio"
        " (ROADMAP 5b).  The models:",
        "",
        "| kernel | bytes-moved model |",
        "|---|---|",
        "| `fft3d_64` | 48 B/el — planar 3-D FFT: per-axis pass read +"
        " (re, im) write over f32 input |",
        "| `sort_psrs` | 28 B/el — PSRS touches every f32 key ~7×:"
        " local sort r+w, pivot partition r, all-to-all exchange r+w,"
        " final merge r+w |",
        "| `sparse_spmm_ring` | p·X + 12 B/nnz + out — the ring"
        " circulates the dense operand past every shard (p reads of X),"
        " each CSR block streams once (f64 value + int32 column), the"
        " f64 output writes once |",
        "| `spgemm_ring` | p·B_planes + r_max·(16 B/nnz_A) + 16 B/nnz_C"
        " — B's (comp, other, val) triplet planes circulate past every"
        " shard, each A entry expands to r_max partial triplets"
        " (int32 keys + f32/f64 value) that sort/merge locally, and"
        " only the canonical output triplets write back; no dense"
        " (m/P, n) block ever exists (ISSUE 16 tentpole 1) |",
        "| `fftn_2d` / `fftn_f64` | 2-D: 32 B/el — two axis passes"
        " read + (re, im) write over f32 input; f64 doubles the element"
        " size but NOT the pass count — the hi/lo split contraction"
        " (three f32 dots per f64 dot) raises flops, not minimal bytes,"
        " so the bytes model stays per-axis-pass · 2 · elsize |",
        "",
        "Each record also carries `model_gbytes_per_s` (the model over"
        " the measured time) so the anchored ratio is auditable.",
        "",
        "See also: [observability](observability.md), the committed gate"
        " record `BENCH_CI.json`, and `scripts/perf_gate.py` for the"
        " regression rules.",
        "",
    ]
    with open(out_path, "w") as f:
        f.write("\n".join(lines))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--bench", default=os.path.join(REPO, "BENCH_CI.json"))
    ap.add_argument("--history", default=os.path.join(REPO, "BENCH_HISTORY.jsonl"))
    ap.add_argument("--out", default=os.path.join(REPO, "docs", "perf_history.md"))
    ap.add_argument(
        "--render-only", action="store_true",
        help="re-render the markdown from the existing history, no append",
    )
    ap.add_argument(
        "--backfill", action="store_true",
        help="seed the history with the archived BENCH_r0*.json chip runs "
             "(idempotent) before appending/rendering",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="run the rolling-median trend gate over the history and exit "
             "1 on any DRIFT verdict",
    )
    args = ap.parse_args()

    if args.backfill:
        n = backfill_history(args.history)
        print(f"backfilled {n} archived run(s) -> {args.history}")

    if args.check:
        res = trend_check(args.history)
        print(json.dumps(res, indent=1))
        sys.exit(1 if res["count"] > 0 else 0)

    if not args.render_only:
        with open(args.bench) as f:
            bench = json.load(f)
        record = extract_record(
            bench,
            rev=_git_rev(),
            timestamp=datetime.datetime.now(datetime.timezone.utc).isoformat(
                timespec="seconds"
            ),
        )
        if append_history(args.history, record):
            print(f"appended run {record['git_rev']} -> {args.history}")
        else:
            print("history unchanged (same metrics as the last record)")

    records = load_history(args.history)
    render_markdown(records, args.out)
    print(f"rendered {len(records)} run(s) -> {args.out}")


if __name__ == "__main__":
    main()
