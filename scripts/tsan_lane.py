"""Sanitized CI test lane: the threaded test subset under HEAT_TPU_TSAN=1.

Runs the test files that exercise the framework's real thread
surface — the async-checkpoint writer and loader threads
(``test_overlap.py``), the introspection HTTP server and crash
excepthooks (``test_introspection.py``), the shared metrics/span
state (``test_telemetry.py``), the serving layer's coalescer/
registry-loader/admission threads plus its HTTP routes
(``test_serving.py``), the canary decision plane's shadow thread vs
batcher offers vs /canaryz scrapes (``test_canary.py``), the
request-tracing context handoffs +
tail-store concurrency (``test_tracing.py``), the quality-signal
layer's SLO tick thread / alert table / sketch registry
(``test_slo.py``, ``test_drift.py``), the fleet layer's router
handler/health-poller threads, circuit breakers, AOT-cache config and
autoscaler tick (``test_fleet.py``), and the
streaming layer's segment-log producer/consumer split, refresh-driver
poll thread and 4-thread live-traffic e2e (``test_streaming.py``,
``test_streaming_resume.py``), and the QoS layer's priority-lane
admission under flood threads, EDF coalescer wake races and the
process-wide preemption gate vs fit threads (``test_qos.py``,
``test_qos_resume.py``), and the explainability plane's decision
journal (durable segment writer vs /decisionz scrapes vs the forced
4-thread incident e2e) plus the TSDB sampler thread vs controller
``record`` pushes (``test_journal.py``, ``test_tsdb.py``), and the
protocol verifier's runtime conformance hook racing controller emits
through the journal (``test_protocols.py``) — in a
subprocess with the concurrency
sanitizer armed, then audits the subprocess's ``HEAT_TPU_TSAN_DUMP``
findings artifact.  The lane passes only when the tests pass AND the
sanitizer recorded **zero** findings: no lock-order cycle and no
off-thread unguarded access anywhere in the real code paths the subset
drives.

    python scripts/tsan_lane.py [--pytest-args ...]

Exit status: 0 = tests green + zero findings, 1 = anything else.
``run_lane()`` returns the record ``perf_ci.py`` embeds (hard-cap gate:
``count`` must stay 0).
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the threaded subset (the surfaces the sanitizer instruments)
LANE_FILES = (
    "tests/test_overlap.py",
    "tests/test_introspection.py",
    "tests/test_telemetry.py",
    "tests/test_serving.py",
    "tests/test_canary.py",
    "tests/test_tracing.py",
    "tests/test_slo.py",
    "tests/test_drift.py",
    "tests/test_fleet.py",
    "tests/test_streaming.py",
    "tests/test_streaming_resume.py",
    "tests/test_qos.py",
    "tests/test_qos_resume.py",
    "tests/test_journal.py",
    "tests/test_tsdb.py",
    "tests/test_protocols.py",
)


def run_lane(pytest_args=(), quiet=False):
    """Run the sanitized lane; returns a perf_ci-embeddable record:
    ``{"count", "max_count", "findings", "pytest_exit", ...}`` where
    ``count`` sums sanitizer findings plus a sentinel for a red test
    run."""
    fd, dump = tempfile.mkstemp(prefix="heat_tpu_tsan_", suffix=".json")
    os.close(fd)
    os.unlink(dump)  # the subprocess writes it at exit
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        HEAT_TPU_TSAN="1",
        HEAT_TPU_TSAN_DUMP=dump,
    )
    cmd = [
        sys.executable, "-m", "pytest", *LANE_FILES, "-q",
        "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
        *pytest_args,
    ]
    proc = subprocess.run(
        cmd, cwd=REPO, env=env,
        capture_output=quiet, text=True,
    )
    findings = None
    try:
        with open(dump) as f:
            findings = json.load(f).get("findings", [])
    except (OSError, ValueError):
        pass  # missing/torn dump counts as a lane failure below
    finally:
        try:
            os.unlink(dump)
        except OSError:
            pass

    count = 0
    items = []
    if proc.returncode != 0:
        count += 1000  # red tests fail the lane regardless of findings
        items.append(f"pytest exited {proc.returncode}")
    if findings is None:
        count += 1000
        items.append("sanitizer dump missing/unreadable")
        findings = []
    count += len(findings)
    items += [f"{f.get('rule')}: {f.get('message', '')[:120]}" for f in findings]
    return {
        "count": count,
        "max_count": 0,
        "pytest_exit": proc.returncode,
        "findings": len(findings),
        "files": list(LANE_FILES),
        "items": items[:20],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pytest-args", nargs=argparse.REMAINDER, default=[])
    args = ap.parse_args()

    res = run_lane(pytest_args=args.pytest_args)
    print(json.dumps({k: v for k, v in res.items() if k != "files"}, indent=1))
    if res["count"] > 0:
        print("\nTSAN LANE FAILED:")
        for item in res["items"]:
            print(f"  - {item}")
        sys.exit(1)
    print("tsan lane passed: tests green, zero sanitizer findings")
    sys.exit(0)


if __name__ == "__main__":
    main()
