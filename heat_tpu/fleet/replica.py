"""Fleet replica: one serving process, born warm, drained gracefully.

``python -m heat_tpu.fleet.replica`` runs ONE shared-nothing serving
replica: it loads its models from checkpoint directories, arms the AOT
executable cache, **pre-warms** every (model, bucket) program from the
manifest (reporting 503-not-ready with ``state: "warming"`` on
``/readyz`` the whole time), flips to ready, and serves ``/v1/*`` until
a SIGTERM starts a **graceful drain**: readiness goes
``state: "draining"`` (the router stops routing here), in-flight and
already-queued requests finish, then the process exits 0 — the
zero-failed-requests half of the replica-kill/drain gates.

:class:`LocalReplicaSet` is the process-management side — the
``ProcessSupervisor`` pattern (PR 8) pointed at serving replicas
instead of fit workers: spawn a replica subprocess (ephemeral port
published through a port file), wait for readiness, drain it with
SIGTERM (escalating to SIGKILL past the timeout), with per-replica log
files for postmortems.  The autoscaler drives it as its actuator; the
fleet bench and tests drive it directly.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Tuple

from ..analysis import tsan as _tsan
from ..resilience.errors import WorkerLostError
from ..resilience.faults import inject as _inject
from ..telemetry import metrics as _tm

__all__ = ["LocalReplicaSet", "main"]

_SPAWNS_C = _tm.counter("fleet.replica_spawns", "replica subprocesses launched")
_STOPS_C = _tm.counter("fleet.replica_stops", "replica subprocesses drained/stopped")
_REPLICAS_G = _tm.gauge("fleet.replicas", "replica subprocesses currently managed")


class _Handle:
    """One managed replica subprocess."""

    __slots__ = ("proc", "url", "port", "log_path", "port_file", "index")

    def __init__(self, proc, url, port, log_path, port_file, index):
        self.proc = proc
        self.url = url
        self.port = port
        self.log_path = log_path
        self.port_file = port_file
        self.index = index


class LocalReplicaSet:
    """Spawn/drain serving-replica subprocesses on this host.

    ``models`` maps model name -> checkpoint directory; every replica
    loads all of them.  ``aot_cache``/``prewarm`` arm cold-start
    elimination: the first replica populates the AOT cache, every later
    one boots from it.  ``base_dir`` holds per-replica port files and
    logs."""

    def __init__(
        self,
        models: Dict[str, str],
        base_dir: str,
        aot_cache: Optional[str] = None,
        prewarm: Optional[str] = None,
        max_batch: Optional[int] = None,
        max_delay_ms: Optional[float] = None,
        queue_depth: Optional[int] = None,
        spawn_timeout_s: float = 120.0,
        env: Optional[dict] = None,
    ):
        self.models = dict(models)
        self.base_dir = os.path.abspath(base_dir)
        self.aot_cache = aot_cache
        self.prewarm = prewarm
        self.max_batch = max_batch
        self.max_delay_ms = max_delay_ms
        self.queue_depth = queue_depth
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.base_env = dict(os.environ if env is None else env)
        self._handles: Dict[str, _Handle] = {}
        self._spawned = 0
        self._lock = _tsan.register_lock("fleet.replicas")
        os.makedirs(self.base_dir, exist_ok=True)

    # -- spawn ----------------------------------------------------------
    def _argv(self, port_file: str) -> List[str]:
        argv = [sys.executable, "-m", "heat_tpu.fleet.replica",
                "--port", "0", "--port-file", port_file]
        for name, directory in sorted(self.models.items()):
            argv += ["--model", f"{name}={directory}"]
        if self.aot_cache:
            argv += ["--aot-cache", self.aot_cache]
        if self.prewarm:
            argv += ["--prewarm", self.prewarm]
        if self.max_batch is not None:
            argv += ["--max-batch", str(int(self.max_batch))]
        if self.max_delay_ms is not None:
            argv += ["--max-delay-ms", str(float(self.max_delay_ms))]
        if self.queue_depth is not None:
            argv += ["--queue-depth", str(int(self.queue_depth))]
        return argv

    def _env(self) -> dict:
        env = dict(self.base_env)
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        # a serving replica is one process on its own device set; the
        # parent's virtual-mesh XLA flags must not leak into it
        env.pop("XLA_FLAGS", None)
        env.setdefault("JAX_PLATFORMS", "cpu")
        return env

    def spawn(self, wait_ready: bool = True) -> str:
        """Launch one replica; returns its base URL (blocks until the
        replica reports ready unless ``wait_ready=False``, in which case
        it blocks only until the port is published).  Raises
        :class:`WorkerLostError` when the replica dies or the timeout
        expires first."""
        _inject("fleet.spawn")
        with self._lock:
            _tsan.note_access("fleet.replicas.table")
            index = self._spawned
            self._spawned += 1
        port_file = os.path.join(self.base_dir, f"replica-{index}.port")
        log_path = os.path.join(self.base_dir, f"replica-{index}.log")
        try:
            os.remove(port_file)
        except OSError:
            pass
        log_fd = os.open(log_path, os.O_CREAT | os.O_WRONLY | os.O_TRUNC, 0o644)
        try:
            proc = subprocess.Popen(
                self._argv(port_file), env=self._env(),
                stdout=log_fd, stderr=subprocess.STDOUT,
            )
        finally:
            os.close(log_fd)
        _SPAWNS_C.inc()
        port = self._await_port(proc, port_file, log_path)
        url = f"http://127.0.0.1:{port}"
        handle = _Handle(proc, url, port, log_path, port_file, index)
        with self._lock:
            _tsan.note_access("fleet.replicas.table")
            self._handles[url] = handle
            _REPLICAS_G.set(len(self._handles))
        if wait_ready:
            self._await_ready(handle)
        return url

    def _await_port(self, proc, port_file: str, log_path: str) -> int:
        deadline = time.monotonic() + self.spawn_timeout_s
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise WorkerLostError(
                    f"replica died during startup (rc={proc.returncode}); "
                    f"log tail:\n{self._tail(log_path)}"
                )
            try:
                with open(port_file) as f:
                    return int(f.read().strip())
            except (OSError, ValueError):
                time.sleep(0.05)
        proc.kill()
        proc.wait()
        raise WorkerLostError(
            f"replica did not publish its port within {self.spawn_timeout_s:.0f}s; "
            f"log tail:\n{self._tail(log_path)}"
        )

    def _await_ready(self, handle: _Handle) -> None:
        deadline = time.monotonic() + self.spawn_timeout_s
        while time.monotonic() < deadline:
            if handle.proc.poll() is not None:
                raise WorkerLostError(
                    f"replica died before ready (rc={handle.proc.returncode}); "
                    f"log tail:\n{self._tail(handle.log_path)}"
                )
            try:
                with urllib.request.urlopen(handle.url + "/readyz", timeout=2.0):
                    return
            except urllib.error.HTTPError:
                time.sleep(0.1)  # up but warming (503)
            except Exception:  # lint: allow H501(socket not accepting yet; keep polling until the deadline)
                time.sleep(0.1)
        raise WorkerLostError(
            f"replica did not become ready within {self.spawn_timeout_s:.0f}s; "
            f"log tail:\n{self._tail(handle.log_path)}"
        )

    @staticmethod
    def _tail(path: str, limit: int = 2000) -> str:
        try:
            with open(path, "rb") as f:
                data = f.read()
            return data[-limit:].decode("utf-8", "replace")
        except OSError:
            return ""

    # -- drain / stop ---------------------------------------------------
    def drain_stop(self, url: str, timeout_s: float = 30.0) -> Optional[int]:
        """Gracefully stop one replica: SIGTERM (the replica drains and
        exits 0), SIGKILL past the timeout.  Returns the exit code, or
        None when the url is unknown."""
        with self._lock:
            _tsan.note_access("fleet.replicas.table")
            handle = self._handles.pop(url.rstrip("/"), None)
            _REPLICAS_G.set(len(self._handles))
        if handle is None:
            return None
        proc = handle.proc
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        _STOPS_C.inc()
        return proc.returncode

    def kill(self, url: str) -> Optional[int]:
        """SIGKILL one replica (the replica-loss scenario; no drain)."""
        with self._lock:
            _tsan.note_access("fleet.replicas.table")
            handle = self._handles.pop(url.rstrip("/"), None)
            _REPLICAS_G.set(len(self._handles))
        if handle is None:
            return None
        proc = handle.proc
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        _STOPS_C.inc()
        return proc.returncode

    def urls(self) -> List[str]:
        with self._lock:
            _tsan.note_access("fleet.replicas.table", write=False)
            return sorted(self._handles)

    def tail(self, url: str, limit: int = 2000) -> str:
        """The log tail of one managed replica (postmortems)."""
        with self._lock:
            _tsan.note_access("fleet.replicas.table", write=False)
            handle = self._handles.get(url.rstrip("/"))
        return self._tail(handle.log_path, limit) if handle is not None else ""

    def close(self, timeout_s: float = 30.0) -> None:
        """Drain-stop every managed replica.  Idempotent."""
        for url in self.urls():
            self.drain_stop(url, timeout_s=timeout_s)

    def __enter__(self) -> "LocalReplicaSet":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


# ----------------------------------------------------------------------
# the replica process itself
# ----------------------------------------------------------------------
def _parse_models(specs: List[str]) -> List[Tuple[str, str]]:
    out = []
    for spec in specs:
        if "=" not in spec:
            raise SystemExit(f"--model needs name=directory, got {spec!r}")
        name, directory = spec.split("=", 1)
        out.append((name, directory))
    return out


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m heat_tpu.fleet.replica`` — one serving replica."""
    import argparse

    ap = argparse.ArgumentParser(description="heat_tpu fleet serving replica")
    ap.add_argument("--model", action="append", default=[],
                    help="name=checkpoint-directory (repeatable)")
    ap.add_argument("--port", type=int, default=0,
                    help="HTTP port (0 = ephemeral, published via --port-file)")
    ap.add_argument("--port-file", default=None,
                    help="write the bound port here once serving")
    ap.add_argument("--prewarm", default=None,
                    help="pre-warm manifest path (export_prewarm_manifest)")
    ap.add_argument("--aot-cache", default=None,
                    help="AOT executable cache directory (HEAT_TPU_AOT_CACHE)")
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--max-delay-ms", type=float, default=None)
    ap.add_argument("--queue-depth", type=int, default=None)
    ap.add_argument("--drain-timeout-s", type=float, default=None)
    args = ap.parse_args(argv)

    from ..core import aot_cache
    from ..serving import InferenceService
    from ..telemetry import server as tserver

    if args.aot_cache:
        aot_cache.configure(args.aot_cache)

    svc = InferenceService(
        max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms,
        queue_depth=args.queue_depth,
    )
    svc.set_state("warming")
    url = svc.serve(args.port)
    if args.port_file:
        from ..resilience.atomic import atomic_write

        port = int(url.rsplit(":", 1)[1])
        with atomic_write(args.port_file, checksum=False) as tmp:
            with open(tmp, "w") as f:
                f.write(f"{port}\n")
    for name, directory in _parse_models(args.model):
        svc.load(name, directory)
    if args.prewarm:
        res = svc.prewarm(path=args.prewarm)
        print(f"replica prewarm: {json.dumps(res)}", flush=True)
    svc.set_state("ready")
    print(f"replica ready on {url}", flush=True)

    # SIGTERM -> graceful drain: readiness flips to "draining", the
    # router stops sending, in-flight work finishes, exit 0
    stop = threading.Event()

    def _on_term(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    stop.wait()
    drained = svc.drain(timeout=args.drain_timeout_s)
    tserver.stop_server()
    print(f"replica drained cleanly: {drained}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
