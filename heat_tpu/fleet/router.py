"""Fault-tolerant fleet router: N shared-nothing replicas, one front door.

The serving layer (``heat_tpu/serving``) is one process on one port —
a crash drops every in-flight request and nothing shares its load.
:class:`FleetRouter` is the explicit front door of a *replica set*:
a stdlib HTTP process that owns routing policy and admission, in front
of N replicas that share nothing (PAPER.md's shape — explicit
communication, no hidden coordinator).  Five mechanisms:

* **Consistent-hash model affinity with bounded load** — a request for
  model M prefers the replica that rendezvous-hashes highest for M
  (warm executable caches, warm model state), but spills to the next
  replica in M's preference order when the favorite's in-flight count
  exceeds ``HEAT_TPU_FLEET_LOAD_FACTOR`` x the ready-replica average
  + 1 (consistent hashing with bounded loads): affinity when idle,
  fan-out under pressure — the property the 1->4 replica scale-out
  gate measures.
* **Health-aware routing** — a poller thread scrapes every replica's
  ``/readyz`` each ``HEAT_TPU_FLEET_HEALTH_PERIOD_S``: readiness,
  lifecycle state (a *draining* replica stops receiving new work), and
  the replica's model list (the placement map 404-free routing needs).
* **Bounded-retry failover** — ``POST /v1/predict`` is idempotent, so
  a connect error, timeout or 5xx fails over to the next healthy
  replica under a :class:`~heat_tpu.resilience.retry.RetryPolicy`
  (``HEAT_TPU_FLEET_RETRIES`` attempts, short backoff).  Only when no
  replica can take the model does the client see a **typed 503**
  (:class:`~heat_tpu.resilience.errors.NoReplicaError`) with a
  ``Retry-After`` of one health period.  Replica-side verdicts that
  retrying cannot change (400/404/429) pass through.
* **Per-replica circuit breaker** — ``HEAT_TPU_FLEET_CB_FAILURES``
  consecutive failures eject a replica from routing; after
  ``HEAT_TPU_FLEET_CB_COOLDOWN_S`` ONE half-open probe request is
  admitted — success readmits the replica, failure re-opens the
  breaker.  A flapping replica costs its own probes, never the fleet's
  tail latency.
* **Global admission** — one fleet-wide token bucket
  (``HEAT_TPU_FLEET_RATE``/``BURST``) sheds with a 429 + Retry-After
  *before* any replica is touched: N replicas must not mean N times
  the configured quota.

**Cross-replica tracing**: the router stamps a fresh trace_id into
every forwarded predict body; the replica adopts it for its
``serve.request`` tree, so ``aggregate.stitch_traces`` reassembles one
request across router and replica by the existing trace_id merge.

Run in-process (tests, the autoscaler harness) or as its own process::

    python -m heat_tpu.fleet.router --port 8000 \
        --replica http://host:8001 --replica http://host:8002

Routes: ``/v1/*`` proxied with failover; ``/fleet/statusz`` (replica
table, breaker states, counters), ``/fleet/healthz`` (200 iff >= 1
ready replica), ``/fleetz`` (fleet rollup: the health poller collects
each ready replica's ``/canaryz`` canary decision-plane snapshot,
rolled into a fleet-wide per-model verdict table with divergent-replica
highlighting, its ``/tenantz`` accounts and its ``/decisionz`` journal;
``?format=json`` for the machine form), ``/tenantz`` (the
fleet-merged per-tenant cost ledger: each ready replica's ``/tenantz``
accounts summed per tenant via ``aggregate.merge_tenant_accounts`` —
the fleet answer to "which tenant cost what"), ``/metrics`` (the
router process's own registry).
"""

from __future__ import annotations

import hashlib
import json
import socket
import threading
import time
import urllib.error
import urllib.request
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Tuple

from ..analysis import tsan as _tsan
from ..analysis.protocols import (
    ACTOR_ROUTER, CB_HALF_OPEN, CB_READMIT, CB_REOPEN, CB_TRIP,
)
from ..resilience.errors import NoReplicaError, OverloadedError, TransientFault
from ..resilience.faults import inject as _inject
from ..resilience.retry import RetryPolicy
from ..serving.admission import TokenBucket
from ..telemetry import journal as _journal
from ..telemetry import metrics as _tm
from ..telemetry import tracing as _tracing

__all__ = ["FleetRouter", "ReplicaFailure"]

_REQS_C = _tm.counter("fleet.requests", "requests routed (all verbs)")
_FAILOVERS_C = _tm.counter(
    "fleet.failovers", "attempts that failed over to another replica"
)
_SHED_C = _tm.counter("fleet.shed", "requests shed by the fleet-global token bucket")
_NO_REPLICA_C = _tm.counter(
    "fleet.no_replica", "typed 503s: no replica could take the model"
)
_CB_OPEN_C = _tm.counter("fleet.cb_ejections", "circuit-breaker replica ejections")
_CB_CLOSE_C = _tm.counter(
    "fleet.cb_readmissions", "circuit-breaker readmissions (successful half-open probe)"
)
_LATENCY_H = _tm.histogram("fleet.latency_ms", "end-to-end routed request latency")


class ReplicaFailure(TransientFault):
    """One replica attempt failed retryably (connect error, timeout,
    5xx); the failover loop picks another replica on the next attempt."""

    def __init__(self, message: str, url: str = ""):
        super().__init__(message)
        self.url = url


class _Replica:
    """Router-side bookkeeping for one replica (guarded by the router
    lock)."""

    __slots__ = (
        "url", "ready", "state", "models", "not_models", "inflight", "fails",
        "cb_open", "cb_open_until", "probing", "last_poll_ok", "added_at",
        "sweep_ts", "canary", "tenants", "journal",
    )

    def __init__(self, url: str):
        self.url = url.rstrip("/")
        self.ready = False
        self.state = "unknown"
        self.models: Optional[frozenset] = None  # None until first poll
        self.not_models: set = set()  # 404-learned absences until the next poll
        self.inflight = 0
        self.fails = 0
        self.cb_open = False
        self.cb_open_until = 0.0
        self.probing = False
        self.last_poll_ok = 0.0
        self.added_at = time.time()
        #: when the health poller last swept this replica's snapshots
        #: (the throttled cadence of the three below)
        self.sweep_ts = 0.0
        #: last /canaryz?format=json snapshot (None until the replica
        #: answers one) — the fleet-wide canary rollup's per-replica half
        self.canary: Optional[Dict[str, Any]] = None
        #: last /tenantz?format=json snapshot — the fleet-wide
        #: per-tenant cost rollup's per-replica half
        self.tenants: Optional[Dict[str, Any]] = None
        #: last /decisionz?format=json snapshot — the fleet-wide
        #: decision-timeline rollup's per-replica half
        self.journal: Optional[Dict[str, Any]] = None

    def doc(self) -> Dict[str, Any]:
        return {
            "url": self.url,
            "ready": self.ready,
            "state": self.state,
            "models": sorted(self.models) if self.models is not None else None,
            "inflight": self.inflight,
            "consecutive_failures": self.fails,
            "circuit": (
                "half_open" if self.cb_open and self.probing
                else "open" if self.cb_open
                else "closed"
            ),
            "last_poll_ok_age_s": (
                round(time.time() - self.last_poll_ok, 3) if self.last_poll_ok else None
            ),
        }


def _env():
    from ..core import _env as envmod

    return envmod


class FleetRouter:
    """A running fleet router: replica table + health poller + HTTP
    front door.  Constructor arguments override the ``HEAT_TPU_FLEET_*``
    knob defaults per instance."""

    def __init__(
        self,
        replicas: Tuple[str, ...] = (),
        port: int = 0,
        host: str = "127.0.0.1",
        retries: Optional[int] = None,
        timeout_s: Optional[float] = None,
        cb_failures: Optional[int] = None,
        cb_cooldown_s: Optional[float] = None,
        health_period_s: Optional[float] = None,
        rate: Optional[float] = None,
        burst: Optional[float] = None,
        load_factor: Optional[float] = None,
    ):
        env = _env()
        self.retries = int(retries) if retries is not None else env.env_int("HEAT_TPU_FLEET_RETRIES")
        self.timeout_s = float(timeout_s) if timeout_s is not None else env.env_float("HEAT_TPU_FLEET_TIMEOUT_S")
        self.cb_failures = int(cb_failures) if cb_failures is not None else env.env_int("HEAT_TPU_FLEET_CB_FAILURES")
        self.cb_cooldown_s = float(cb_cooldown_s) if cb_cooldown_s is not None else env.env_float("HEAT_TPU_FLEET_CB_COOLDOWN_S")
        self.health_period_s = float(health_period_s) if health_period_s is not None else env.env_float("HEAT_TPU_FLEET_HEALTH_PERIOD_S")
        self.load_factor = float(load_factor) if load_factor is not None else env.env_float("HEAT_TPU_FLEET_LOAD_FACTOR")
        self._bucket = TokenBucket(
            float(rate) if rate is not None else env.env_float("HEAT_TPU_FLEET_RATE"),
            float(burst) if burst is not None else env.env_float("HEAT_TPU_FLEET_BURST"),
        )
        self._replicas: Dict[str, _Replica] = {}
        #: (monotonic, latency_ms) per routed request, bounded — the
        #: autoscaler's p99 window
        self._latencies: deque = deque(maxlen=4096)
        self._lock = _tsan.register_lock("fleet.router")
        self._closed = False
        for url in replicas:
            self.add_replica(url)
        _tm.gauge(
            "fleet.replicas_ready", "replicas currently ready for routing",
            fn=lambda: self._count_ready(),
        )
        # HTTP front door
        router = self

        class _Handler(BaseHTTPRequestHandler):
            server_version = "heat-tpu-fleet-router/1"

            def log_message(self, fmt, *args):  # clients poll; stay silent
                pass

            def _reply(self, status: int, body: str, ctype: str = "application/json",
                       headers: Optional[Dict[str, str]] = None) -> None:
                payload = body.encode("utf-8")
                self.send_response(int(status))
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(payload)))
                for k, v in (headers or {}).items():
                    self.send_header(k, str(v))
                self.end_headers()
                self.wfile.write(payload)

            def do_GET(self):  # noqa: N802 - http.server API
                try:
                    status, body, ctype, headers = router.handle("GET", self.path, None)
                    self._reply(status, body, ctype, headers)
                except BrokenPipeError:
                    pass
                except Exception as e:  # lint: allow H501(a handler bug must 500, never kill the router thread)
                    try:
                        self._reply(500, json.dumps({"error": f"{type(e).__name__}: {e}"}))
                    except Exception:  # lint: allow H501(socket already gone)
                        pass

            def do_POST(self):  # noqa: N802 - http.server API
                try:
                    length = int(self.headers.get("Content-Length") or 0)
                    body = self.rfile.read(length) if length else b""
                    status, out, ctype, headers = router.handle("POST", self.path, body)
                    self._reply(status, out, ctype, headers)
                except BrokenPipeError:
                    pass
                except Exception as e:  # lint: allow H501(a handler bug must 500, never kill the router thread)
                    try:
                        self._reply(500, json.dumps({"error": f"{type(e).__name__}: {e}"}))
                    except Exception:  # lint: allow H501(socket already gone)
                        pass

        self._httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self._address = self._httpd.server_address
        self._serve_thread = threading.Thread(
            target=self._httpd.serve_forever, name="heat-tpu-fleet-router", daemon=True
        )
        self._serve_thread.start()
        # health poller: Event-driven cadence (close() wakes it)
        self._stop = threading.Event()
        self._poll_thread = threading.Thread(
            target=self._poll_loop, name="heat-tpu-fleet-health", daemon=True
        )
        self._poll_thread.start()

    # -- replica set ----------------------------------------------------
    @property
    def port(self) -> int:
        return self._address[1]

    @property
    def url(self) -> str:
        return f"http://{self._address[0]}:{self.port}"

    def add_replica(self, url: str) -> None:
        """Register a replica (idempotent); it becomes routable after
        its first successful readiness poll."""
        r = _Replica(url)
        with self._lock:
            _tsan.note_access("fleet.router.replicas")
            self._replicas.setdefault(r.url, r)

    def remove_replica(self, url: str) -> None:
        """Drop a replica from the table (no-op when absent)."""
        with self._lock:
            _tsan.note_access("fleet.router.replicas")
            self._replicas.pop(url.rstrip("/"), None)

    def drain_replica(self, url: str) -> None:
        """Stop routing NEW work to a replica (its in-flight requests
        finish normally) — the autoscaler calls this before SIGTERM."""
        with self._lock:
            _tsan.note_access("fleet.router.replicas")
            r = self._replicas.get(url.rstrip("/"))
            if r is not None:
                r.state = "draining"
                r.ready = False

    def replica_urls(self) -> List[str]:
        with self._lock:
            _tsan.note_access("fleet.router.replicas", write=False)
            return sorted(self._replicas)

    def preferred(self, model: str) -> Optional[str]:
        """The replica URL ``model``'s traffic currently prefers (the
        rendezvous-hash favorite among ready replicas) — what a
        kill-under-load scenario should aim at, and what an operator
        asks before draining 'the hot one'."""
        with self._lock:
            _tsan.note_access("fleet.router.replicas", write=False)
            ready = [r for r in self._replicas.values() if r.ready and r.state != "draining"]
            order = self._preference(model, ready)
            return order[0].url if order else None

    def _count_ready(self) -> int:
        with self._lock:
            _tsan.note_access("fleet.router.replicas", write=False)
            return sum(1 for r in self._replicas.values() if r.ready)

    # -- health polling -------------------------------------------------
    def _poll_loop(self) -> None:
        while not self._stop.is_set():
            self.poll_health()
            self._stop.wait(self.health_period_s)

    def poll_health(self) -> None:
        """One readiness sweep over the replica table (the poller thread
        runs this every period; tests call it directly for determinism)."""
        with self._lock:
            _tsan.note_access("fleet.router.replicas", write=False)
            urls = list(self._replicas)
            sweep_ts = {u: self._replicas[u].sweep_ts for u in urls}
        now = time.time()
        # the snapshot sweep runs on its own (slower) cadence: the
        # readiness poll can tick sub-second, but re-pulling three
        # snapshots that fast buys nothing
        sweep_period = max(self.health_period_s, 2.0)
        for url in urls:
            ready, state, models = self._probe_readyz(url)
            # the same sweep collects the replica's canary, tenant and
            # journal snapshots (the per-replica halves of the /fleetz
            # fleet rollup) on the throttled cadence.  Only ready
            # replicas are asked: a warming/draining replica's windows
            # are noise.
            due = ready and now - sweep_ts.get(url, 0.0) >= sweep_period
            can = self._probe_canaryz(url) if due else None
            ten = self._probe_tenantz(url) if due else None
            jnl = self._probe_decisionz(url) if due else None
            with self._lock:
                _tsan.note_access("fleet.router.replicas")
                r = self._replicas.get(url)
                if r is None:
                    continue
                if due:
                    r.sweep_ts = now
                if can is not None:
                    r.canary = can
                if ten is not None:
                    r.tenants = ten
                if jnl is not None:
                    r.journal = jnl
                if r.state == "draining" and state not in ("ready",):
                    # a locally initiated drain sticks until the replica
                    # itself reports ready again (a cancelled drain)
                    r.models = models if models is not None else r.models
                    continue
                r.ready = ready
                r.state = state
                if models is not None:
                    r.models = models
                    r.not_models = set()  # the poll is fresher truth
                if ready:
                    r.last_poll_ok = time.time()

    def _probe_readyz(self, url: str):
        """(ready, state, models) for one replica; never raises."""
        try:
            with urllib.request.urlopen(url + "/readyz", timeout=2.0) as resp:
                doc = json.load(resp)
            code = 200
        except urllib.error.HTTPError as e:
            try:
                doc = json.load(e)
            except Exception:  # lint: allow H501(non-JSON 5xx body; the status code is the verdict)
                doc = {}
            code = e.code
        except Exception:  # lint: allow H501(unreachable replica is a routing verdict, not an error)
            return False, "unreachable", None
        state = str(doc.get("state", "unknown"))
        models = doc.get("models")
        models = frozenset(str(m) for m in models) if isinstance(models, list) else None
        return code == 200 and bool(doc.get("ready", code == 200)), state, models

    def _probe_canaryz(self, url: str) -> Optional[Dict[str, Any]]:
        """One replica's canary decision-plane snapshot, or None
        (replica without the route, unreachable, or malformed — never
        raises)."""
        try:
            with urllib.request.urlopen(
                url + "/canaryz?format=json", timeout=2.0
            ) as resp:
                doc = json.load(resp)
            return doc if isinstance(doc, dict) else None
        except Exception:  # lint: allow H501(a canary-less replica is a rollup gap, not an error)
            return None

    def _probe_tenantz(self, url: str) -> Optional[Dict[str, Any]]:
        """One replica's per-tenant cost-account snapshot, or None
        (replica without the route, unreachable, or malformed — never
        raises)."""
        try:
            with urllib.request.urlopen(
                url + "/tenantz?format=json", timeout=2.0
            ) as resp:
                doc = json.load(resp)
            return doc if isinstance(doc, dict) else None
        except Exception:  # lint: allow H501(a meter-less replica is a rollup gap, not an error)
            return None

    def _probe_decisionz(self, url: str) -> Optional[Dict[str, Any]]:
        """One replica's decision-journal snapshot, or None (replica
        without the route, unreachable, or malformed — never raises)."""
        try:
            with urllib.request.urlopen(
                url + "/decisionz?format=json&limit=64", timeout=2.0
            ) as resp:
                doc = json.load(resp)
            return doc if isinstance(doc, dict) else None
        except Exception:  # lint: allow H501(a journal-less replica is a rollup gap, not an error)
            return None

    # -- routing policy -------------------------------------------------
    def _preference(self, model: str, replicas: List[_Replica]) -> List[_Replica]:
        """Rendezvous-hash preference order of ``replicas`` for
        ``model`` (highest hash first): every router instance computes
        the same order from the same replica set, no shared state."""

        def score(r: _Replica) -> int:
            h = hashlib.blake2b(
                f"{model}|{r.url}".encode("utf-8"), digest_size=8
            ).digest()
            return int.from_bytes(h, "big")

        return sorted(replicas, key=score, reverse=True)

    def _pick(self, model: str, exclude: Optional[set] = None) -> Optional[_Replica]:
        """Choose a replica for one attempt (and count it in-flight), or
        None when no replica can take the model right now.

        Policy: rendezvous order, filtered to ready + not draining +
        hosting the model (unknown model lists count as hosting);
        breaker-open replicas are skipped unless their cooldown expired
        and no probe is out (then ONE half-open probe is admitted);
        bounded load spills past a replica whose in-flight exceeds
        ``load_factor`` x the eligible average + 1."""
        now = time.monotonic()
        with self._lock:
            _tsan.note_access("fleet.router.replicas")
            eligible: List[_Replica] = []
            for r in self._replicas.values():
                if exclude and r.url in exclude:
                    continue
                if not r.ready or r.state == "draining":
                    continue
                if model and (
                    model in r.not_models
                    or (r.models is not None and model not in r.models)
                ):
                    continue
                if r.cb_open:
                    if now >= r.cb_open_until and not r.probing:
                        eligible.append(r)  # half-open probe candidate
                    continue
                eligible.append(r)
            if not eligible:
                return None
            order = self._preference(model, eligible)
            total = sum(r.inflight for r in eligible)
            cap = self.load_factor * (total / len(eligible)) + 1.0
            chosen = next((r for r in order if r.inflight < cap), None)
            if chosen is None:
                chosen = min(order, key=lambda r: r.inflight)
            probe = self._cb_mark_probe(chosen)
            chosen.inflight += 1
        # journal after our lock is released (emit takes its own lock)
        if probe:
            trip = _journal.find_last(actor=ACTOR_ROUTER, action=CB_TRIP)
            _journal.emit(
                ACTOR_ROUTER, CB_HALF_OPEN,
                model=model or None,
                severity="info",
                message=f"half-open probe admitted to {chosen.url}",
                cause=(
                    trip["event_id"]
                    if trip and trip["evidence"].get("replica") == chosen.url
                    else None
                ),
                evidence={"replica": chosen.url,
                          "cooldown_s": self.cb_cooldown_s},
            )
        return chosen

    # -- breaker transitions (registered in analysis/protocols.py:
    # writes live in the lock-held helpers below, the declared journal
    # events are emitted by _pick/_report after the lock is released) --
    def _cb_mark_probe(self, replica: _Replica) -> bool:
        """(caller holds ``self._lock``) Flip an eligible open replica
        into its half-open probe slot; True iff this attempt IS the
        probe (open -> half_open)."""
        if not replica.cb_open:
            return False
        replica.probing = True  # the one admitted half-open probe
        return True

    def _cb_on_success(self, replica: _Replica) -> Optional[str]:
        """(caller holds ``self._lock``) Success-path breaker
        bookkeeping; returns the journal verb to emit after release.

        Only the half-open PROBE's success readmits (half_open ->
        closed).  A success while open with no probe out is a stale
        response from before the trip — readmitting on it would skip
        the probe protocol entirely, so it only clears the failure
        streak."""
        replica.fails = 0
        if replica.cb_open and replica.probing:
            replica.cb_open = False
            replica.probing = False
            _CB_CLOSE_C.inc()
            return CB_READMIT
        return None

    def _cb_on_failure(self, replica: _Replica, now: float) -> Optional[str]:
        """(caller holds ``self._lock``) Failure-path breaker
        bookkeeping; returns the journal verb to emit after release.

        A failed half-open probe re-opens for another cooldown
        (half_open -> open, journaled as ``cb_reopen``); a stale
        failure while open with no probe out is silent bookkeeping; a
        closed replica trips once the consecutive-failure threshold is
        crossed."""
        replica.fails += 1
        if replica.cb_open:
            probe_failed = replica.probing
            replica.probing = False
            replica.cb_open_until = now + self.cb_cooldown_s
            return CB_REOPEN if probe_failed else None
        if replica.fails >= self.cb_failures:
            replica.cb_open = True
            replica.probing = False
            replica.cb_open_until = now + self.cb_cooldown_s
            _CB_OPEN_C.inc()
            return CB_TRIP
        return None

    def _report(self, replica: _Replica, ok: bool) -> None:
        """Account one attempt's outcome into the replica's breaker."""
        now = time.monotonic()
        with self._lock:
            _tsan.note_access("fleet.router.replicas")
            replica.inflight = max(0, replica.inflight - 1)
            if ok:
                transition = self._cb_on_success(replica)
            else:
                transition = self._cb_on_failure(replica, now)
            fails = replica.fails
        if transition == CB_TRIP:
            _journal.emit(
                ACTOR_ROUTER, CB_TRIP,
                severity="warn",
                message=(
                    f"circuit breaker opened for {replica.url} after "
                    f"{fails} consecutive failures"
                ),
                evidence={"replica": replica.url, "consecutive_failures": fails,
                          "threshold": self.cb_failures,
                          "cooldown_s": self.cb_cooldown_s},
            )
        elif transition == CB_READMIT:
            probe = _journal.find_last(actor=ACTOR_ROUTER, action=CB_HALF_OPEN)
            _journal.emit(
                ACTOR_ROUTER, CB_READMIT,
                severity="info",
                message=f"half-open probe succeeded; {replica.url} readmitted",
                cause=(
                    probe["event_id"]
                    if probe and probe["evidence"].get("replica") == replica.url
                    else None
                ),
                evidence={"replica": replica.url},
            )
        elif transition == CB_REOPEN:
            probe = _journal.find_last(actor=ACTOR_ROUTER, action=CB_HALF_OPEN)
            _journal.emit(
                ACTOR_ROUTER, CB_REOPEN,
                severity="warn",
                message=(
                    f"half-open probe failed; {replica.url} re-opened for "
                    f"another {self.cb_cooldown_s}s cooldown"
                ),
                cause=(
                    probe["event_id"]
                    if probe and probe["evidence"].get("replica") == replica.url
                    else None
                ),
                evidence={"replica": replica.url,
                          "cooldown_s": self.cb_cooldown_s},
            )

    # -- proxying -------------------------------------------------------
    def _forward(self, replica: _Replica, method: str, path: str,
                 body: Optional[bytes]):
        """One proxied attempt; returns ``(status, body_bytes, headers)``
        or raises :class:`ReplicaFailure` on a retryable outcome."""
        req = urllib.request.Request(
            replica.url + path, data=body, method=method,
            headers={"Content-Type": "application/json"} if body else {},
        )
        try:
            with urllib.request.urlopen(req, timeout=self.timeout_s) as resp:
                out = resp.read()
                self._report(replica, True)
                return resp.getcode(), out, dict(resp.headers)
        except urllib.error.HTTPError as e:
            out = e.read()
            if e.code >= 500:
                self._report(replica, False)
                raise ReplicaFailure(
                    f"replica {replica.url} answered {e.code}", url=replica.url
                ) from None
            # 4xx is the replica's considered verdict (bad request, over
            # quota, unknown model): the replica itself is healthy
            self._report(replica, True)
            return e.code, out, dict(e.headers)
        except (urllib.error.URLError, socket.timeout, ConnectionError, OSError) as e:
            self._report(replica, False)
            raise ReplicaFailure(
                f"replica {replica.url} unreachable ({e})", url=replica.url
            ) from None

    def _route(self, model: str, method: str, path: str, body: Optional[bytes]):
        """Failover routing of one idempotent request: each attempt
        picks the best replica excluding the one that just failed, under
        the bounded :class:`RetryPolicy`."""
        _inject("fleet.route", model=model, path=path)
        policy = RetryPolicy(
            max_attempts=max(1, self.retries),
            base_delay=0.02,
            max_delay=0.5,
            retryable=(ReplicaFailure,),
        )
        last_failed: set = set()

        def no_candidate(attempts: int):
            # distinguish "the fleet is down" (typed 503, retryable by
            # the client) from "no ready replica hosts this model at
            # all" (an unknown model: honest 404, retrying is pointless)
            with self._lock:
                _tsan.note_access("fleet.router.replicas", write=False)
                ready = [
                    r for r in self._replicas.values()
                    if r.ready and r.state != "draining"
                ]
                unknown_everywhere = bool(model) and bool(ready) and all(
                    model in r.not_models
                    or (r.models is not None and model not in r.models)
                    for r in ready
                )
            if unknown_everywhere and not last_failed:
                return _ModelNotFound(model)
            return NoReplicaError(
                f"no replica can take model {model!r} "
                f"({len(self.replica_urls())} registered)",
                model=model,
                attempts=attempts,
                retry_after_s=self.health_period_s,
            )

        def attempt():
            tried_here: set = set(last_failed)
            queue_shed = None
            while True:
                replica = self._pick(model, exclude=tried_here)
                if replica is None:
                    if queue_shed is not None:
                        # EVERY candidate is at its local queue bound:
                        # the fleet really is full — pass the shed (and
                        # its drain-rate Retry-After) to the client
                        return queue_shed
                    raise no_candidate(len(last_failed) + 1)
                try:
                    status, out, headers = self._forward(replica, method, path, body)
                except ReplicaFailure:
                    last_failed.add(replica.url)
                    _FAILOVERS_C.inc()
                    raise
                if status == 404 and path == "/v1/predict":
                    # this replica cannot take the model; remember and
                    # try the next in preference order without burning a
                    # retry attempt (the replica is healthy)
                    tried_here.add(replica.url)
                    with self._lock:
                        _tsan.note_access("fleet.router.replicas")
                        replica.not_models.add(model)
                    continue
                if status == 429 and path == "/v1/predict":
                    # replica-LOCAL pressure (bounded admission queue)
                    # spills to the next replica — that is exactly what
                    # a fleet is for; a tenant-quota shed is a policy
                    # verdict and passes through untouched
                    try:
                        cause = json.loads(out).get("cause")
                    except ValueError:
                        cause = None
                    if cause == "queue":
                        tried_here.add(replica.url)
                        queue_shed = (status, out, headers)
                        continue
                return status, out, headers

        return policy.call(attempt)

    # -- the HTTP surface ----------------------------------------------
    def handle(self, method: str, path: str, body: Optional[bytes]):
        """Route one request; returns ``(status, body_str, content_type,
        headers)``.  The in-process entry point the HTTP handlers and
        the tests share."""
        bare = path.split("?", 1)[0]
        if bare.startswith("/fleet/") or bare in ("/metrics", "/fleetz", "/tenantz"):
            query = path.split("?", 1)[1] if "?" in path else ""
            params = dict(kv.split("=", 1) for kv in query.split("&") if "=" in kv)
            return self._handle_local(bare, params)
        if not bare.startswith("/v1/"):
            return 404, json.dumps({"error": f"unknown route {bare!r}"}), "application/json", {}
        t0 = time.perf_counter()
        try:
            if method == "POST" and bare == "/v1/predict":
                status, out, headers = self._predict(body)
            else:
                model = ""
                if bare.startswith("/v1/models/"):
                    model = bare[len("/v1/models/"):].split("/", 1)[0]
                status, out, headers = self._route(model, method, bare, body)
        except OverloadedError as e:
            _SHED_C.inc()
            doc = {"error": str(e), "cause": e.cause, "retry_after_s": e.retry_after_s}
            hdrs = {}
            if e.retry_after_s is not None:
                hdrs["Retry-After"] = f"{max(e.retry_after_s, 0.001):.3f}"
            return 429, json.dumps(doc), "application/json", hdrs
        except NoReplicaError as e:
            _NO_REPLICA_C.inc()
            doc = {
                "error": str(e),
                "cause": "no_replica",
                "model": e.model,
                "attempts": e.attempts,
                "retry_after_s": e.retry_after_s,
            }
            hdrs = {"Retry-After": f"{max(e.retry_after_s or 0.001, 0.001):.3f}"}
            return 503, json.dumps(doc), "application/json", hdrs
        except _ModelNotFound as e:
            return 404, json.dumps({"error": f"unknown model {e.model!r}"}), "application/json", {}
        except ReplicaFailure as e:
            # bounded failover exhausted on real failures: the honest
            # verdict is unavailability, typed like the no-replica case
            _NO_REPLICA_C.inc()
            doc = {
                "error": f"all failover attempts failed (last: {e})",
                "cause": "failover_exhausted",
                "retry_after_s": self.health_period_s,
            }
            return 503, json.dumps(doc), "application/json", {
                "Retry-After": f"{max(self.health_period_s, 0.001):.3f}"
            }
        ms = (time.perf_counter() - t0) * 1e3
        _REQS_C.inc()
        _LATENCY_H.observe(ms)
        with self._lock:
            _tsan.note_access("fleet.router.replicas")
            self._latencies.append((time.monotonic(), ms))
        ctype = headers.get("Content-Type", "application/json")
        fwd = {k: v for k, v in headers.items() if k.lower() == "retry-after"}
        return status, out.decode("utf-8", "replace"), ctype, fwd

    def _predict(self, body: Optional[bytes]):
        """The /v1/predict path: global admission, trace-id stamping,
        failover routing."""
        try:
            doc = json.loads(body or b"")
        except ValueError:
            return 400, b'{"error": "request body must be a JSON object"}', {}
        if not isinstance(doc, dict) or "model" not in doc:
            return 400, b'{"error": "predict body needs a \\"model\\" field"}', {}
        model = str(doc["model"])
        inputs = doc.get("inputs")
        rows = len(inputs) if isinstance(inputs, list) and inputs and isinstance(inputs[0], list) else 1
        retry_after = self._bucket.take(max(1, rows))
        if retry_after > 0.0:
            raise OverloadedError(
                f"fleet quota exceeded ({self._bucket.rate:g} rows/s); "
                f"retry in {retry_after:.3f}s",
                cause="quota",
                retry_after_s=retry_after,
            )
        if not doc.get("trace_id"):
            # stamp the routed trace id: the replica adopts it, so the
            # request stitches across processes in /tracez + aggregate
            doc["trace_id"] = _tracing.new_trace_id()
            body = json.dumps(doc).encode("utf-8")
        return self._route(model, "POST", "/v1/predict", body)

    def _handle_local(self, path: str, params: Optional[Dict[str, str]] = None):
        params = params or {}
        if path == "/fleet/healthz":
            n = self._count_ready()
            doc = {"ready_replicas": n, "replicas": len(self.replica_urls())}
            return (200 if n else 503), json.dumps(doc), "application/json", {}
        if path == "/fleet/statusz":
            return 200, json.dumps(self.statusz(), indent=1, default=str), "application/json", {}
        if path == "/fleetz":
            if params.get("format") == "json":
                return 200, json.dumps(self.fleetz_report(), indent=1, default=str), "application/json", {}
            return 200, self.render_fleetz_html(), "text/html", {}
        if path == "/tenantz":
            # the fleet-merged view of every replica's tenant accounts —
            # same route name as the replica surface, so a dashboard
            # pointed at "the service" works against router or replica
            doc = self.fleetz_report()["tenants"]
            if params.get("format") == "json":
                return 200, json.dumps(doc, indent=1, default=str), "application/json", {}
            return 200, self._render_tenants_html(doc), "text/html", {}
        if path == "/metrics":
            from ..telemetry.server import OPENMETRICS_CONTENT_TYPE

            return 200, _tm.expose(), OPENMETRICS_CONTENT_TYPE, {}
        return 404, json.dumps({"error": f"unknown route {path!r}"}), "application/json", {}

    # -- fleet-wide rollup (/fleetz) -------------------------------------
    def fleetz_report(self) -> Dict[str, Any]:
        """The fleet-wide rollup of every polled replica's snapshots:
        per-model canary verdicts (divergent replicas flagged), the
        merged tenant accounts, and the interleaved decision timeline."""
        with self._lock:
            _tsan.note_access("fleet.router.replicas", write=False)
            canary_snaps = {
                r.url: dict(r.canary)
                for r in self._replicas.values()
                if r.canary is not None
            }
            tenant_snaps = {
                r.url: dict(r.tenants)
                for r in self._replicas.values()
                if r.tenants is not None
            }
            journal_snaps = {
                r.url: dict(r.journal)
                for r in self._replicas.values()
                if r.journal is not None
            }
        # fleet-wide canary rollup: each replica runs its own decision
        # plane over its own shadow traffic — a model whose replicas
        # disagree on the canary version or verdict is DIVERGENT, the
        # state an operator must resolve before trusting any promotion
        canary_models: Dict[str, Dict[str, Any]] = {}
        for url in sorted(canary_snaps):
            for name, st in sorted((canary_snaps[url].get("models") or {}).items()):
                e = canary_models.setdefault(
                    name,
                    {"replicas": {}, "verdicts": [], "canary_versions": [],
                     "divergent": False},
                )
                e["replicas"][url] = {
                    "canary_version": st.get("canary_version"),
                    "verdict": st.get("verdict"),
                    "rows": st.get("rows"),
                    "mismatch_pct": st.get("mismatch_pct"),
                    "latency_ratio": st.get("latency_ratio"),
                    "decision": (st.get("decision") or {}).get("action"),
                    "last_trace_id": st.get("last_trace_id"),
                }
                if st.get("verdict") not in e["verdicts"]:
                    e["verdicts"].append(st.get("verdict"))
                if st.get("canary_version") not in e["canary_versions"]:
                    e["canary_versions"].append(st.get("canary_version"))
        for e in canary_models.values():
            e["divergent"] = (
                len(e["verdicts"]) > 1 or len(e["canary_versions"]) > 1
            )
        # fleet-wide per-tenant cost rollup: each replica's /tenantz
        # accounts merged by tenant — totals re-derived from the merged
        # rows, so "accounts sum to the fleet total" survives the merge
        from ..telemetry.aggregate import merge_tenant_accounts

        tenants = merge_tenant_accounts(
            [tenant_snaps[u] for u in sorted(tenant_snaps)]
        )
        # fleet-wide decision timeline: every polled replica's decision
        # journal plus the router's own (breaker trips, probes), merged
        # into one worker-tagged timeline — "what did the fleet decide,
        # in what order" without ssh-ing into N replicas
        decisions = _journal.merge_journal_snapshots(
            [(u, journal_snaps[u]) for u in sorted(journal_snaps)]
            + [("router", _journal.journal_snapshot())]
        )
        return {
            "timestamp": time.time(),
            "ready_replicas": self._count_ready(),
            "canary": dict(sorted(canary_models.items())),
            "tenants": tenants,
            "decisions": decisions,
        }

    def render_fleetz_html(self) -> str:
        """The human form of ``/fleetz``: the fleet canary state, tenant
        accounts and decision timeline."""
        import html as _html

        doc = self.fleetz_report()
        parts = [
            "<html><head><title>/fleetz</title></head><body>",
            "<h1>/fleetz — fleet rollup</h1>",
            f"<p>{doc['ready_replicas']} ready replica(s)</p>",
        ]
        parts.append("<h2>fleet canary state</h2>")
        canary = doc.get("canary") or {}
        if canary:
            parts.append(
                "<table border=1 cellpadding=3><tr><th>model</th>"
                "<th>replica</th><th>canary</th><th>verdict</th>"
                "<th>rows</th><th>mismatch %</th><th>latency x</th>"
                "<th>decision</th></tr>"
            )
            for name, entry in canary.items():
                per = entry["replicas"]
                first = True
                label = _html.escape(name)
                if entry.get("divergent"):
                    label = (
                        f"<b style='color:#b00'>{label} ⟵ divergent "
                        f"({'/'.join(str(v) for v in entry['verdicts'])})</b>"
                    )
                for url in sorted(per):
                    row = per[url]
                    parts.append(
                        "<tr>"
                        + (f"<td rowspan={len(per)}>{label}</td>" if first else "")
                        + f"<td>{_html.escape(url)}</td>"
                        f"<td>v{_html.escape(str(row.get('canary_version')))}</td>"
                        f"<td>{_html.escape(str(row.get('verdict')))}</td>"
                        f"<td>{row.get('rows')}</td>"
                        f"<td>{row.get('mismatch_pct')}</td>"
                        f"<td>{row.get('latency_ratio')}</td>"
                        f"<td>{_html.escape(str(row.get('decision') or '—'))}</td>"
                        "</tr>"
                    )
                    first = False
            parts.append("</table>")
        else:
            parts.append("<p>no canary snapshots collected yet</p>")
        parts.append("<h2>fleet tenant accounts</h2>")
        parts.append(self._tenants_table_html(doc.get("tenants") or {}))
        parts.append("<h2>fleet decision timeline</h2>")
        decisions = (doc.get("decisions") or {}).get("events") or []
        if decisions:
            parts.append(
                "<table border=1 cellpadding=3><tr><th>time</th><th>worker</th>"
                "<th>actor</th><th>action</th><th>model</th><th>sev</th>"
                "<th>message</th></tr>"
            )
            for e in decisions[-32:]:
                parts.append(
                    "<tr>"
                    f"<td>{time.strftime('%H:%M:%S', time.localtime(e.get('ts', 0)))}</td>"
                    f"<td>{_html.escape(str(e.get('worker', '')))}</td>"
                    f"<td>{_html.escape(str(e.get('actor', '')))}</td>"
                    f"<td>{_html.escape(str(e.get('action', '')))}</td>"
                    f"<td>{_html.escape(str(e.get('model') or '—'))}</td>"
                    f"<td>{_html.escape(str(e.get('severity', '')))}</td>"
                    f"<td>{_html.escape(str(e.get('message', '')))}</td>"
                    "</tr>"
                )
            parts.append("</table>")
        else:
            parts.append("<p>no decision events collected yet</p>")
        parts.append(
            "<p><a href='/tenantz'>full /tenantz</a> · "
            "<a href='/fleetz?format=json'>json</a></p></body></html>"
        )
        return "".join(parts)

    @staticmethod
    def _tenants_table_html(doc: Dict[str, Any]) -> str:
        """The merged-tenant-ledger table fragment (/fleetz + /tenantz)."""
        import html as _html

        rows = doc.get("tenants") or []
        if not rows:
            return "<p>no tenant-account snapshots collected yet</p>"
        t = doc.get("total") or {}
        parts = [
            f"<p>{t.get('tenants', 0)} tenants · {t.get('rows', 0)} rows · "
            f"{float(t.get('flops') or 0.0):.3g} FLOPs · "
            f"{float(t.get('device_ms') or 0.0):.1f} device-ms across "
            f"{doc.get('sources', 0)} replica snapshot(s)</p>",
            "<table border=1 cellpadding=3><tr><th>tenant</th><th>class</th>"
            "<th>requests</th><th>rows</th><th>FLOPs</th><th>bytes</th>"
            "<th>device-ms</th><th>replicas</th><th>models</th></tr>",
        ]
        for r in rows:
            parts.append(
                "<tr>"
                f"<td>{_html.escape(str(r['tenant']))}</td>"
                f"<td>{_html.escape(str(r.get('class')))}</td>"
                f"<td align=right>{r['requests']}</td>"
                f"<td align=right>{r['rows']}</td>"
                f"<td align=right>{float(r['flops']):.3g}</td>"
                f"<td align=right>{float(r['bytes_accessed']):.3g}</td>"
                f"<td align=right>{float(r['device_ms']):.1f}</td>"
                f"<td align=right>{r.get('replicas')}</td>"
                f"<td>{_html.escape(', '.join(r.get('models') or []))}</td>"
                "</tr>"
            )
        parts.append("</table>")
        return "".join(parts)

    def _render_tenants_html(self, doc: Dict[str, Any]) -> str:
        """The human form of the router's merged ``/tenantz``."""
        return (
            "<html><head><title>tenantz (fleet)</title></head><body>"
            "<h1>Fleet per-tenant cost accounts</h1>"
            + self._tenants_table_html(doc)
            + "<p><a href='/tenantz?format=json'>json</a> · merged from the "
            "health poller's per-replica /tenantz snapshots</p>"
            "</body></html>"
        )

    # -- introspection / autoscaler signals ----------------------------
    def statusz(self) -> Dict[str, Any]:
        with self._lock:
            _tsan.note_access("fleet.router.replicas", write=False)
            replicas = [r.doc() for r in self._replicas.values()]
        return {
            "url": self.url,
            "replicas": replicas,
            "requests": _REQS_C.value,
            "failovers": _FAILOVERS_C.value,
            "shed": _SHED_C.value,
            "no_replica_503": _NO_REPLICA_C.value,
            "cb_ejections": _CB_OPEN_C.value,
            "cb_readmissions": _CB_CLOSE_C.value,
        }

    def stats(self, window_s: float = 30.0) -> Dict[str, Any]:
        """The autoscaler's signal snapshot: ready count, total
        in-flight, shed counter, and the latency p50/p99 over the
        sliding window."""
        now = time.monotonic()
        with self._lock:
            _tsan.note_access("fleet.router.replicas", write=False)
            ready = [r for r in self._replicas.values() if r.ready]
            inflight = sum(r.inflight for r in ready)
            lat = [ms for (t, ms) in self._latencies if now - t <= window_s]
        lat.sort()
        n = len(lat)
        return {
            "replicas": len(self.replica_urls()),
            "ready": len(ready),
            "inflight": inflight,
            "inflight_per_ready": (inflight / len(ready)) if ready else 0.0,
            "shed": _SHED_C.value,
            "no_replica_503": _NO_REPLICA_C.value,
            "window_requests": n,
            "p50_ms": lat[n // 2] if n else 0.0,
            "p99_ms": lat[min(n - 1, int(n * 0.99))] if n else 0.0,
        }

    # -- shutdown -------------------------------------------------------
    def close(self) -> None:
        """Stop the poller and the front door.  Idempotent."""
        with self._lock:
            _tsan.note_access("fleet.router.replicas")
            if self._closed:
                return
            self._closed = True
        self._stop.set()
        httpd = self._httpd
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        t = self._serve_thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=5)
        p = self._poll_thread
        if p is not None and p is not threading.current_thread():
            p.join(timeout=5)

    def __enter__(self) -> "FleetRouter":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


class _ModelNotFound(Exception):
    """Every candidate replica answered 404 for the model: the honest
    client verdict is 404, not 503 (internal control flow only)."""

    def __init__(self, model: str):
        super().__init__(f"unknown model {model!r}")
        self.model = model


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m heat_tpu.fleet.router`` — a standalone router
    process."""
    import argparse

    ap = argparse.ArgumentParser(description="heat_tpu fleet router")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--replica", action="append", default=[],
                    help="replica base URL (repeatable)")
    args = ap.parse_args(argv)
    router = FleetRouter(replicas=tuple(args.replica), port=args.port, host=args.host)
    print(f"fleet router serving on {router.url}", flush=True)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        pass
    finally:
        router.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
