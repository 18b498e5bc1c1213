"""Unified resilience layer: failure domains, retry policy, breakers.

[REF: spark-rapids-jni :: src/main/cpp/faultinj/ — the LD_PRELOAD CUDA
 interceptor forcing errors at arbitrary driver entry points;
 sql-plugin :: RmmRapidsRetryIterator.scala — the uniform
 rollback-and-retry contract every device step gets; SURVEY §3.5/§5.3]

The engine's device/IO boundaries are twelve named **failure domains**:

======================  ====================================  ==========
domain                  chokepoint                            degradable
======================  ====================================  ==========
``execute``             kernel dispatch (kernel_cache)        yes: eager
``transfer``            device→host pull (columnar.column)    yes: sync
``alloc``               HBM reservation (runtime.memory)      via OOM retry
``spill_write``         host→disk spill (np.savez)            yes: stay host
``spill_read``          disk→host restore (np.load)           no (data gone)
``shuffle_ser``         tudo serialization (shuffle.manager)  no
``shuffle_exchange``    reduce-side shuffle read              no
``collective``          ICI all-to-all (exec.distributed)     yes: host shuffle
``compile``             jit wrapper build (kernel_cache)      yes: un-jitted
``rendezvous``          coordinator barrier (parallel.        no: epoch retry
                        rendezvous :: allgather)
``peer_loss``           simulated executor death              no: fails slice
``tenancy``             cluster directive apply (runtime.     yes: local-only
                        tenancy :: on_heartbeat)              enforcement
======================  ====================================  ==========

The distributed domains retry differently: ``rendezvous`` faults
re-enter the stage at epoch+1 through ``run_stage_epochs`` (same
policy, same budget), and ``peer_loss`` is always terminal — every
survivor raises the same peer-tagged ``TerminalDeviceError`` within
~one heartbeat lease (see docs/resilience.md, "Distributed failure
domains").  ``tenancy`` degrades softest of all: an injected (or
real) fault in the directive path drops that heartbeat's directives —
suspends are coordinator-renewed leases, so the protocol re-converges
on the next beat, and a sustained outage just means local-only
enforcement (never an error surfaced to a query).

Three cooperating pieces, all conf-driven:

* ``INJECTOR`` — a registry of independently armable fault injectors,
  one per domain (``spark.rapids.tpu.test.inject.<domain>.{at,
  transientCount}``), keeping the original self-disarm/transient-budget
  firing model.  The ``armed`` flag is a plain attribute written only
  under the lock, so the disarmed fast path is one atomic attribute
  read and an ARMED injector is never skipped by a racing pump thread
  (the old per-field fast-path reads could miss a concurrent arm).
* ``RetryPolicy`` — ``retry.maxAttempts`` attempts with exponential
  backoff (``retry.backoffBaseMs``..``retry.backoffMaxMs``) and
  deterministic seeded jitter (``JITTER_SEED``), spending from a
  per-query retry budget (``retry.budgetPerQuery``).
* per-op **circuit breakers** — on retry exhaustion in a degradable
  domain the op's breaker trips and the step re-runs on the host path;
  later calls of the same op inside the query skip straight to the host
  path.  Non-degradable domains raise a domain-tagged
  ``TerminalDeviceError`` instead.  Every degradation is recorded in
  the query event log, emits a health WARN, and counts in
  ``tpuq_host_degraded_ops_total``.
"""

from __future__ import annotations

import random
import threading
import time
import zipfile
from typing import Callable, Dict, List, Optional, Tuple

from spark_rapids_tpu import conf as C
from spark_rapids_tpu.runtime import cancel
from spark_rapids_tpu.runtime import telemetry as TM

DOMAINS: Tuple[str, ...] = C.FAILURE_DOMAINS

# domains whose exhaustion can re-run on the host path (graceful
# degradation); the rest raise a domain-tagged terminal error
DEGRADABLE = frozenset(
    {"execute", "transfer", "spill_write", "collective", "compile"})

# IO-backed domains also retry real filesystem faults, not only
# injected ones (a flaky NFS spill dir, a vanished shuffle file)
_IO_RETRYABLE = (OSError, EOFError, zipfile.BadZipFile)
_IO_DOMAINS = frozenset(
    {"spill_write", "spill_read", "shuffle_ser", "shuffle_exchange"})

_TM_RETRY = TM.REGISTRY.labeled_counter(
    "tpuq_retry_total",
    "retries performed by the unified retry policy, per failure domain")
_TM_INJECTED = TM.REGISTRY.labeled_counter(
    "tpuq_faults_injected_total",
    "fault-injector fires, per failure domain")
_TM_EXHAUSTED = TM.REGISTRY.counter(
    "tpuq_retry_exhausted_total",
    "device/IO steps whose retries were exhausted (incl. terminal "
    "faults, which exhaust immediately)")
_TM_BREAKER = TM.REGISTRY.counter(
    "tpuq_breaker_trips_total",
    "per-op circuit breakers tripped by retry exhaustion")
_TM_DEGRADED = TM.REGISTRY.counter(
    "tpuq_host_degraded_ops_total",
    "op executions served by the host degradation path")


class InjectedDeviceError(RuntimeError):
    """A fault-injected device/IO error (any failure domain)."""

    def __init__(self, where: str, nth: int, transient: bool):
        super().__init__(
            f"injected {where} error at call #{nth} "
            f"({'transient' if transient else 'terminal'})")
        self.where = where
        self.transient = transient

    @property
    def domain(self) -> str:
        return self.where


class TerminalDeviceError(RuntimeError):
    """A failure domain gave up: retries exhausted (or the fault was
    terminal) and no host degradation applied.  Domain-tagged so chaos
    harnesses and operators see WHICH boundary failed — a bare
    ``InjectedDeviceError`` never escapes the engine."""

    def __init__(self, domain: str, cause: BaseException,
                 attempts: int = 1):
        super().__init__(
            f"{domain} failed after {attempts} attempt(s): "
            f"{type(cause).__name__}: {cause}")
        self.domain = domain
        self.cause = cause
        self.attempts = attempts

    @property
    def transient(self) -> bool:
        """True when the underlying fault was transient (retries were
        exhausted on a fault that kept firing)."""
        return bool(getattr(self.cause, "transient", False))

    @property
    def peer(self):
        """The dead executor's pid for ``peer_loss`` failures (from the
        underlying ``RendezvousAborted``); None elsewhere."""
        return getattr(self.cause, "peer", None)


class _DomainState:
    __slots__ = ("at", "budget", "count", "fired")

    def __init__(self, at: int = -1, budget: int = 0):
        self.at = int(at)
        self.budget = int(budget)
        self.count = 0
        self.fired = 0


class FaultInjector:
    """Registry of per-domain injectors (the generalized ``_Injector``).

    Firing model per domain: once its call count reaches the configured
    N it starts firing.  With ``transient budget == 0`` the fire is
    terminal and the domain disarms.  With a budget K > 0, K consecutive
    calls fire transient and then the domain disarms — K = 1 proves
    single-retry recovery; K ≥ the engine's retry attempts models a
    persistent fault.  Disarming on exhaustion means an armed injection
    never leaks into later queries.

    ``armed`` is a plain bool attribute recomputed under the lock on
    every state change; ``on()``'s fast path is a single atomic read, so
    a concurrent pump thread can never observe stale per-domain fields
    and skip an armed injection.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.armed = False
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._config: Optional[tuple] = None
            self._domains: Dict[str, _DomainState] = {
                d: _DomainState() for d in DOMAINS}
            self.armed = False

    def configure(self, domains: Dict[str, Tuple[int, int]]) -> None:
        """Arm from {domain: (at, transient_budget)}; unlisted domains
        disarm.  Call counts restart at zero."""
        with self._lock:
            self._config = tuple(sorted(
                (d, int(at), int(b)) for d, (at, b) in domains.items()))
            self._domains = {d: _DomainState() for d in DOMAINS}
            for d, (at, budget) in domains.items():
                if d not in self._domains:
                    raise ValueError(f"unknown failure domain {d!r}; "
                                     f"expected one of {DOMAINS}")
                self._domains[d] = _DomainState(at, budget)
            self._recompute_armed()

    def configure_legacy(self, exec_at: int, transfer_at: int,
                         transient_count: int) -> None:
        """The original two-chokepoint signature (execute/transfer with
        a shared transient budget)."""
        self.configure({"execute": (exec_at, transient_count),
                        "transfer": (transfer_at, transient_count)})

    def _recompute_armed(self) -> None:
        # callers hold self._lock
        self.armed = any(s.at >= 0 for s in self._domains.values())

    def domain_armed(self, domain: str) -> bool:
        with self._lock:
            return self._domains[domain].at >= 0

    def on(self, domain: str) -> None:
        """The chokepoint: count this call and fire if configured."""
        if not self.armed:
            return
        with self._lock:
            s = self._domains[domain]
            if s.at < 0:
                return
            s.count += 1
            if s.count < s.at:
                return
            transient = s.fired < s.budget
            if transient:
                s.fired += 1
                if s.fired >= s.budget:
                    s.at = -1  # budget spent: later calls pass
            else:
                s.at = -1  # terminal
            self._recompute_armed()
            n = s.count
        _TM_INJECTED.inc(domain)
        raise InjectedDeviceError(domain, n, transient)

    # -- original chokepoint names (compat) -----------------------------
    def on_execute(self) -> None:
        self.on("execute")

    def on_transfer(self) -> None:
        self.on("transfer")


INJECTOR = FaultInjector()


def configure_from_conf(conf) -> None:
    """Arm the injector and refresh the retry policy from a session
    conf.  Injection reconfigures only when the requested config
    CHANGES — a conf with every injection key at its default never
    touches the injector, so concurrent clean sessions (planning,
    explain()) cannot disarm another session's armed injection.  Disarm
    happens via terminal self-disarm or ``INJECTOR.reset()``."""
    configure_policy(conf)
    legacy_ex = int(conf.get(C.INJECT_EXECUTE_AT))
    legacy_tr = int(conf.get(C.INJECT_TRANSFER_AT))
    legacy_tc = int(conf.get(C.INJECT_TRANSIENT_COUNT))
    requested: Dict[str, Tuple[int, int]] = {}
    for d in DOMAINS:
        at = int(conf.get(C.INJECT_DOMAIN_AT[d]))
        budget = int(conf.get(C.INJECT_DOMAIN_TRANSIENT[d]))
        # legacy execute/transfer keys map onto their domains unless the
        # domain key is set explicitly
        if at < 0 and d == "execute" and legacy_ex >= 0:
            at, budget = legacy_ex, legacy_tc
        if at < 0 and d == "transfer" and legacy_tr >= 0:
            at, budget = legacy_tr, legacy_tc
        if at >= 0:
            requested[d] = (at, budget)
    if not requested:
        return
    config_token = tuple(sorted(
        (d, at, b) for d, (at, b) in requested.items()))
    # reconfigure on a CHANGED config, or re-arm an identical config
    # whose fires are fully spent (per-query determinism) — but never
    # while any domain of the current config is still armed, which
    # would reset another in-flight query's injection pattern
    if INJECTOR._config != config_token or not INJECTOR.armed:
        INJECTOR.configure(requested)


# ---------------------------------------------------------------------------
# retry policy + per-query state (budget, breakers, degradations)
# ---------------------------------------------------------------------------

class _QueryState:
    """Per-query resilience scope shared by all pump threads: the retry
    budget, tripped breakers, and degradation records.  Reset on
    ``begin_query``; read out by ``finish_query`` into the event log."""

    def __init__(self):
        self.lock = threading.Lock()
        self.query_id: Optional[int] = None
        self.depth = 0  # nested executions share the outer scope
        self.retries_used = 0
        self.breakers: set = set()
        self.degraded_ops: List[dict] = []
        self.retries_by_domain: Dict[str, int] = {}
        self.exhausted = 0


_STATE = _QueryState()


# backoff jitter is a pure function of (seed, domain, attempt): a run
# replays exactly
JITTER_SEED = 0


class RetryPolicy:
    """Conf-driven retry contract every failure domain shares."""

    def __init__(self, max_attempts: int = 8,
                 backoff_base_ms: float = 5.0,
                 backoff_max_ms: float = 1000.0,
                 budget_per_query: int = 64,
                 host_degrade: bool = True):
        self.max_attempts = max(1, int(max_attempts))
        self.backoff_base_ms = float(backoff_base_ms)
        self.backoff_max_ms = float(backoff_max_ms)
        self.budget_per_query = int(budget_per_query)
        self.host_degrade = bool(host_degrade)

    def _token(self) -> tuple:
        return (self.max_attempts, self.backoff_base_ms,
                self.backoff_max_ms, self.budget_per_query,
                self.host_degrade)

    def backoff_s(self, domain: str, attempt: int) -> float:
        """Exponential backoff with deterministic seeded jitter: a pure
        function of (seed, domain, attempt) so chaos runs replay
        exactly."""
        if self.backoff_base_ms <= 0:
            return 0.0
        base = min(self.backoff_base_ms * (2 ** (attempt - 1)),
                   self.backoff_max_ms)
        rnd = random.Random(f"{JITTER_SEED}:{domain}:{attempt}")
        return base * (0.5 + 0.5 * rnd.random()) / 1000.0

    def _retryable(self, domain: str, exc: BaseException) -> bool:
        if isinstance(exc, InjectedDeviceError):
            return True
        if domain in _IO_DOMAINS and isinstance(exc, _IO_RETRYABLE):
            return True
        # a corrupt .npz payload surfaces from np.load as ValueError —
        # still a spill-tier IO fault, still domain-tagged on exhaustion
        if domain == "spill_read" and isinstance(exc, ValueError):
            return True
        # only the abort/timeout family of rendezvous failures retries
        # (epoch re-entry); protocol errors and dead peers never do
        if (domain == "rendezvous"
                and getattr(exc, "rendezvous_retryable", False)):
            return True
        return False

    def _budget_left(self) -> bool:
        if self.budget_per_query <= 0 or _STATE.depth == 0:
            return True  # budget is a per-query notion
        with _STATE.lock:
            return _STATE.retries_used < self.budget_per_query

    def run(self, domain: str, fn: Callable, *,
            op: Optional[str] = None,
            degrade: Optional[Callable] = None):
        """Run one device/IO step under the policy.

        ``fn`` performs the step (firing the domain's injection
        chokepoint itself, so retries re-arm against the injector).
        ``degrade``, when given and enabled, is the host path taken on
        retry exhaustion — its success is recorded as a degraded op.
        Without a degrade path, exhaustion raises a domain-tagged
        ``TerminalDeviceError``."""
        op_key = (domain, op or domain)
        if degrade is not None and breaker_open(op_key):
            _TM_DEGRADED.inc()
            return degrade()
        attempt = 0
        while True:
            attempt += 1
            cancel.check()
            try:
                return fn()
            except BaseException as e:
                if not self._retryable(domain, e):
                    raise
                transient = bool(getattr(e, "transient", True))
                if (transient and attempt < self.max_attempts
                        and self._budget_left()):
                    note_retry(domain)
                    delay = self.backoff_s(domain, attempt)
                    if delay > 0:
                        cancel.sleep(delay)
                    continue
                note_exhausted()
                if degrade is not None and self.host_degrade:
                    _trip_breaker(op_key, domain, op, e)
                    _TM_DEGRADED.inc()
                    return degrade()
                raise TerminalDeviceError(domain, e, attempt) from e


_policy = RetryPolicy()
_policy_lock = threading.Lock()


def get_policy() -> RetryPolicy:
    return _policy


def configure_policy(conf) -> RetryPolicy:
    """Refresh the process policy from a session conf (same
    last-writer-wins model as the memory manager)."""
    global _policy
    cfg = RetryPolicy(
        max_attempts=conf.get(C.RETRY_MAX),
        backoff_base_ms=conf.get(C.RETRY_BACKOFF_BASE_MS),
        backoff_max_ms=conf.get(C.RETRY_BACKOFF_MAX_MS),
        budget_per_query=conf.get(C.RETRY_BUDGET_PER_QUERY),
        host_degrade=conf.get(C.RETRY_HOST_DEGRADE),
    )
    with _policy_lock:
        if cfg._token() != _policy._token():
            _policy = cfg
    return _policy


def active() -> bool:
    """Cheap hot-path check: anything armed or any breaker open?  The
    disarmed/closed case is two attribute reads — kernel dispatch and
    D2H wrap themselves in the policy only when this is True."""
    return INJECTOR.armed or bool(_STATE.breakers)


def note_retry(domain: str) -> None:
    """Count one retry against the labeled counter and the per-query
    budget.  Also the hook ``with_retry`` (alloc/OOM rollback) calls so
    every retry in the engine lands in one place."""
    _TM_RETRY.inc(domain)
    # flight recorder: a retry burst right before a timeout is exactly
    # the evidence the black box exists to preserve
    from spark_rapids_tpu.runtime import attribution
    attribution.record_event("retry", {"domain": domain})
    with _STATE.lock:
        _STATE.retries_used += 1
        _STATE.retries_by_domain[domain] = (
            _STATE.retries_by_domain.get(domain, 0) + 1)


def note_exhausted() -> None:
    _TM_EXHAUSTED.inc()
    with _STATE.lock:
        _STATE.exhausted += 1


def breaker_open(op_key: tuple) -> bool:
    with _STATE.lock:
        return op_key in _STATE.breakers


def _trip_breaker(op_key: tuple, domain: str, op: Optional[str],
                  cause: BaseException) -> None:
    rec = {"domain": domain, "op": op or domain,
           "cause": f"{type(cause).__name__}: {cause}"}
    with _STATE.lock:
        fresh = op_key not in _STATE.breakers
        if fresh:
            _STATE.breakers.add(op_key)
        _STATE.degraded_ops.append(rec)
        qid = _STATE.query_id
    if fresh:
        _TM_BREAKER.inc()
    TM.REGISTRY.record_health({
        "severity": "WARN", "check": "host_degraded", "value": 1,
        "threshold": 0, "query_id": qid,
        "detail": (f"{domain} op {rec['op']!r} degraded to the host "
                   f"path after retry exhaustion ({rec['cause']})")})


def run_guarded(domain: str, fn: Callable, *, op: Optional[str] = None,
                degrade: Optional[Callable] = None):
    """Module-level convenience: ``get_policy().run(...)``."""
    return get_policy().run(domain, fn, op=op, degrade=degrade)


def begin_query(query_id: int) -> Optional[_QueryState]:
    """Open (or join) the query's resilience scope.  Nested executions
    (a sub-query pumped during an outer query) share the outer scope;
    only the outermost begin resets budget/breakers/records."""
    with _STATE.lock:
        _STATE.depth += 1
        if _STATE.depth > 1:
            return None  # joined an existing scope
        _STATE.query_id = query_id
        _STATE.retries_used = 0
        _STATE.breakers = set()
        _STATE.degraded_ops = []
        _STATE.retries_by_domain = {}
        _STATE.exhausted = 0
    return _STATE


def finish_query(scope: Optional[_QueryState]) -> Optional[dict]:
    """Close the scope opened by ``begin_query``; the outermost close
    returns the query's resilience record for the event log (None when
    nothing happened)."""
    with _STATE.lock:
        _STATE.depth = max(0, _STATE.depth - 1)
        if scope is None or _STATE.depth > 0:
            return None
        out = {
            "retries": dict(_STATE.retries_by_domain),
            "retries_total": _STATE.retries_used,
            "retry_exhausted": _STATE.exhausted,
            "breaker_trips": len(_STATE.breakers),
            "degraded_ops": list(_STATE.degraded_ops),
        }
        _STATE.query_id = None
    if not (out["retries"] or out["retry_exhausted"]
            or out["degraded_ops"]):
        return None
    return out


def counters_snapshot() -> dict:
    """Process-cumulative resilience counters (bench reporting)."""
    return {
        "retries": _TM_RETRY.child_values(),
        "faults_injected": _TM_INJECTED.child_values(),
        "retry_exhausted": _TM_EXHAUSTED.value,
        "breaker_trips": _TM_BREAKER.value,
        "host_degraded_ops": _TM_DEGRADED.value,
    }


def retry_device_call(fn, *args, max_attempts: Optional[int] = None,
                      **kw):
    """Back-compat wrapper for the original faultinj API: run a device
    call retrying transient injected faults, attempts governed by the
    conf-driven policy (``spark.rapids.tpu.retry.maxAttempts``) instead
    of the old hardcoded 2."""
    attempts = max_attempts or get_policy().max_attempts
    attempt = 0
    while True:
        attempt += 1
        try:
            return fn(*args, **kw)
        except InjectedDeviceError as e:
            if not e.transient or attempt >= attempts:
                raise
            note_retry(e.domain)
