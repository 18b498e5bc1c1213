"""QueryServer — the multi-tenant serving front door over a session.

``TpuSession`` executes one query per caller thread; the server turns
that into a *service*: many concurrent ``submit`` calls across named
tenants, each admission-checked and fairness-scheduled by
``runtime/scheduler.py`` before it may touch the device.  The flow per
submission:

1. ``submit`` mints the query id and its ``CancelToken`` (deadline
   ticking from SUBMIT time — queue time counts against it) and
   registers the token, so ``session.cancel(qid)`` and per-tenant
   ``active_queries`` work while the query is still QUEUED.
2. The scheduler admits (or raises ``QueryRejected(reason=...)`` —
   quota breach or load shed; nothing was started, retry/back off).
3. A worker thread blocks in ``scheduler.acquire`` until the fairness
   dispatcher grants a run slot, then runs ``DataFrame.toArrow`` which
   adopts the server's query id and token.
4. ``poll``/``result`` observe completion; ``release`` in the worker's
   ``finally`` hands the slot to the next waiter no matter how the
   query ended.

See docs/serving.md for the admission-state walkthrough and tuning
guide.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Union

from spark_rapids_tpu.runtime.scheduler import (  # re-exported API
    QueryRejected, get_scheduler, peek_scheduler)

#: handle states reported by ``poll``
QUEUED = "QUEUED"
RUNNING = "RUNNING"
OK = "OK"
CANCELLED = "CANCELLED"
ERROR = "ERROR"


class QueryHandle:
    """One submission's future.  ``done`` is set exactly once, after
    the run slot has been released and the token unregistered — a
    ``result()`` returner can immediately submit a follow-up without
    racing the slot it just freed."""

    __slots__ = ("query_id", "tenant", "priority", "token", "ticket",
                 "done", "result", "error", "state", "submitted_at",
                 "queue_wait_s", "wall_s")

    def __init__(self, query_id: int, tenant: str, priority: int,
                 token, ticket):
        self.query_id = query_id
        self.tenant = tenant
        self.priority = priority
        self.token = token
        self.ticket = ticket
        self.done = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.state = QUEUED
        self.submitted_at = time.monotonic()
        self.queue_wait_s: Optional[float] = None
        self.wall_s: Optional[float] = None


class QueryServer:
    """Accepts concurrent query submissions for one ``TpuSession``.

    A submission is either a ``DataFrame`` or a zero-arg callable
    returning one.  Prefer the callable for concurrent load: it is
    invoked on the admitted worker thread, so plan construction happens
    per-execution and per-DataFrame caches (``_last_plan`` etc.) are
    not raced by overlapping runs of the SAME DataFrame object.

    ``warmup_plans`` (DataFrames, or callables taking the session)
    name the shapes the server expects to serve; when
    ``spark.rapids.tpu.kernel.warmupOnStart`` is on (default) they run
    through ``session.warmup`` at construction — so the op x bucket
    matrix compiles BEFORE the first tenant submission, outside any
    query's telemetry window, and (with kernel.cacheDir set) the
    executables persist for the next server process.
    """

    def __init__(self, session, warmup_plans=None, scheduler=None):
        from spark_rapids_tpu import conf as C
        self.session = session
        # an explicit scheduler pins this server to it (the cluster
        # tenancy soak hosts several executors in one process, each
        # with its own non-singleton scheduler); None = the process
        # singleton, as before
        self._scheduler = scheduler
        self._lock = threading.Lock()
        self._handles: Dict[int, QueryHandle] = {}
        self._threads: List[threading.Thread] = []
        self._closed = False
        self.warmup_report: Optional[dict] = None
        conf = session.rapids_conf()
        if warmup_plans and conf.get(C.KERNEL_WARMUP_ON_START):
            self.warmup_report = session.warmup(warmup_plans)

    # -- submission --------------------------------------------------------

    def submit(self, query: Union[Callable, object],
               tenant: str = "default", priority: int = 0,
               timeout_ms: Optional[float] = None) -> QueryHandle:
        """Admit one query for ``tenant``.  Returns a ``QueryHandle``
        immediately (the query is queued or already running) or raises
        ``QueryRejected(reason=...)`` without side effects.  Higher
        ``priority`` drains first within the tenant; ``timeout_ms``
        deadlines the query from NOW — time spent queued counts, so a
        deadline can expire a query that was never admitted.

        An out-of-range ``priority`` is rejected here with
        ``QueryRejected(reason='bad_priority')`` — at the door, before
        any token is minted or scheduler state touched."""
        from spark_rapids_tpu import conf as C
        from spark_rapids_tpu.runtime import cancel
        from spark_rapids_tpu.runtime import scheduler as sched_mod
        from spark_rapids_tpu.runtime import trace
        with self._lock:
            if self._closed:
                raise QueryRejected("server_shutdown", tenant=tenant,
                                    detail="QueryServer.shutdown() ran")
        priority = sched_mod.check_priority(priority, tenant)
        conf = self.session.rapids_conf()
        qid = trace.next_query_id()
        eff = (timeout_ms if timeout_ms is not None
               else float(conf.get(C.QUERY_TIMEOUT_MS)))
        if eff is not None and eff <= 0:
            eff = None
        token = cancel.CancelToken(
            qid, timeout_ms=eff,
            poll_ms=float(conf.get(C.CANCEL_POLL_MS)))
        token.tenant = tenant   # HBM arbiter charges this tenant
        cancel.register(token)
        # result-cache admission check: a DataFrame submission whose
        # result key is already resident is served on THIS thread —
        # it never enters the scheduler, holds no run slot, and
        # touches no device state.  (Callable submissions build their
        # plan on the worker, so their cache probe happens inside
        # toArrow instead — a hit still releases the run slot in
        # microseconds.)
        if not callable(query):
            hit = self._try_serve_cached(query, qid, token, tenant,
                                         priority, conf)
            if hit is not None:
                return hit
        sched = (self._scheduler if self._scheduler is not None
                 else get_scheduler(conf))
        try:
            ticket = sched.submit(qid, tenant=tenant, priority=priority,
                                  token=token)
        except BaseException:
            cancel.unregister(token)
            raise
        handle = QueryHandle(qid, tenant, priority, token, ticket)
        with self._lock:
            self._handles[qid] = handle
        worker = threading.Thread(target=self._run, args=(handle, query),
                                  name=f"tpuq-serve-{qid}", daemon=True)
        with self._lock:
            self._threads.append(worker)
            self._threads = [t for t in self._threads if t.is_alive()
                             or t is worker]
        worker.start()
        return handle

    def _try_serve_cached(self, df, qid: int, token, tenant: str,
                          priority: int, conf) -> Optional[QueryHandle]:
        """Serve a submission from the result cache without admission.

        Probes non-destructively (``peek``); on a resident key, runs
        ``toArrow`` synchronously — the probe guarantees it resolves as
        a hit short of a racing eviction, in which case the query
        computes here without a run slot but still under the device
        semaphore.  Returns None on miss (normal admission proceeds).
        """
        from spark_rapids_tpu import cache as cache_mod
        from spark_rapids_tpu import conf as C
        from spark_rapids_tpu.runtime import cancel
        if not conf.get(C.CACHE_ENABLED):
            return None
        store = cache_mod.get_cache(conf)
        try:
            plan = df._execute_plan()
            ckey = cache_mod.result_key(df._plan, plan, conf,
                                        tenant=tenant)
        except Exception:
            return None
        if store.peek(ckey.key) is None:
            return None
        handle = QueryHandle(qid, tenant, priority, token, ticket=None)
        try:
            handle.state = RUNNING
            handle.result = df.toArrow(query_id=qid, cancel_token=token,
                                       tenant=tenant)
            handle.state = OK
        except cancel.QueryCancelled as e:
            handle.error = e
            handle.state = CANCELLED
        except BaseException as e:
            handle.error = e
            handle.state = ERROR
        finally:
            handle.queue_wait_s = 0.0
            handle.wall_s = time.monotonic() - handle.submitted_at
            cancel.unregister(token)
            handle.done.set()
        return handle

    def _run(self, handle: QueryHandle, query) -> None:
        from spark_rapids_tpu.runtime import attribution
        from spark_rapids_tpu.runtime import cancel
        from spark_rapids_tpu.runtime import trace
        from spark_rapids_tpu.sql.dataframe import open_books
        sched = (self._scheduler if self._scheduler is not None
                 else peek_scheduler())
        t0 = time.monotonic()
        # the query's books open here, on the thread it will run on and
        # before it has a run slot: the wait for the slot is in them,
        # and toArrow adopts them as it adopts the id and the token
        tracer, arec = open_books(self.session.rapids_conf(),
                                  handle.query_id)
        serve = None
        df = None
        try:
            with trace.span("QueryServer", "queueWait"):
                handle.queue_wait_s = sched.acquire(handle.ticket)
            if tracer is not None:
                serve = tracer.begin("QueryServer", "serve")
            handle.state = RUNNING
            if callable(query):
                # the plan is built here, on the admitted worker: the
                # first of the query's plan time
                with trace.span("QueryServer", "buildPlan"):
                    df = query()
            else:
                df = query
            handle.result = df.toArrow(query_id=handle.query_id,
                                       cancel_token=handle.token,
                                       tenant=handle.tenant,
                                       books=(tracer, arec))
            handle.state = OK
        except cancel.QueryCancelled as e:
            handle.error = e
            if handle.state == QUEUED:
                # died while still queued for a run slot — toArrow
                # never ran, so the dataframe-side black-box hook never
                # fired; leave a queue-side box where the entire wall
                # is queue wait
                self._dump_queued_blackbox(handle, e, t0)
            handle.state = CANCELLED
        except BaseException as e:
            handle.error = e
            handle.state = ERROR
        finally:
            handle.wall_s = time.monotonic() - t0
            sched.release(handle.ticket)
            cancel.unregister(handle.token)
            with self._lock:
                self._handles.pop(handle.query_id, None)
            if handle.state == OK:
                self._record_latency(sched, handle, df)
            if serve is not None:
                tracer.end(serve)
            if tracer is not None and trace.current() is tracer:
                # toArrow, which closes the books it adopts, never ran
                # (cancelled while queued, a plan that did not build)
                trace.end_query(tracer)
                attribution.end_query(arec)
            handle.done.set()

    def _record_latency(self, sched, handle: QueryHandle, df) -> None:
        """Feed a completed query's submit-to-done wall into the
        tenant's SLO estimator; on the un-breached -> breached
        transition the scheduler returns a breach record and the
        server leaves an ``slo``-triggered black box naming the
        offending dominant bucket."""
        entry = getattr(df, "_last_query_entry", None) or {}
        att = entry.get("attribution") or {}
        try:
            breach = sched.record_latency(
                handle.tenant, handle.wall_s,
                buckets=att.get("buckets"),
                query_id=handle.query_id)
        except Exception:
            return
        if not breach:
            return
        from spark_rapids_tpu import conf as C
        from spark_rapids_tpu.runtime import attribution
        conf = self.session.rapids_conf()
        if not conf.get(C.ATTRIBUTION_ENABLED):
            return
        bb_dir = str(conf.get(C.ATTRIBUTION_BLACKBOX_PATH))
        if not bb_dir:
            return
        attribution.dump_blackbox(
            bb_dir, handle.query_id, "slo",
            attribution=att or None,
            extra={"status": "ok", "tenant": handle.tenant,
                   "slo_breach": breach})

    def _dump_queued_blackbox(self, handle: QueryHandle, exc,
                              t0: float) -> None:
        """Black box for a query killed before admission (deadline or
        cancel fired while QUEUED): ``toArrow`` never ran and closed
        no ledger, so one is built from the one fact the server owns —
        the whole wall was queue wait."""
        from spark_rapids_tpu import conf as C
        from spark_rapids_tpu.runtime import attribution
        conf = self.session.rapids_conf()
        if not conf.get(C.ATTRIBUTION_ENABLED):
            return
        bb_dir = str(conf.get(C.ATTRIBUTION_BLACKBOX_PATH))
        if not bb_dir:
            return
        waited = time.monotonic() - t0
        att = attribution.attribute(
            spans=(), e2e_s=0.0, extras={"queue_wait": waited})
        trigger = ("timeout" if getattr(exc, "reason", "") == "deadline"
                   else "cancel")
        attribution.dump_blackbox(
            bb_dir, handle.query_id, trigger, attribution=att,
            extra={"status": "cancelled", "tenant": handle.tenant,
                   "cancel": {"reason": getattr(exc, "reason", "user"),
                              "while": "QUEUED"}})

    # -- observation -------------------------------------------------------

    def poll(self, handle: QueryHandle) -> dict:
        """Non-blocking status snapshot."""
        return {"query_id": handle.query_id,
                "tenant": handle.tenant,
                "state": handle.state,
                "done": handle.done.is_set(),
                "queue_wait_s": handle.queue_wait_s,
                "wall_s": handle.wall_s}

    def result(self, handle: QueryHandle,
               timeout_s: Optional[float] = None):
        """Block until the query finishes and return its Arrow table;
        re-raises the query's ``QueryCancelled``/error.  ``timeout_s``
        bounds the wait (``TimeoutError``) without affecting the query
        itself."""
        if not handle.done.wait(timeout=timeout_s):
            raise TimeoutError(
                f"query {handle.query_id} still {handle.state} after "
                f"{timeout_s}s")
        if handle.error is not None:
            raise handle.error
        return handle.result

    def cancel(self, query_id: int, reason: str = "user") -> bool:
        """Cancel a submitted query — queued or running.  A queued
        query surfaces ``QueryCancelled`` within ~one poll interval
        WITHOUT ever being admitted; its queue entry is removed and the
        dispatcher moves on."""
        from spark_rapids_tpu.runtime import cancel
        return cancel.cancel_query(query_id, reason=reason)

    def active_queries(self, tenant: Optional[str] = None) -> List[int]:
        """Queued + running query ids, optionally one tenant's."""
        sched = (self._scheduler if self._scheduler is not None
                 else peek_scheduler())
        if sched is None:
            return []
        return sched.active_queries(tenant)

    def stats(self) -> Dict[str, dict]:
        """Per-tenant scheduler accounting (see
        ``QueryScheduler.stats``)."""
        sched = (self._scheduler if self._scheduler is not None
                 else peek_scheduler())
        return sched.stats() if sched is not None else {}

    # -- lifecycle ---------------------------------------------------------

    def shutdown(self, timeout_s: float = 30.0,
                 cancel_pending: bool = True) -> None:
        """Stop accepting submissions; optionally cancel everything
        outstanding; join workers.  Idempotent."""
        with self._lock:
            self._closed = True
            handles = list(self._handles.values())
            threads = list(self._threads)
            self._threads = []
        if cancel_pending:
            from spark_rapids_tpu.runtime import cancel
            for h in handles:
                cancel.cancel_query(h.query_id, reason="user",
                                    detail="server shutdown")
        deadline = time.monotonic() + timeout_s
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
