"""Query-scoped span tracing + persistent query event log.

The NVTX analog [REF: sql-plugin/../GpuMetrics.scala :: NvtxRange /
NvtxWithMetrics; spark-rapids-jni profiler]: every exec's partition pump
and its internal stages (compile, H2D transfer, device compute, D2H
gather, shuffle/collective) open spans on a per-query ``Tracer``.  Spans
nest per thread (the executor pool's task threads each keep their own
stack), accumulate their children's time so self-time vs total-time per
operator is finally attributable — the fix for ``opTime``
double-counting across parent/child iterators.

Durations stay on ``time.perf_counter()``.  Where a jax profiler
session is recording when the query starts (the benchmark's
``jax.profiler.start_trace``, ``spark.rapids.profile.enabled``), every
span is also a ``jax.profiler.TraceAnnotation`` named
``tpuq.<op>:<stage>`` with the ``query_id`` (and ``partition``) as
stats: a host event on the clock the device plane uses, same thread,
same nesting — one timeline, no exporter of its own.

The event log is the reference's driver-log "plan conversion report"
made machine-readable: one JSONL entry per query
(``spark.rapids.sql.queryLog.path``) recording the plan tree, the
device/fallback report from plan/overrides.py, every metric at its
level, the span rollup, and cross-links to the xplane profile dump and
LORE tag when enabled.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from spark_rapids_tpu.runtime import inflight
from spark_rapids_tpu.runtime import telemetry as TM
from spark_rapids_tpu.runtime.inflight import BOOKS

# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


class Span:
    """One timed range on one thread.  ``child_time`` accumulates the
    durations of directly-nested spans (any operator), so
    ``self_time = dur - child_time`` is this span's exclusive time."""

    __slots__ = ("op", "stage", "tid", "t0", "t1", "child_time",
                 "parent_op", "args", "ann")

    def __init__(self, op: str, stage: str, tid: int, t0: float,
                 parent_op: Optional[str], args: Optional[dict]):
        self.op = op
        self.stage = stage
        self.tid = tid
        self.t0 = t0
        self.t1 = t0
        self.child_time = 0.0
        self.parent_op = parent_op
        self.args = args
        # the span's mirror on the profiler's clock (mirroring tracers)
        self.ann = None

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    @property
    def self_time(self) -> float:
        return max(self.dur - self.child_time, 0.0)


def _close_mirror(span: Span) -> None:
    ann = span.ann
    if ann is not None:
        span.ann = None
        ann.__exit__(None, None, None)


class _SpanCtx:
    __slots__ = ("_tracer", "_span")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span

    def __enter__(self):
        return self._span

    def __exit__(self, *exc):
        self._tracer.end(self._span)
        return False


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullCtx()
# span cap a traced query; spans past it are counted as dropped, not
# recorded (bounds tracer memory on pathological plans)
MAX_EVENTS = 100_000


class Tracer:
    """Thread-safe span collector for ONE query execution.

    Every thread keeps its own span stack (``threading.local``), so
    pump iterators nest correctly across the executor thread pool: a
    child operator's ``next()`` runs inside its consumer's span on the
    SAME thread and its duration subtracts from the consumer's
    self-time.  Spans on a pool thread with no enclosing span start a
    fresh top-level track for that thread."""

    def __init__(self, query_id: int, max_events: int = MAX_EVENTS,
                 mirror: bool = False):
        self.query_id = query_id
        self.max_events = max_events
        # spans are also TraceAnnotations (a profiler session records)
        self.mirror = mirror
        self.t_start = time.perf_counter()
        # the same instant on the clock callers stamp requests with
        self.t_start_mono = time.monotonic()
        self.wall_s: Optional[float] = None
        self.dropped = 0
        self.events: List[Span] = []
        # what the query's operators counted on the host (probe groups,
        # slots probed, sorts a rung): the ledger's ``counts``
        self.counts: Dict[str, int] = {}
        # duck-typed flight-recorder hook (runtime/attribution.py):
        # when set, every closed span also lands in the recorder's
        # bounded ring — one extra deque append, no new timers
        self.recorder = None
        self._lock = threading.Lock()
        self._tls = threading.local()

    # -- recording ----------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def begin(self, op: str, stage: str,
              args: Optional[dict] = None) -> Span:
        st = self._stack()
        parent_op = st[-1].op if st else None
        sp = Span(op, stage, threading.get_ident(), time.perf_counter(),
                  parent_op, args)
        if self.mirror:
            stats = {"query_id": self.query_id}
            if args and "partition" in args:
                stats["partition"] = args["partition"]
            sp.ann = TraceAnnotation(f"tpuq.{op}:{stage}", **stats)
            sp.ann.__enter__()
        st.append(sp)
        return sp

    def end(self, span: Span) -> None:
        span.t1 = time.perf_counter()
        st = self._stack()
        # pop back to (and including) this span — tolerate a leaked
        # child that never closed (generator dropped mid-pump)
        while st and st[-1] is not span:
            _close_mirror(st.pop())
        if st:
            st.pop()
        _close_mirror(span)
        if st:
            st[-1].child_time += span.dur
        with self._lock:
            if len(self.events) < self.max_events:
                self.events.append(span)
            else:
                self.dropped += 1
        rec = self.recorder
        if rec is not None:
            rec.record_span(span)

    def span(self, op: str, stage: str, args: Optional[dict] = None):
        """Context manager recording one span."""
        return _SpanCtx(self, self.begin(op, stage, args))

    def finish(self) -> None:
        self.wall_s = time.perf_counter() - self.t_start

    # -- counts -------------------------------------------------------------
    def count(self, name: str, value: int) -> None:
        """Add to the query's count ``name``."""
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + value

    # -- export -------------------------------------------------------------
    def rollup(self) -> Dict[str, Dict[str, Any]]:
        """Per-operator total vs self time derived from the span tree.

        ``total_s`` counts only spans NOT nested inside a span of the
        same operator (a pump span's internal opTime span must not
        double-count); ``self_s`` sums every span's exclusive time, so
        across all operators self times partition the traced wall time
        exactly — the attribution ``opTime`` alone cannot give."""
        out: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            spans = list(self.events)
        for sp in spans:
            r = out.setdefault(sp.op, {
                "total_s": 0.0, "self_s": 0.0, "spans": 0, "stages": {}})
            r["spans"] += 1
            if sp.parent_op != sp.op:
                r["total_s"] += sp.dur
            r["self_s"] += sp.self_time
            st = r["stages"]
            st[sp.stage] = st.get(sp.stage, 0.0) + sp.self_time
        for r in out.values():
            r["total_s"] = round(r["total_s"], 6)
            r["self_s"] = round(r["self_s"], 6)
            r["stages"] = {k: round(v, 6)
                           for k, v in sorted(r["stages"].items())}
        return out


# ---------------------------------------------------------------------------
# The tracer of the query in flight on this thread (runtime/inflight.py)
# ---------------------------------------------------------------------------

# Checked on every pump step: one attribute load on the calling
# thread's slots, whether or not other queries are in flight on other
# threads.  A second query starting on a thread that has an owner (a
# sub-query planned during execution) rides the owner's spans instead
# of replacing the tracer.
_QUERY_IDS = itertools.count(1)


def next_query_id() -> int:
    return next(_QUERY_IDS)


def current() -> Optional[Tracer]:
    return BOOKS.tracer


def start_query(query_id: int) -> Optional[Tracer]:
    """Install a fresh tracer for the calling thread's query, whatever
    runs on other threads; returns None when the thread already has an
    owner (the caller is a nested execution and rides it).  Asks the
    profiler once, here, whether a session is recording: the tracer
    then mirrors its spans for the whole query, and otherwise never
    makes one."""
    tracer = inflight.install(inflight.TRACER, lambda: Tracer(
        query_id, mirror=bool(TraceAnnotation.is_enabled())))
    if tracer is None:
        TM.BOOKS_RIDDEN.inc()
    return tracer


def end_query(tracer: Optional[Tracer]) -> None:
    if tracer is None:
        return
    tracer.finish()
    inflight.remove(inflight.TRACER, tracer)


def span(op: str, stage: str, args: Optional[dict] = None):
    """Span on the calling thread's tracer, or a no-op when tracing is
    off or the thread works for no query — THE hook free-standing
    stages (kernel compile, spill, shuffle serialize) use without
    carrying a tracer reference."""
    tr = BOOKS.tracer
    if tr is None:
        return _NULL
    return tr.span(op, stage, args)


def count(name: str, value: int) -> None:
    """``Tracer.count`` on the calling thread's query; nothing where
    the thread works for no query."""
    tr = BOOKS.tracer
    if tr is not None:
        tr.count(name, value)


# ---------------------------------------------------------------------------
# Query event log
# ---------------------------------------------------------------------------

def plan_metrics(plan) -> List[dict]:
    """Every node's metrics WITH their verbosity levels — the event log
    records all levels; readers filter."""
    out: List[dict] = []

    def walk(node):
        out.append({
            "op": type(node).__name__,
            "metrics": {
                name: {"value": (round(m.value, 6)
                                 if isinstance(m.value, float)
                                 else m.value),
                       "level": m.level}
                for name, m in getattr(node, "metrics", {}).items()},
        })
        for c in node.children:
            walk(c)

    walk(plan)
    return out


_LOG_LOCK = threading.Lock()


def append_query_log(path: str, entry: Dict[str, Any]) -> None:
    """Append one JSONL record; directory auto-created.  Failures are
    swallowed to stderr — observability must never fail the query."""
    import sys
    try:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        line = json.dumps(entry, default=str)
        with _LOG_LOCK:
            with open(path, "a") as f:
                f.write(line + "\n")
    except OSError as e:
        print(f"[tpuq] query log write failed: {e}", file=sys.stderr,
              flush=True)
