"""Per-query wall-clock attribution ledger + flight recorder.

The reference accelerator attributes query wall time through per-op
metrics surfaced in the Spark SQL UI [REF: GpuMetrics.scala; the
qualification/profiling tool's per-stage breakdown]; this engine has
spans (runtime/trace.py), counter deltas (runtime/telemetry.py), and
op stats (runtime/stats.py) — this module is the layer that folds them
into ONE exclusive decomposition that closes against end-to-end wall
time, and that survives a timeout/cancel with evidence.

Three pieces:

* **Ledger** (``attribute``): project every trace span of the query
  onto the single wall-clock timeline and charge each instant to
  exactly one declared bucket (``BUCKETS``).  Overlaps across pump
  threads resolve by specificity (``BUCKET_PRIORITY`` — a semaphore
  wait inside a pump task is a wait, not pump time), so the buckets
  are exclusive by construction, sum to <= e2e, and the gap is
  reported explicitly as ``unaccounted`` — never silently absorbed.
  ``closed`` is the <= ``CLOSE_TOLERANCE`` verdict on that gap.

* **Flight recorder** (``FlightRecorder``): a bounded ring of the
  query's most recent spans plus health/retry/cancel events, fed from
  the tracer's span-close path and ``record_event`` — cheap deque
  appends, no new timers.  On a bad exit (timeout, cancel, error,
  health WARN) the ring + ledger dump atomically to
  ``query-<id>.blackbox.json`` (tmp + rename, bounded dir with
  oldest-first eviction), so a query killed at the deadline still
  names its dominant bucket.

* **Verdict engine** (``verdict_line``): one ranked diagnosis line —
  "exchange-bound: 71% of 23.3 s in exchange_collective" — attached to
  the event-log entry, the stats profile, the black box, and rendered
  by ``profile why``.
"""

from __future__ import annotations

import json
import os
import time
import uuid
from collections import deque
from typing import Any, Dict, Iterable, List, Optional, Tuple

from spark_rapids_tpu.runtime import inflight
from spark_rapids_tpu.runtime import telemetry as TM
from spark_rapids_tpu.runtime.inflight import BOOKS

# ---------------------------------------------------------------------------
# The bucket catalog — the declared registry the ledger, the
# bucket-accounting lint rule, and the docs drift gate all read.
# ---------------------------------------------------------------------------

BUCKETS: Dict[str, str] = {
    "queue_wait": "time queued for a QueryServer run slot before "
                  "execution started (server-submitted queries only)",
    "semaphore_wait": "time blocked in the device admission semaphore "
                      "(concurrentGpuTasks) or the pre-materialize hold",
    "compile": "XLA kernel / fused-region / exchange-program compiles "
               "detected on this query's clock",
    "plan": "logical optimization, physical planning and the device "
            "overrides, before the first batch is pumped",
    "kernel_launch": "host time inside cached-kernel calls: handing "
                     "one compiled program to the runtime",
    "scan_h2d": "a device scan filling the device: reading files and "
                "the H2D copy of a scan-cache miss",
    "result_d2h": "the result leaving the device: D2H at a device->"
                  "host boundary (where a device-bound query's host "
                  "waits) and the root's Arrow conversion and concat",
    "kernel_dispatch": "exec code around the launches: operator "
                       "bodies, mid-plan transfers, gather/broadcast/"
                       "concat and other device-batch work",
    "exchange_collective": "ICI exchange collectives (the compiled "
                           "exchange's device launches)",
    "host_shuffle": "host-side shuffle partition/serialize/read/write",
    "spill_io": "device->host->disk spill writes and restore reads",
    "preempted": "time parked in the SUSPENDED state after the "
                 "scheduler preempted the query (permits released, "
                 "residency spilled) — never lands in unaccounted",
    "cache": "result-cache probe and store (serve on hit, put on miss)",
    "pump_idle": "partition-pump machinery between instrumented "
                 "stages: iterator plumbing, batch handoff, "
                 "arrow conversion at the root boundary",
    "host_fallback": "CPU-fallback operator pumps, python UDFs, and "
                     "host-side scans",
    "unaccounted": "e2e wall minus everything above — genuinely "
                   "uninstrumented time, reported, never absorbed",
}

# Verdict label per dominant bucket ("<label>: NN% of S s in <bucket>").
BUCKET_VERDICTS: Dict[str, str] = {
    "queue_wait": "queue-bound",
    "semaphore_wait": "admission-bound",
    "compile": "compile-bound",
    "plan": "plan-bound",
    "kernel_launch": "launch-bound",
    "scan_h2d": "scan-bound",
    "result_d2h": "result-bound",
    "kernel_dispatch": "kernel-bound",
    "exchange_collective": "exchange-bound",
    "host_shuffle": "shuffle-bound",
    "spill_io": "spill-bound",
    "preempted": "preempt-bound",
    "cache": "cache-bound",
    "pump_idle": "pump-bound",
    "host_fallback": "fallback-bound",
    "unaccounted": "uninstrumented",
}

# Every MetricTimer stage name / pump-stage label in runtime/ + exec/
# must map here (or carry ``# attribution-exempt: <why>``) — enforced
# by the ``bucket-accounting`` lint rule.  "pump" resolves per op at
# fold time: a Cpu* operator's pump is host-fallback, not pump_idle.
STAGE_BUCKETS: Dict[str, Optional[str]] = {
    "pump": "pump_idle",            # Cpu* ops -> host_fallback
    "pumpTask": "pump_idle",
    "poolWait": "pump_idle",        # the caller waiting for the pump pool
    "optimize": "plan",
    "physicalPlan": "plan",
    "overrides": "plan",
    "buildPlan": "plan",            # QueryServer: the submitted callable
    "kernelLaunch": "kernel_launch",
    "opTime": "kernel_dispatch",
    "transferTime": "kernel_dispatch",  # DeviceToHostExec -> result_d2h
    "h2dTime": "scan_h2d",
    "scanTime": "scan_h2d",         # Cpu* ops -> host_fallback
    "resultD2H": "result_d2h",
    "resultConcat": "result_d2h",
    "concatTime": "kernel_dispatch",
    "gatherTime": "kernel_dispatch",
    "broadcastTime": "kernel_dispatch",
    "partialTime": "kernel_dispatch",
    "mergeTime": "kernel_dispatch",
    "repartitionTime": "kernel_dispatch",
    "measureTime": "kernel_dispatch",
    "decideTime": "kernel_dispatch",
    "compile": "compile",
    "collectiveTime": "exchange_collective",
    "partitionTime": "host_shuffle",
    "writeTime": "host_shuffle",
    "readTime": "host_shuffle",
    "udfTime": "host_fallback",
    "spillTime": "spill_io",
    "restoreTime": "spill_io",
    "semaphoreWait": "semaphore_wait",
    "semaphoreWaitTime": "semaphore_wait",
    "preemptWait": "preempted",
    "cacheProbe": "cache",
    "cacheServe": "cache",
    "queueWait": "queue_wait",
    # the query-root span: deliberately NOT charged to any bucket —
    # charging it would absorb every uninstrumented gap and make the
    # closure check vacuous
    "execute": None,
    # the served envelope, run slot granted to handle.done: the same
    "serve": None,
    # the epilogue runs after the wall the ledger closes on: its span
    # is timed for ``record_s`` (and mirrored), never charged
    "record": None,
}

# Specificity order for overlap resolution, most specific first: an
# instant covered by several threads' spans charges to the
# highest-priority active bucket.  Waits and one-shot I/O stages beat
# compute; a launch call (the innermost span there is) beats the
# stage it is made from; compute beats the pump envelope.
BUCKET_PRIORITY: Tuple[str, ...] = (
    "compile", "preempted", "semaphore_wait", "spill_io",
    "exchange_collective", "host_shuffle", "cache", "host_fallback",
    "kernel_launch", "scan_h2d", "result_d2h", "plan",
    "kernel_dispatch", "queue_wait", "pump_idle",
)

# closure slack floor: on sub-100ms queries fixed per-query overheads
# (plan metric finalize, log append) dominate any percentage
ABS_CLOSE_SLACK_S = 0.010
# fraction of the wall the unaccounted remainder may reach before the
# ledger is reported NOT CLOSED (the gap is reported either way)
CLOSE_TOLERANCE = 0.10
# flight-recorder ring: the last N closed spans and the last N
# health/retry/cancel events a query, shipped in the black box
RING_SIZE = 256
# black-box files kept in blackboxPath; the oldest are evicted first
BLACKBOX_MAX_DUMPS = 64

_TM_UNACCOUNTED = TM.REGISTRY.counter(
    "tpuq_attribution_unaccounted_seconds_total",
    "per-query wall seconds the attribution ledger could not charge "
    "to any instrumented bucket (the explicit 'unaccounted' gap)")
_TM_DUMPS = TM.REGISTRY.labeled_counter(
    "tpuq_blackbox_dumps_total",
    "flight-recorder black boxes dumped, per trigger "
    "(timeout|cancel|error|health)")


def span_bucket(op: str, stage: str) -> Optional[str]:
    """Bucket of one span; None = uncharged (unknown stage or the
    query-root envelope)."""
    if op.startswith("Cpu") and stage in ("pump", "scanTime"):
        return "host_fallback"
    if stage == "transferTime" and op == "DeviceToHostExec":
        return "result_d2h"
    return STAGE_BUCKETS.get(stage)


# ---------------------------------------------------------------------------
# The ledger fold
# ---------------------------------------------------------------------------

def _project(intervals: List[Tuple[float, float, int, int]], nkeys: int,
             t0: float, t1: float) -> Tuple[List[float], List[float]]:
    """Charge the [t0, t1] timeline to buckets by priority sweep.

    ``intervals`` is (start, end, priority_index, key_index); returns
    seconds per ``BUCKET_PRIORITY`` index and, from the same sweep,
    seconds per key index (``nkeys`` of them).  At each elementary
    segment between boundary points the highest-priority active bucket
    (lowest index) wins, and within it the span opened last (the
    innermost on its thread), so both results are exclusive by
    construction, sum to at most (t1 - t0), and the keys of one bucket
    add up to the bucket."""
    n = len(BUCKET_PRIORITY)
    out = [0.0] * n
    stages = [0.0] * nkeys
    if t1 <= t0 or not intervals:
        return out, stages
    events: List[Tuple[float, int, int]] = []
    for i, (s, e, _pri, _key) in enumerate(intervals):
        s, e = max(s, t0), min(e, t1)
        if e > s:
            events.append((s, 1, i))
            events.append((e, 0, i))
    if not events:
        return out, stages
    events.sort()
    # the open spans of each priority in the order they opened (events
    # come in time order), so the innermost is the last
    active: List[List[int]] = [[] for _ in range(n)]
    prev = events[0][0]
    for t, opens, idx in events:
        if t > prev:
            for pri, open_here in enumerate(active):
                if open_here:
                    out[pri] += t - prev
                    stages[intervals[open_here[-1]][3]] += t - prev
                    break
            prev = t
        open_here = active[intervals[idx][2]]
        if opens:
            open_here.append(idx)
        elif open_here[-1] == idx:
            open_here.pop()
        else:
            open_here.remove(idx)
    return out, stages


_PRI_INDEX = {b: i for i, b in enumerate(BUCKET_PRIORITY)}
# (op, stage) -> (a launch?, priority index or None, "<op>:<stage>"):
# what the fold needs of a span, worked out once a process per pair
_SPAN_KINDS: Dict[Tuple[str, str], Tuple[bool, Optional[int], str]] = {}


def _span_kind(op: str, stage: str) -> Tuple[bool, Optional[int], str]:
    kind = _SPAN_KINDS.get((op, stage))
    if kind is None:
        b = span_bucket(op, stage)
        kind = _SPAN_KINDS[(op, stage)] = (
            op.startswith("Kernel."), None if b is None else _PRI_INDEX[b],
            f"{op}:{stage}")
    return kind


def _nodes(by_node: Dict[Optional[str], Dict[str, float]],
           tracer) -> Dict[str, Dict[str, Any]]:
    """The ledger's books by plan node: ``{path: {"op", "stages_s",
    "counts"}}``, the query's own under ``"query"`` (its ``op`` None).
    A node's seconds are not rounded: summed over the nodes they give
    each key of the flat ``stages_s`` to its six places."""
    ops = tracer.node_ops if tracer is not None else {}
    counts = tracer.node_counts if tracer is not None else {}
    out: Dict[str, Dict[str, Any]] = {}
    for node, mine in by_node.items():
        out["query" if node is None else node] = {
            "op": ops.get(node), "stages_s": mine,
            "counts": dict(counts.get(node, ()))}
    for node, mine in counts.items():
        if node not in by_node:
            out["query" if node is None else node] = {
                "op": ops.get(node), "stages_s": {}, "counts": dict(mine)}
    return out


def attribute(tracer=None, spans: Optional[Iterable] = None,
              e2e_s: Optional[float] = None,
              tolerance: float = CLOSE_TOLERANCE,
              extras: Optional[Dict[str, float]] = None
              ) -> Dict[str, Any]:
    """Fold a query's trace spans into the exclusive bucket ledger.

    ``tracer`` is a finished ``trace.Tracer`` (preferred — its
    ``t_start``/``wall_s`` anchor the timeline); ``spans`` + ``e2e_s``
    is the raw form the black-box/test path uses.  ``extras`` adds
    scalar seconds measured outside the trace window (the server's
    queue wait) — they extend e2e rather than competing for it.

    Returns ``{"buckets", "stages_s", "counts", "nodes", "e2e_s",
    "unaccounted_s", "closed", "tolerance", "verdict", "dominant",
    "dominant_share", "launches"}`` with buckets rounded, exclusive,
    and summing (with ``unaccounted``) to ``e2e_s`` exactly;
    ``stages_s`` is the same sweep keyed ``<op>:<stage>`` before it
    collapses into buckets (the books by operator: the stages that map
    to a bucket add up to it; an extra is the stage ``extra:<bucket>``);
    ``counts`` is what the query's operators counted on the host
    (``Tracer.counts``: probe groups, slots probed, sorts a rung);
    ``nodes`` is both again by plan node (``_nodes``: a key's seconds
    over the nodes add up to its ``stages_s``, a name's counts to its
    ``counts``); ``launches`` counts the cached-kernel calls (the
    ``Kernel.<label>`` spans)."""
    if tracer is not None:
        spans = list(tracer.events)
        t0 = tracer.t_start
        wall = tracer.wall_s
        if wall is None:
            wall = (time.perf_counter() - t0)
        t1 = t0 + wall
    else:
        spans = list(spans or ())
        if spans:
            t0 = min(sp.t0 for sp in spans)
            t1 = max(sp.t1 for sp in spans)
        else:
            t0 = t1 = 0.0
        if e2e_s is not None:
            t1 = t0 + e2e_s
    e2e = max(t1 - t0, 0.0)
    # the sweep's keys: (plan path, "<op>:<stage>"), each an index
    keys: Dict[Tuple[Optional[str], str], int] = {}
    intervals: List[Tuple[float, float, int, int]] = []
    launches = 0
    for sp in spans:
        launch, pri, name = _span_kind(sp.op, sp.stage)
        launches += launch
        if pri is None:
            continue
        k = (getattr(sp, "node", None), name)
        kid = keys.get(k)
        if kid is None:
            kid = keys[k] = len(keys)
        intervals.append((sp.t0, sp.t1, pri, kid))
    per_pri, per_key = _project(intervals, len(keys), t0, t1)
    # the books by operator are the books by node summed over nodes
    stages: Dict[str, float] = {}
    by_node: Dict[Optional[str], Dict[str, float]] = {}
    for (node, key), kid in keys.items():
        secs = per_key[kid]
        if secs:
            stages[key] = stages.get(key, 0.0) + secs
            mine = by_node.get(node)
            if mine is None:
                mine = by_node[node] = {}
            mine[key] = secs
    buckets = {b: per_pri[i] for i, b in enumerate(BUCKET_PRIORITY)}
    covered = sum(per_pri)
    unaccounted = max(e2e - covered, 0.0)
    for name, secs in (extras or {}).items():
        if name in buckets and secs:
            buckets[name] += float(secs)
            stages[f"extra:{name}"] = float(secs)
            by_node.setdefault(None, {})[f"extra:{name}"] = float(secs)
            e2e += float(secs)
    buckets["unaccounted"] = unaccounted
    tol = float(tolerance)
    closed = unaccounted <= max(tol * e2e, ABS_CLOSE_SLACK_S)
    ranked = sorted(buckets.items(), key=lambda kv: -kv[1])
    dominant, dom_s = ranked[0] if ranked else ("unaccounted", 0.0)
    share = (dom_s / e2e) if e2e > 0 else 0.0
    att = {
        "buckets": {b: round(s, 6) for b, s in buckets.items()},
        "stages_s": {k: round(s, 6) for k, s in stages.items()},
        "counts": dict(tracer.counts) if tracer is not None else {},
        "nodes": _nodes(by_node, tracer),
        "e2e_s": round(e2e, 6),
        "unaccounted_s": round(unaccounted, 6),
        "closed": closed,
        "tolerance": tol,
        "dominant": dominant,
        "dominant_share": round(share, 4),
        "launches": launches,
    }
    att["verdict"] = verdict_line(att)
    return att


# ---------------------------------------------------------------------------
# The books, published: the last ledgers of the process, for readers
# outside the query (the benchmark's per-layer metrics)
# ---------------------------------------------------------------------------

RECENT_MAX = 4096
_RECENT: deque = deque(maxlen=RECENT_MAX)


def publish(att: Dict[str, Any], tracer) -> Dict[str, Any]:
    """Stamp a query's ledger with its id and its wall on
    ``time.monotonic()`` (the clock a caller times requests on; the
    tracer keeps ``perf_counter`` for durations) and put it into the
    bounded ring ``recent()`` reads.  ``record_s`` — what the epilogue
    after the wall cost the caller — is filled in by ``toArrow`` when
    ``_record_query`` returns.  Returns ``att``, the ring's entry."""
    att["query_id"] = tracer.query_id
    att["t0_mono"] = tracer.t_start_mono
    att["t1_mono"] = tracer.t_start_mono + (tracer.wall_s or 0.0)
    att["record_s"] = None
    _RECENT.append(att)
    TM.BOOKS_PUBLISHED.inc()
    return att


def recent() -> List[Dict[str, Any]]:
    """The last ``RECENT_MAX`` published ledgers, oldest first."""
    return list(_RECENT)


def verdict_line(att: Dict[str, Any]) -> str:
    """The one-line diagnosis: '<label>: NN% of S s in <bucket>'."""
    dom = att.get("dominant") or "unaccounted"
    label = BUCKET_VERDICTS.get(dom, dom)
    share = float(att.get("dominant_share") or 0.0)
    e2e = float(att.get("e2e_s") or 0.0)
    line = f"{label}: {share:.0%} of {e2e:.1f} s in {dom}"
    if not att.get("closed", True):
        gap = float(att.get("unaccounted_s") or 0.0)
        line += f" (NOT CLOSED: {gap:.1f} s unaccounted)"
    return line


# ---------------------------------------------------------------------------
# Flight recorder — one a query in flight, by the query's thread of
# execution (runtime/inflight.py, the mechanism the tracer uses)
# ---------------------------------------------------------------------------

class FlightRecorder:
    """Bounded ring of a query's most recent spans + health/retry/
    cancel events.  Appends are lock-free deque pushes (deque.append
    is atomic) — the black box is cheap enough to leave on by
    default."""

    def __init__(self, query_id: int, ring_size: int = RING_SIZE):
        self.query_id = query_id
        self.ring_size = max(8, int(ring_size))
        self.t_start = time.perf_counter()
        self.spans: deque = deque(maxlen=self.ring_size)
        self.events: deque = deque(maxlen=self.ring_size)

    # called from Tracer.end via the duck-typed ``recorder`` hook —
    # keep it to one append
    def record_span(self, span) -> None:
        self.spans.append((span.op, span.stage,
                           span.t0 - self.t_start, span.t1 - span.t0))

    def record_event(self, kind: str, payload: dict) -> None:
        self.events.append({
            "kind": kind,
            "t_s": round(time.perf_counter() - self.t_start, 6),
            **payload})

    def snapshot(self) -> Dict[str, Any]:
        return {
            "query_id": self.query_id,
            "ring_size": self.ring_size,
            "recent_spans": [
                {"op": op, "stage": stage, "t_s": round(t, 6),
                 "dur_s": round(d, 6)}
                for op, stage, t, d in list(self.spans)],
            "events": list(self.events),
        }


def current() -> Optional[FlightRecorder]:
    return BOOKS.recorder


def start_query(query_id: int,
                ring_size: int = RING_SIZE) -> Optional[FlightRecorder]:
    """Install a fresh recorder for the calling thread's query; None
    when the thread already has one (a nested execution rides the
    owner, same as tracing)."""
    return inflight.install(inflight.RECORDER, lambda: FlightRecorder(
        query_id, ring_size=ring_size))


def end_query(rec: Optional[FlightRecorder]) -> None:
    inflight.remove(inflight.RECORDER, rec)


def record_event(kind: str, payload: dict) -> None:
    """Event into the ring of the calling thread's query, no-op
    otherwise — THE hook free-standing producers (retry policy, health
    evaluator, cancel path) use without carrying a recorder
    reference."""
    rec = BOOKS.recorder
    if rec is not None:
        rec.record_event(kind, payload)


# ---------------------------------------------------------------------------
# Black-box dumps — atomic, bounded, concurrent-safe
# ---------------------------------------------------------------------------

def blackbox_path(dir_path: str, query_id: int) -> str:
    return os.path.join(dir_path, f"query-{query_id:06d}.blackbox.json")


def _evict_oldest(dir_path: str, max_dumps: int) -> None:
    """Keep at most ``max_dumps`` black boxes, oldest-first eviction by
    mtime — a crash-looping server must never flood the dump dir."""
    try:
        names = [n for n in os.listdir(dir_path)
                 if n.endswith(".blackbox.json")]
        if len(names) <= max_dumps:
            return
        full = [os.path.join(dir_path, n) for n in names]
        full.sort(key=lambda p: (os.path.getmtime(p), p))
        for p in full[:len(full) - max_dumps]:
            try:
                os.unlink(p)
            except OSError:
                pass
    except OSError:
        pass


def dump_blackbox(dir_path: str, query_id: int, trigger: str,
                  attribution: Optional[Dict[str, Any]] = None,
                  recorder: Optional[FlightRecorder] = None,
                  extra: Optional[Dict[str, Any]] = None,
                  max_dumps: int = BLACKBOX_MAX_DUMPS) -> Optional[str]:
    """Atomically write ``query-<id>.blackbox.json``.

    tmp + ``os.replace`` in the spill-file style (runtime/memory.py,
    telemetry's prom dump): a reader never sees a torn file and a
    mid-dump crash leaves only a uniquely-named tmp, not a corrupt
    dump.  The tmp name carries pid + random hex so concurrent
    QueryServer queries dumping into one dir never collide.  Returns
    the path, None on failure (observability never fails the query)."""
    import sys
    box = {
        "record": "blackbox",
        "query_id": query_id,
        "trigger": trigger,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }
    if attribution is not None:
        box["attribution"] = attribution
        box["verdict"] = attribution.get("verdict")
    if recorder is not None:
        box["flight_recorder"] = recorder.snapshot()
    if extra:
        box.update(extra)
    try:
        os.makedirs(dir_path, exist_ok=True)
        final = blackbox_path(dir_path, query_id)
        tmp = os.path.join(
            dir_path,
            f".{os.path.basename(final)}.tmp-{os.getpid()}-"
            f"{uuid.uuid4().hex[:8]}")
        with open(tmp, "w") as f:
            json.dump(box, f, default=str)
        os.replace(tmp, final)
        _TM_DUMPS.inc(trigger)
        _evict_oldest(dir_path, max_dumps)
        return final
    except OSError as e:
        print(f"[tpuq] blackbox dump failed: {e}", file=sys.stderr,
              flush=True)
        return None


def note_unaccounted(seconds: float) -> None:
    if seconds > 0:
        _TM_UNACCOUNTED.inc(seconds)
