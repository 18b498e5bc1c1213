"""Physical operator base classes.

[REF: sql-plugin/../GpuExec.scala :: GpuExec.internalDoExecuteColumnar,
 GpuMetrics] — re-designed for this engine's split: ``CpuExec`` nodes pump
``HostBatch`` (the numpy oracle/fallback path, vanilla-Spark analog) and
``TpuExec`` nodes pump ``DeviceBatch`` (static-shape XLA path).  Transition
nodes (exec/transitions.py) convert at the boundary, exactly where the
reference inserts GpuRowToColumnarExec/GpuColumnarToRowExec.

Execution model: a physical plan is a tree; ``execute(partition)`` returns
an iterator of batches for that partition (iterator chaining = the
reference's operator pipelining, SURVEY.md §2.3).
"""

from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Dict, Iterator, Tuple

from spark_rapids_tpu import kernels
from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.runtime import cancel
from spark_rapids_tpu.runtime import shapes
from spark_rapids_tpu.runtime import stats
from spark_rapids_tpu.runtime import trace

# Metric verbosity levels [REF: GpuMetrics.scala :: MetricsLevel] —
# ESSENTIAL always collected, MODERATE the default, DEBUG opt-in.
METRIC_LEVELS = ("ESSENTIAL", "MODERATE", "DEBUG")
_DEFAULT_METRIC_LEVEL = {
    "numOutputRows": "ESSENTIAL",
    "numOutputBatches": "ESSENTIAL",
    "opTime": "MODERATE",
    "transferTime": "MODERATE",
    "partitionTime": "MODERATE",
    "collectiveTime": "MODERATE",
    "semaphoreWaitTime": "MODERATE",
    "concatTime": "DEBUG",
    "fusedIntoConsumer": "DEBUG",
}

class Metric:
    """One operator metric (opTime, numOutputRows, ...).

    [REF: sql-plugin/../GpuMetrics.scala :: GpuMetric]
    """

    __slots__ = ("name", "value", "level", "_lock")

    def __init__(self, name: str, level: str = None):
        self.name = name
        self.value = 0
        self.level = level or _DEFAULT_METRIC_LEVEL.get(name, "MODERATE")
        self._lock = threading.Lock()

    def add(self, v):
        # partitions pump on a thread pool; += is not atomic.  Per-metric
        # lock so unrelated nodes' updates never contend.
        with self._lock:
            self.value += v


class MetricTimer:
    """Times into a Metric and, when a query tracer is active, opens a
    span (op=owning exec, stage=metric name, booked under the exec's
    plan node) — every existing timer site (opTime, transferTime,
    collectiveTime, ...) becomes a trace range with zero per-site
    changes, the NVTX-with-metrics pairing of the reference."""

    __slots__ = ("metric", "op", "node", "_t0", "_tr", "_span")

    def __init__(self, metric: Metric, op: str = None, node=None):
        self.metric = metric
        self.op = op
        self.node = node
        self._tr = None
        self._span = None

    def __enter__(self):
        if self.op is not None:
            tr = trace.current()
            if tr is not None:
                self._tr = tr
                self._span = tr.begin(self.op, self.metric.name,
                                      node=self.node)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.metric.add(time.perf_counter() - self._t0)
        if self._span is not None:
            self._tr.end(self._span)
            self._tr = self._span = None
        return False


def _traced_pump(node: "ExecNode", partition: int, it: Iterator) -> Iterator:
    """Each ``next()`` on a pump iterator becomes one span, so operator
    time nests correctly through the iterator chain: a child's pump span
    opens INSIDE its consumer's on the same thread and its duration
    subtracts from the consumer's self-time."""
    op = node.name
    while True:
        tr = trace.current()
        if tr is None:  # tracer closed mid-pump (leaked iterator)
            yield from it
            return
        sp = tr.begin(op, "pump", {"partition": partition}, node)
        try:
            batch = next(it)
        except StopIteration:
            tr.end(sp)
            return
        except BaseException:
            tr.end(sp)
            raise
        tr.end(sp)
        yield batch


def _cancellable_pump(tok, it: Iterator) -> Iterator:
    """Poll the query's CancelToken before each pumped batch — every
    operator boundary in the plan becomes a cancellation point AND a
    preemption yield point (``preempt_point`` parks here when the
    scheduler suspended the query, releasing this thread's device
    permits until the resume)."""
    while True:
        tok.check()
        tok.preempt_point()
        try:
            batch = next(it)
        except StopIteration:
            return
        yield batch


def _shape_pump(node: "ExecNode", it: Iterator) -> Iterator:
    """Pin every pumped DeviceBatch to the shape plane's canonical
    bucket (runtime/shapes.py) — the operator boundary where stray
    batch capacities would otherwise fan out into fresh (op, schema,
    bucket) XLA compiles downstream.  Pad rows are dead (sel=False)
    and recorded per node in the stats plane as ``padded_rows``."""
    while True:
        try:
            batch = next(it)
        except StopIteration:
            return
        batch, pad = shapes.bucket_batch(batch)
        if pad:
            st = stats.current()
            if st is not None:
                st.node_stats(node).add_padded(pad)
        yield batch


def _prefetch_pump(it: Iterator, depth: int) -> Iterator:
    """Double-buffered pump (kernel plane): keep up to ``depth``
    batches in flight ahead of the consumer.

    JAX dispatch is async — pulling batch N+1 from the producer while
    the consumer still holds batch N enqueues N+1's transfers and
    kernels behind N's, so H2D copy, compute, and D2H readback overlap
    across consecutive batches instead of serializing on each host
    sync.  Only the in-flight window (``depth`` batches) is kept
    alive; ``spark.rapids.tpu.exec.pumpDepth`` = 1 disables it."""
    buf: collections.deque = collections.deque()
    exhausted = False
    while True:
        while not exhausted and len(buf) < depth:
            try:
                buf.append(next(it))
            except StopIteration:  # PEP 479: never leaks out of a gen
                exhausted = True
        if not buf:
            return
        yield buf.popleft()


def _stats_pump(st, node: "ExecNode", it: Iterator) -> Iterator:
    """Record every yielded batch on the query's OpStatsCollector —
    rows/batches/bytes out per node, the observation side of the stats
    plane (runtime/stats.py)."""
    while True:
        try:
            batch = next(it)
        except StopIteration:
            return
        st.observe(node, batch)
        yield batch


def _wrap_execute(fn):
    @functools.wraps(fn)
    def execute(self, partition: int) -> Iterator:
        it = fn(self, partition)
        depth = kernels.current_policy().pump_depth
        if depth > 1 and isinstance(self, TpuExec):
            # innermost of all: the producer runs ahead of every
            # downstream pump so its async dispatches overlap the
            # consumer's work
            it = _prefetch_pump(it, depth)
        if shapes.current_policy().enabled and isinstance(self, TpuExec):
            # innermost: downstream pumps (and consumers) see the
            # bucketed batch
            it = _shape_pump(self, it)
        tok = cancel.current()
        if tok is not None:
            it = _cancellable_pump(tok, it)
        st = stats.current()
        if st is not None:
            # register the node up front: a pump that yields nothing
            # still produces a (zeroed) stats record
            st.node_stats(self)
            it = _stats_pump(st, self, it)
        if trace.current() is None:  # fast path: tracing off
            return it
        return _traced_pump(self, partition, it)

    execute._traced = True
    return execute


class ExecNode:
    """Base physical operator.

    Subclass ``execute`` methods are auto-wrapped at class-creation time
    so that, when a query tracer is active, every partition pump emits
    per-batch spans — no exec opts in or out individually."""

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        fn = cls.__dict__.get("execute")
        if fn is not None and not getattr(fn, "_traced", False):
            cls.execute = _wrap_execute(fn)

    def __init__(self, schema: T.StructType, *children: "ExecNode"):
        self.schema = schema
        self._children: Tuple[ExecNode, ...] = children
        self.metrics: Dict[str, Metric] = {}
        for m in ("opTime", "numOutputRows", "numOutputBatches"):
            self.metrics[m] = Metric(m)

    @property
    def children(self) -> Tuple["ExecNode", ...]:
        return self._children

    @property
    def name(self) -> str:
        return type(self).__name__

    def metric(self, name: str) -> Metric:
        m = self.metrics.get(name)
        if m is None:
            # setdefault is atomic: racing pool threads converge on one
            # Metric instead of orphaning each other's counts
            m = self.metrics.setdefault(name, Metric(name))
        return m

    def timer(self, name: str = "opTime") -> MetricTimer:
        return MetricTimer(self.metric(name), op=self.name, node=self)

    def count(self, name: str, value: int) -> None:
        """A count of this operator's work the host knows: into the
        node's metric and the books of the query in flight (the
        ledger's ``counts``, and this node's in its ``nodes``)."""
        self.metric(name).add(value)
        trace.count(name, value, self)

    def num_partitions(self) -> int:
        if self._children:
            return self._children[0].num_partitions()
        return 1

    def estimated_size_bytes(self):
        """Planner-side output size estimate (broadcast decisions);
        None = unknown.  Narrowing operators forward their child's
        estimate (an upper bound, like Spark's statistics)."""
        if len(self._children) == 1:
            return self._children[0].estimated_size_bytes()
        return None

    def execute(self, partition: int) -> Iterator:
        raise NotImplementedError

    # -- plan display -------------------------------------------------------
    def node_string(self) -> str:
        return self.name

    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + ("*" if self.is_tpu else "") +
                 self.node_string()]
        for c in self._children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    @property
    def is_tpu(self) -> bool:
        return isinstance(self, TpuExec)

    def collect_metrics(self, out=None, level: str = "DEBUG"):
        """Per-node metric values, filtered by verbosity level
        (``spark.rapids.sql.metrics.level``): ESSENTIAL ⊂ MODERATE ⊂
        DEBUG."""
        out = out if out is not None else []
        rank = METRIC_LEVELS.index(level.upper())
        out.append((self.name,
                    {k: m.value for k, m in self.metrics.items()
                     if METRIC_LEVELS.index(m.level) <= rank}))
        for c in self._children:
            c.collect_metrics(out, level)
        return out


class CpuExec(ExecNode):
    """Operator over HostBatch (numpy) — the CPU-fallback / oracle path."""


class TpuExec(ExecNode):
    """Operator over DeviceBatch (jax) — the accelerated path.

    [REF: GpuExec.scala :: GpuExec]
    """

    def fusion(self):
        """(pure batch→batch fn, cache-key) when this operator is a pure
        per-batch map that may fuse into a downstream consumer's kernel
        (filter/project), else None.

        THE XLA counterpart of the reference's tiered projection /
        kernel-launch amortization: a consumer (aggregate, sort, join,
        transfer) composes upstream map fns into its own jitted kernel,
        so a {scan → filter → project → agg} pipeline reads HBM once
        per batch instead of once per operator.
        """
        return None


def fuse_upstream(node: "TpuExec"):
    """Walk down through fusible map operators.

    Returns (source_exec, composed_fn, cache_key): pull batches from
    ``source_exec`` and apply ``composed_fn`` INSIDE the consumer's
    jitted kernel (cache_key must join the consumer's kernel key).
    Fused operators get a ``fusedIntoConsumer`` metric so explain output
    shows why their own row/time metrics stay zero."""
    fns = []
    keys = []
    while isinstance(node, TpuExec):
        f = node.fusion()
        if f is None:
            break
        fn, key = f
        fns.append(fn)
        keys.append(key)
        node.metric("fusedIntoConsumer").value = 1
        node = node.children[0]
    fns.reverse()

    if not fns:
        return node, (lambda b: b), ()

    def composed(batch):
        for f in fns:
            batch = f(batch)
        return batch

    return node, composed, tuple(reversed(keys))
