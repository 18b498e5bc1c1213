"""Per-process executor context for multi-executor (multi-process) runs.

[REF: sql-plugin/../Plugin.scala :: RapidsExecutorPlugin — the
reference's executor plugin initializes the device runtime once per
executor JVM; SURVEY §5.8 — the rendezvous turns Spark's
independently-scheduled tasks into collective participants.]

One ``ExecutorContext`` per process, created by ``TpuSession`` when
``spark.rapids.executor.count > 1``:

* joins the **global device mesh** via ``jax.distributed.initialize``
  (each process addresses only its local devices; collectives span all),
* holds the ``RendezvousClient`` every ICI exchange uses for shape
  agreement and collective entry,
* assigns deterministic per-process stage ids: all executors plan the
  same query with the same deterministic planner, so the Nth exchange
  materialized in one process is the Nth in every process (the analog of
  Spark's driver-assigned shuffle ids).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Callable, List, Optional, Sequence

from spark_rapids_tpu.parallel.rendezvous import RendezvousClient
from spark_rapids_tpu.runtime import inflight
from spark_rapids_tpu.runtime import telemetry as TM
from spark_rapids_tpu.runtime import trace


class ExecutorContext:
    def __init__(self, process_id: int, num_processes: int,
                 coordinator_address: str, rendezvous_address: str,
                 timeout: float, heartbeat_s: float = 0.0):
        # register under the coordinator's heartbeat lease BEFORE the
        # jax.distributed handshake: a peer that dies mid-init is then
        # already visible to the reaper
        self.client = RendezvousClient(rendezvous_address, process_id,
                                       default_timeout=timeout)
        if heartbeat_s > 0:
            self.client.start_heartbeat(heartbeat_s)
        import jax
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
        self.process_id = process_id
        self.num_processes = num_processes
        self.timeout = timeout
        self._stage_counter = itertools.count()

    def next_stage_id(self) -> str:
        """Deterministic across processes (same planner, same order)."""
        return f"stage-{next(self._stage_counter)}"

    def local_partition_ids(self, mesh) -> List[int]:
        """Global mesh-partition indices whose device this process owns."""
        import jax
        pi = jax.process_index()
        return [i for i, d in enumerate(mesh.devices.flatten())
                if d.process_index == pi]


_CTX: Optional[ExecutorContext] = None
_LOCK = threading.Lock()


def rendezvous_timeout_s(conf) -> float:
    """Stage deadline in seconds: ``rendezvous.timeoutMs``, unless the
    legacy ``rendezvous.timeoutSec`` key was set explicitly (it wins)."""
    from spark_rapids_tpu import conf as C
    legacy = conf.get_raw(C.RENDEZVOUS_TIMEOUT.key)
    if legacy is not None:
        return float(legacy)
    return float(conf.get(C.RENDEZVOUS_TIMEOUT_MS)) / 1000.0


def init_executor(conf) -> Optional[ExecutorContext]:
    """Create (or return) the process's executor context per conf.

    Idempotent; raises if a second session asks for a conflicting
    topology (jax.distributed can only initialize once per process)."""
    from spark_rapids_tpu import conf as C
    global _CTX
    count = int(conf.get(C.EXECUTOR_COUNT))
    if count <= 1:
        return None
    coord = str(conf.get(C.COORDINATOR_ADDRESS)).strip()
    rdv = str(conf.get(C.RENDEZVOUS_ADDRESS)).strip()
    if not coord or not rdv:
        raise ValueError(
            "executor.count > 1 requires both "
            "spark.rapids.executor.coordinator.address and "
            "spark.rapids.shuffle.rendezvous.address")
    if conf.shuffle_mode != "ICI":
        raise ValueError(
            "multi-executor mode requires spark.rapids.shuffle.mode=ICI "
            f"(got {conf.shuffle_mode})")
    pid = int(conf.get(C.EXECUTOR_ID))
    timeout = rendezvous_timeout_s(conf)
    heartbeat_s = float(conf.get(C.RENDEZVOUS_HEARTBEAT_MS)) / 1000.0
    with _LOCK:
        if _CTX is not None:
            if (_CTX.process_id, _CTX.num_processes) != (pid, count):
                raise ValueError(
                    "executor context already initialized as "
                    f"({_CTX.process_id}/{_CTX.num_processes}); cannot "
                    f"re-initialize as ({pid}/{count})")
            _CTX.timeout = timeout
            _CTX.client.default_timeout = timeout
            return _CTX
        _CTX = ExecutorContext(pid, count, coord, rdv, timeout,
                               heartbeat_s)
        return _CTX


def get_executor() -> Optional[ExecutorContext]:
    return _CTX


# ---------------------------------------------------------------------------
# instrumented partition-pump pool (the Spark-task-slot analog's
# process-level observability: queue depth + task latency)
# ---------------------------------------------------------------------------

_pump_lock = threading.Lock()
_pump_inflight = 0  # tasks submitted and not yet completed

_TM_PUMP_TASKS = TM.REGISTRY.counter(
    "tpuq_pump_tasks_total", "partition pump tasks completed")
_TM_PUMP_TASK_S = TM.REGISTRY.histogram(
    "tpuq_pump_task_seconds",
    "per-task pump execution time (incl. semaphore wait)")
TM.REGISTRY.gauge(
    "tpuq_pump_queue_depth",
    "pump tasks submitted but not yet completed",
    fn=lambda: _pump_inflight)


def run_pump_tasks(fn: Callable, items: Sequence,
                   max_workers: int = 1) -> List:
    """Run ``fn`` over ``items`` preserving order — inline when a single
    worker suffices, else on a transient thread pool — with queue-depth
    and task-latency accounting either way."""
    global _pump_inflight
    items = list(items)
    if not items:
        return []
    started = [0]

    def timed(item):
        global _pump_inflight
        with _pump_lock:
            started[0] += 1
        t0 = time.perf_counter()
        try:
            return fn(item)
        finally:
            _TM_PUMP_TASK_S.observe(time.perf_counter() - t0)
            _TM_PUMP_TASKS.inc()
            with _pump_lock:
                _pump_inflight -= 1

    with _pump_lock:
        _pump_inflight += len(items)
    try:
        if max_workers <= 1 or len(items) == 1:
            return [timed(i) for i in items]
        from concurrent.futures import ThreadPoolExecutor
        # the caller waits here for the pool's threads to start, run
        # and join: the pump's envelope, charged wherever no task's
        # own span covers the instant (beside other queries' threads
        # the wake-up alone can take tens of milliseconds)
        with trace.span("PumpTask", "poolWait"):
            with ThreadPoolExecutor(max_workers=max_workers) as pool:
                # pool threads work for the calling thread's query:
                # their spans, stats and events go into its books
                return list(pool.map(inflight.carry(timed), items))
    finally:
        # tasks cancelled before starting (an earlier task raised)
        # never ran their own decrement — settle the gauge exactly
        with _pump_lock:
            _pump_inflight -= len(items) - started[0]
