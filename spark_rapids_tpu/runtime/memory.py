"""HBM budget arbiter + spill store + OOM-retry framework.

[REF: sql-plugin/../GpuDeviceManager.scala, spill/SpillFramework.scala,
 RmmRapidsRetryIterator.scala :: withRetry / withRetryNoSplit /
 splitSpillableInHalfByRows; spark-rapids-jni :: RmmSpark (per-thread OOM
 state machine, forceRetryOOM injection)]

TPU re-design: there is no RMM — XLA/PJRT owns HBM — so the arbiter is an
*accounting* layer ABOVE the runtime (SURVEY §2.2 N10/N12): operators
``reserve()`` bytes before materializing batches; registered
``SpillableBatch``es are the reclaim pool.  When a reservation would
exceed the budget the arbiter synchronously spills victims
device→host→disk (host tier capped by
``spark.rapids.memory.host.spillStorageSize``, disk tier under
``spark.rapids.tpu.spillPath``), and if still short raises ``RetryOOM``
for ``with_retry`` to catch: restore-from-checkpoint, halve the input by
rows (``SplitAndRetryOOM``), re-run the closure per half.

The ``injectOomAtAlloc`` conf forces an OOM at the Nth reservation — the
test hook that makes the retry/spill path deterministically coverable
(the RmmSpark.forceRetryOOM analog, SURVEY §4.2).
"""

from __future__ import annotations

import atexit
import contextlib
import os
import shutil
import threading
import uuid
import zlib
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar.column import DeviceBatch, DeviceColumn
from spark_rapids_tpu.runtime import cancel
from spark_rapids_tpu.runtime import resilience as R
from spark_rapids_tpu.runtime import telemetry as TM
from spark_rapids_tpu.runtime import trace

# process-cumulative counters (per-manager views live in mgr.metrics);
# gauges pull the CURRENT manager's state at snapshot time
_TM_RESERVE = TM.REGISTRY.counter(
    "tpuq_hbm_reserve_bytes_total",
    "bytes reserved against the HBM budget (cumulative)")
_TM_SPILL_HOST = TM.REGISTRY.counter(
    "tpuq_spill_host_bytes_total", "device→host spill bytes")
_TM_SPILL_DISK = TM.REGISTRY.counter(
    "tpuq_spill_disk_bytes_total", "host→disk spill bytes")
_TM_RESTORE = TM.REGISTRY.counter(
    "tpuq_restore_bytes_total",
    "bytes restored to device from the host/disk spill tiers")
_TM_RETRY_OOM = TM.REGISTRY.counter(
    "tpuq_retry_oom_total", "RetryOOM raises (incl. injected)")
_TM_SPLIT_RETRY = TM.REGISTRY.counter(
    "tpuq_split_retry_total", "SplitAndRetryOOM batch halvings")
_TM_PREEMPT_SPILLED = TM.REGISTRY.counter(
    "tpuq_preempt_spilled_bytes_total",
    "device bytes spilled to host because their query was suspended "
    "by the preemption plane")
_TM_TENANT_BREACH = TM.REGISTRY.labeled_counter(
    "tpuq_tenant_hbm_breach_total",
    "reservations denied because the tenant's enforced HBM byte "
    "budget (hbmShare x pool) was exhausted even after spilling its "
    "own residency", label="tenant")


class RetryOOM(RuntimeError):
    """Device memory exhausted; caller should free/spill and re-run."""


class SplitAndRetryOOM(RetryOOM):
    """Re-running whole won't fit; caller must halve the input."""


# ---------------------------------------------------------------------------
# spill-file integrity + per-process spill directory lifetime
# ---------------------------------------------------------------------------

def _file_crc32(path: str) -> int:
    """CRC32 of a file's bytes, chunked (spill files can be large)."""
    crc = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                return crc
            crc = zlib.crc32(chunk, crc)


def _write_crc_sidecar(path: str) -> None:
    with open(path + ".crc32", "w") as f:
        f.write(f"{_file_crc32(path):08x}\n")


def _verify_crc_sidecar(path: str) -> None:
    """Raise ``ValueError`` (spill_read-retryable, domain-tagged on
    exhaustion) when the payload no longer matches its recorded CRC —
    a garbled batch must never restore silently."""
    sidecar = path + ".crc32"
    if not os.path.exists(sidecar):
        return  # pre-integrity spill file; np.load is the only check
    with open(sidecar) as f:
        want = int(f.read().strip(), 16)
    got = _file_crc32(path)
    if got != want:
        raise ValueError(
            f"spill file {path} corrupt: crc32 {got:08x} != "
            f"recorded {want:08x}")


def _unlink_spill(path: str) -> None:
    for p in (path, path + ".crc32"):
        if os.path.exists(p):
            os.unlink(p)


# every per-process spill subdirectory ever handed to a manager in this
# process; one atexit hook removes them all, so a normal exit strands
# no orphan .npz files under the shared spillPath root
_SPILL_DIRS: set = set()
_SPILL_DIRS_LOCK = threading.Lock()


def _cleanup_spill_dirs() -> None:
    with _SPILL_DIRS_LOCK:
        dirs = list(_SPILL_DIRS)
        _SPILL_DIRS.clear()
    for d in dirs:
        shutil.rmtree(d, ignore_errors=True)


def _register_spill_dir(path: str) -> None:
    with _SPILL_DIRS_LOCK:
        if not _SPILL_DIRS:
            atexit.register(_cleanup_spill_dirs)
        _SPILL_DIRS.add(path)


class SpillableBatch:
    """A device batch registered with the arbiter as reclaimable.

    States: device (batch live, bytes counted) → host (numpy copies) →
    disk (one .npz under spillPath).  ``get()`` restores to device,
    re-reserving its bytes.  [REF: SpillableColumnarBatch]
    """

    def __init__(self, batch: DeviceBatch, manager: "DeviceMemoryManager",
                 reserve: bool = True):
        self._mgr = manager
        self._batch: Optional[DeviceBatch] = batch
        self._host: Optional[list] = None
        self._disk_path: Optional[str] = None
        # True only while this batch's host copy is counted in the
        # manager's _host_used (a disk restore staged in _host is NOT)
        self._host_accounted = False
        # True while the device bytes are counted in _reserved —
        # reserve=False registrations (e.g. out-of-core slices carved
        # from already-materialized inputs) must not release bytes they
        # never claimed
        self._device_accounted = reserve
        # set when a disk spill degraded (stayed in the host tier); the
        # host-limit eviction loop must skip such victims or it spins
        self._disk_spill_failed = False
        # True while a disk write is in flight for this batch.  The
        # write's retry/backoff sleeps are preempt yield points, and a
        # park's suspend-spill can re-enter the host-eviction loop on
        # this very batch — without the guard both frames write a file
        # and the second assignment orphans the first.
        self._disk_spilling = False
        self.schema = batch.schema
        self.compacted = batch.compacted
        self.nbytes = batch.nbytes()
        # static row capacity, readable without restoring a spilled
        # batch (the join's skew re-check must not force an unspill)
        self.capacity = batch.capacity
        # tenancy: the batch belongs to the ambient query — its bytes
        # charge that tenant's enforced HBM budget, and a suspend of
        # that query spills it through the tiers
        tok = cancel.current()
        self._tenant = tok.tenant if tok is not None else "default"
        self._query_id = tok.query_id if tok is not None else None
        if reserve:
            manager.reserve(self.nbytes, tenant=self._tenant)
        manager._register(self)

    @property
    def tier(self) -> str:
        if self._batch is not None:
            return "device"
        if self._host is not None:
            return "host"
        return "disk"

    def spill_to_host(self) -> int:
        """Device → host.  Returns bytes freed on device."""
        if self._batch is None:
            return 0
        with trace.span("Spill", "spillTime"):
            return self._spill_to_host()

    def _spill_to_host(self) -> int:
        import jax
        b = self._batch
        leaves, treedef = jax.tree.flatten(b)
        # one overlapped transfer round trip (see columnar.device_to_host)
        for x in leaves:
            x.copy_to_host_async()
        self._host = ([np.asarray(x) for x in leaves], treedef)
        self._batch = None
        self._host_accounted = True
        was_accounted = self._device_accounted
        self._device_accounted = False
        self._mgr._on_spill(self, self.nbytes,
                            release_device=was_accounted)
        return self.nbytes

    def spill_to_disk(self) -> int:
        """Host → disk through the ``spill_write`` failure domain.
        Returns host bytes freed (0 when the write degraded — the batch
        stays in the host tier, marked so the eviction loop skips it)."""
        if self._host is None or self._disk_spilling:
            return 0
        with trace.span("Spill", "spillTime"):
            return self._spill_to_disk()

    def _spill_to_disk(self) -> int:
        leaves, treedef = self._host
        os.makedirs(self._mgr.spill_path, exist_ok=True)
        if self._disk_path is not None:
            # a restore raced an eviction (preemption churn makes this
            # reachable: the restore staged _host, then RetryOOM'd its
            # reservation while the evictor re-spilled) — drop the
            # stale file or the overwrite below orphans it
            _unlink_spill(self._disk_path)
            self._disk_path = None
        path = os.path.join(self._mgr.spill_path,
                            f"spill-{uuid.uuid4().hex}.npz")

        def attempt():
            R.INJECTOR.on("spill_write")
            np.savez(path, *leaves)
            # integrity sidecar: restore refuses a payload whose bytes
            # no longer match what was written
            _write_crc_sidecar(path)
            return True

        def degrade():
            return False  # keep the host copy; data is still safe

        self._disk_spilling = True
        try:
            ok = R.run_guarded("spill_write", attempt, op="spill_to_disk",
                               degrade=degrade)
        finally:
            self._disk_spilling = False
        if not ok:
            self._disk_spill_failed = True
            _unlink_spill(path)  # drop any partial file
            return 0
        if self._disk_path is not None and self._disk_path != path:
            # someone re-spilled this batch while our write was in its
            # retry loop — never orphan their file
            _unlink_spill(self._disk_path)
        self._disk_path = path
        self._treedef = treedef
        freed = sum(x.nbytes for x in leaves)
        self._host = None
        if self._host_accounted:
            with self._mgr._lock:
                self._mgr._host_used = max(
                    0, self._mgr._host_used - freed)
            self._host_accounted = False
        self._mgr._on_disk_spill(self, freed)
        return freed

    def get(self) -> DeviceBatch:
        """Restore (if needed) and return the device batch."""
        if self._batch is not None:
            return self._batch
        with trace.span("Spill", "restoreTime"):
            return self._restore()

    def _restore(self) -> DeviceBatch:
        import jax
        from_host = self._host is not None
        if not from_host and self._disk_path is not None:
            # disk staging never touches _host_used accounting.  The
            # restore passes the ``spill_read`` failure domain: IO
            # faults (missing/corrupt .npz) retry, and exhaustion is a
            # domain-tagged terminal error — the data is gone, there is
            # no host path to degrade to.
            def attempt():
                R.INJECTOR.on("spill_read")
                _verify_crc_sidecar(self._disk_path)
                with np.load(self._disk_path) as z:
                    return [z[k] for k in z.files]

            leaves = R.run_guarded("spill_read", attempt,
                                   op="spill_restore")
            self._host = (leaves, self._treedef)
            _unlink_spill(self._disk_path)
            self._disk_path = None
        leaves, treedef = self._host
        self._mgr.reserve(self.nbytes, _restoring=self,
                          tenant=self._tenant)
        self._device_accounted = True
        self._batch = jax.tree.unflatten(
            treedef, [jax.numpy.asarray(x) for x in leaves])
        self._host = None
        self._mgr.metrics["restoredBytes"] += self.nbytes
        _TM_RESTORE.inc(self.nbytes)
        if from_host and self._host_accounted:
            self._host_accounted = False
            self._mgr._on_restore(self)
        return self._batch

    def close(self):
        self._mgr._unregister(self)
        if self._disk_path is not None:
            _unlink_spill(self._disk_path)
            self._disk_path = None
        self._batch = None
        self._host = None


class DeviceMemoryManager:
    """The budget arbiter [REF: GpuDeviceManager + SpillFramework].

    Budget = ``poolSize`` if set, else ``allocFraction`` × detected HBM
    (PJRT ``memory_stats().bytes_limit``; 4 GiB fallback when the
    platform doesn't report, e.g. the virtual CPU mesh).
    """

    def __init__(self, budget: Optional[int] = None,
                 alloc_fraction: float = 0.85,
                 host_limit: int = 4 << 30,
                 spill_path: str = "/tmp/tpuq-spill",
                 inject_oom_at: int = -1,
                 retry_max_attempts: int = 8,
                 debug: bool = False,
                 conf=None):
        self.retry_max_attempts = retry_max_attempts
        self._lock = threading.RLock()
        self._spillables: Dict[int, SpillableBatch] = {}
        # per-tenant HBM enforcement: live reserved bytes per tenant,
        # checked against hbmShare x budget at every reserve.  The conf
        # is kept only to resolve per-tenant hbmShare overrides.
        self._conf = conf
        self._tenant_used: Dict[str, int] = {}
        self._tenant_share_default = 1.0
        if conf is not None:
            from spark_rapids_tpu import conf as C
            self._tenant_share_default = float(
                conf.get(C.SCHED_TENANT_HBM_SHARE))
        # leak tracker [REF: cudf MemoryCleaner]: with debug on, every
        # registration records its creation stack; unreleased handles
        # are reported at shutdown / replacement (LEAK DETECTED)
        self.debug = debug
        self._origins: Dict[int, str] = {}
        if debug:
            import atexit
            atexit.register(self.report_leaks)
        self._reserved = 0
        self._host_used = 0
        self.host_limit = host_limit
        # each manager spills into its own per-process subdirectory of
        # the configured root — concurrent/killed processes sharing one
        # spillPath can no longer collide, and the atexit hook removes
        # the whole subtree on normal exit
        self.spill_root = spill_path
        self.spill_path = os.path.join(
            spill_path, f"proc-{os.getpid()}-{uuid.uuid4().hex[:8]}")
        _register_spill_dir(self.spill_path)
        self._alloc_count = 0
        self._inject_at = inject_oom_at
        self.metrics = {"spillToHostBytes": 0, "spillToDiskBytes": 0,
                        "restoredBytes": 0, "retryOOMs": 0,
                        "splitRetries": 0, "peakReserved": 0,
                        "tenantBreaches": 0, "preemptSpilledBytes": 0}
        self.budget = budget if budget else self._detect_budget(
            alloc_fraction)

    @staticmethod
    def _detect_budget(fraction: float) -> int:
        """HBM budget = ``fraction`` of the SMALLEST local device's
        ``bytes_limit`` (batches of a mesh query land on every device,
        so the tightest one bounds them).  Only the CPU backend, which
        reports no limit, gets the nominal 4 GiB; an accelerator that
        cannot say how much memory it has is an error, not a default
        that hides the device."""
        import jax
        if jax.default_backend() == "cpu":
            return int((4 << 30) * fraction)
        limits = [(d.memory_stats() or {}).get("bytes_limit")
                  for d in jax.local_devices()]
        if not all(limits):
            raise LookupError(
                f"backend {jax.default_backend()!r} reports no "
                f"bytes_limit for its devices ({limits}); set "
                "spark.rapids.tpu.memory.poolSize explicitly")
        return int(min(limits) * fraction)

    # -- accounting ---------------------------------------------------------
    def reserve(self, nbytes: int, _restoring=None,
                tenant: Optional[str] = None) -> None:
        """Claim bytes for an upcoming materialization, charged to
        ``tenant`` (the ambient query token's tenant when omitted).
        Synchronously spills victims if needed; raises RetryOOM when
        the global budget — or the tenant's enforced hbmShare byte
        budget — cannot be met (or when fault injection fires).  A
        tenant breach escalates OUTSIDE the manager lock: spill the
        tenant's own residency first, then ask the scheduler to
        preempt its largest-runtime other query, then RetryOOM."""
        if tenant is None:
            tok = cancel.current()
            tenant = tok.tenant if tok is not None else "default"
        breached = False
        with self._lock:
            self._alloc_count += 1
            if self._inject_at >= 0 and self._alloc_count == self._inject_at:
                self.metrics["retryOOMs"] += 1
                _TM_RETRY_OOM.inc()
                raise RetryOOM(
                    f"injected OOM at allocation {self._alloc_count}")
            if R.INJECTOR.armed:
                # the ``alloc`` failure domain: an injected fault here
                # IS a forced OOM — it re-enters the existing
                # RetryOOM/with_retry rollback machinery rather than a
                # separate retry loop
                try:
                    R.INJECTOR.on("alloc")
                except R.InjectedDeviceError as e:
                    self.metrics["retryOOMs"] += 1
                    _TM_RETRY_OOM.inc()
                    raise RetryOOM(str(e)) from e
            if nbytes > self.budget:
                self.metrics["retryOOMs"] += 1
                _TM_RETRY_OOM.inc()
                raise SplitAndRetryOOM(
                    f"allocation of {nbytes} B exceeds the whole budget "
                    f"({self.budget} B) — split required")
            while self._reserved + nbytes > self.budget:
                if not self._spill_one(exclude=_restoring):
                    self.metrics["retryOOMs"] += 1
                    _TM_RETRY_OOM.inc()
                    raise RetryOOM(
                        f"cannot reserve {nbytes} B: {self._reserved} of "
                        f"{self.budget} B reserved, nothing left to spill")
            tenant_budget = self._tenant_budget(tenant)
            if tenant_budget < self.budget:
                # spill-first: the tenant's own device residency pays
                # before anyone else is disturbed
                while (self._tenant_used.get(tenant, 0) + nbytes
                       > tenant_budget):
                    if not self._spill_one_tenant(tenant,
                                                  exclude=_restoring):
                        break
                if (self._tenant_used.get(tenant, 0) + nbytes
                        > tenant_budget):
                    breached = True
                    self.metrics["tenantBreaches"] += 1
                    self.metrics["retryOOMs"] += 1
            if not breached:
                self._reserved += nbytes
                self._tenant_used[tenant] = (
                    self._tenant_used.get(tenant, 0) + nbytes)
                _TM_RESERVE.inc(nbytes)
                self.metrics["peakReserved"] = max(
                    self.metrics["peakReserved"], self._reserved)
        if breached:
            _TM_TENANT_BREACH.inc(tenant)
            _TM_RETRY_OOM.inc()
            # escalate to preemption: suspend the tenant's largest-
            # runtime OTHER running query so its reservations unwind.
            # Must run without the manager lock — the scheduler takes
            # its own lock and the documented order is sched -> memory.
            tok = cancel.current()
            exclude = tok.query_id if tok is not None else None
            from spark_rapids_tpu.runtime import scheduler
            sched = scheduler.peek_scheduler()
            preempted = False
            if sched is not None:
                try:
                    preempted = sched.request_tenant_preemption(
                        tenant, exclude_query_id=exclude)
                except Exception:
                    pass  # best-effort; the RetryOOM still rolls back
            if not preempted:
                # no local victim — relay to the cluster arbiter so it
                # can suspend the tenant's largest query on ANOTHER
                # executor (piggybacks on the next heartbeat)
                from spark_rapids_tpu.runtime import tenancy
                agent = tenancy.peek_agent()
                if agent is not None:
                    try:
                        agent.notify_breach(tenant)
                    except Exception:
                        pass
            raise RetryOOM(
                f"tenant {tenant} cannot reserve {nbytes} B: "
                f"{self._tenant_used.get(tenant, 0)} of its "
                f"{self._tenant_budget(tenant)} B hbmShare budget used "
                "and its own residency is already spilled")

    def release(self, nbytes: int, tenant: Optional[str] = None) -> None:
        if tenant is None:
            tok = cancel.current()
            tenant = tok.tenant if tok is not None else "default"
        with self._lock:
            self._reserved = max(0, self._reserved - nbytes)
            if tenant in self._tenant_used:
                self._tenant_used[tenant] = max(
                    0, self._tenant_used[tenant] - nbytes)

    def _tenant_budget(self, tenant: str) -> int:
        """The tenant's enforced HBM byte budget: hbmShare (per-tenant
        conf override, else the scheduler-wide default) x pool."""
        share = self._tenant_share_default
        if self._conf is not None:
            raw = self._conf.get_raw(
                f"spark.rapids.tpu.scheduler.tenant.{tenant}.hbmShare")
            if raw is not None:
                try:
                    share = float(raw)
                except (TypeError, ValueError):
                    pass
        return int(min(1.0, max(0.0, share)) * self.budget)

    def _spill_one_tenant(self, tenant: str, exclude=None) -> bool:
        for s in list(self._spillables.values()):
            if (s is exclude or s.tier != "device"
                    or not s._device_accounted or s._tenant != tenant):
                continue
            s.spill_to_host()
            return True
        return False

    def tenant_usage(self) -> Dict[str, int]:
        """Live reserved bytes per tenant (snapshot)."""
        with self._lock:
            return dict(self._tenant_used)

    @contextlib.contextmanager
    def transient(self, nbytes: int):
        """Reserve for the duration of a device op (operator working-set
        accounting; released on exit)."""
        self.reserve(nbytes)
        try:
            yield
        finally:
            self.release(nbytes)

    def _spill_one(self, exclude=None) -> bool:
        # oldest-registered first (approximate LRU)
        for s in list(self._spillables.values()):
            if s is exclude or s.tier != "device":
                continue
            s.spill_to_host()
            return True
        return False

    # -- spillable registry callbacks --------------------------------------
    def _register(self, s: SpillableBatch) -> None:
        with self._lock:
            self._spillables[id(s)] = s
            if self.debug:
                import traceback
                self._origins[id(s)] = "".join(
                    traceback.format_stack(limit=12)[:-2])

    def leaked(self, include_pinned: bool = False) -> List[tuple]:
        """(batch, origin-stack) for every never-closed registration.
        The scan cache is a deliberate long-lived pool — excluded unless
        ``include_pinned`` (its entries close on eviction)."""
        from spark_rapids_tpu.exec.basic import _scan_cache
        pinned = {id(sp) for entries in _scan_cache.values()
                  for pairs in entries.values() for sp, _ in pairs}
        with self._lock:
            return [(s, self._origins.get(i, "<enable memory.gpu.debug "
                                             "for stacks>"))
                    for i, s in self._spillables.items()
                    if include_pinned or i not in pinned]

    def spill_pressure(self) -> float:
        """Occupancy fraction of the HOST spill tier (0.0 = empty,
        >= 1.0 = the next host spill will push victims to disk).  The
        admission controller sheds new queries when this crosses its
        watermark — BEFORE the arbiter starts thrashing the disk tier."""
        if self.host_limit <= 0:
            return 0.0
        return self._host_used / self.host_limit

    def report_leaks(self) -> int:
        leaks = self.leaked()
        for s, origin in leaks:
            print(f"LEAK DETECTED: spillable batch {s.nbytes} B "
                  f"(tier={s.tier}) never closed; created at:\n{origin}")
        return len(leaks)

    def suspend_spill(self, query_id: int) -> int:
        """Spill a suspending query's device-resident registered
        batches to the host tier so the preemptor inherits its HBM
        headroom (scan-cache pins are shared residency — they stay).
        Called by the first thread to park in ``_park_suspended``;
        the batches rehydrate lazily (CRC-checked, bit-identical) when
        the resumed query next touches them.  Returns bytes spilled."""
        from spark_rapids_tpu.exec.basic import _scan_cache
        pinned = {id(sp) for entries in _scan_cache.values()
                  for pairs in entries.values() for sp, _ in pairs}
        spilled = 0
        with self._lock:
            for s in list(self._spillables.values()):
                if (s.tier != "device" or id(s) in pinned
                        or s._query_id != query_id):
                    continue
                spilled += s.spill_to_host()
        if spilled:
            self.metrics["preemptSpilledBytes"] += spilled
            _TM_PREEMPT_SPILLED.inc(spilled)
        return spilled

    def reclaim_all(self) -> int:
        """Close every non-pinned registered spillable — the cancelled
        query's reclamation sweep.  Closing releases device/host
        accounting and unlinks disk spill files (+ CRC sidecars), so
        ``report_leaks()`` returns 0 afterwards.  Returns the number of
        batches reclaimed."""
        n = 0
        for s, _origin in self.leaked():
            s.close()
            n += 1
        return n

    def _unregister(self, s: SpillableBatch) -> None:
        with self._lock:
            self._spillables.pop(id(s), None)
            self._origins.pop(id(s), None)
            if s.tier == "device" and s._device_accounted:
                s._device_accounted = False
                self.release(s.nbytes, tenant=s._tenant)
            elif s._host_accounted:
                # symmetric with _on_spill: host-tier bytes leave the
                # host budget when the batch is closed/evicted (staged
                # disk restores were never counted — skip those)
                s._host_accounted = False
                self._host_used = max(0, self._host_used - s.nbytes)

    def _on_spill(self, s: SpillableBatch, nbytes: int,
                  release_device: bool = True) -> None:
        with self._lock:
            if release_device:
                # charge the batch's OWN tenant, not the ambient one —
                # the global spill loop may evict another query's batch
                self.release(nbytes, tenant=s._tenant)
            self._host_used += nbytes
            self.metrics["spillToHostBytes"] += nbytes
            _TM_SPILL_HOST.inc(nbytes)
            trace.count("spilledBytes", nbytes)
            while self._host_used > self.host_limit:
                victim = next(
                    (v for v in self._spillables.values()
                     if v.tier == "host" and v._host_accounted
                     and not v._disk_spill_failed
                     and not v._disk_spilling and v is not s), None)
                if victim is None:
                    break
                victim.spill_to_disk()  # decrements _host_used itself

    def _on_disk_spill(self, s: SpillableBatch, nbytes: int) -> None:
        self.metrics["spillToDiskBytes"] += nbytes
        _TM_SPILL_DISK.inc(nbytes)

    def _on_restore(self, s: SpillableBatch) -> None:
        with self._lock:
            self._host_used = max(0, self._host_used - s.nbytes)


# ---------------------------------------------------------------------------
# process-wide manager, configured per session conf
# ---------------------------------------------------------------------------

_manager: Optional[DeviceMemoryManager] = None
_manager_lock = threading.Lock()


def get_manager(conf=None) -> DeviceMemoryManager:
    """The process arbiter.  First caller's conf wins; a session with
    explicit memory confs replaces an unconfigured default."""
    global _manager
    replaced = False
    with _manager_lock:
        if _manager is None:
            _manager = _build(conf)
        elif conf is not None:
            cfg = _build(conf)
            if (cfg.budget, cfg.host_limit, cfg._inject_at,
                    cfg.retry_max_attempts, cfg.spill_root,
                    cfg.debug) != (
                    _manager.budget, _manager.host_limit,
                    _manager._inject_at, _manager.retry_max_attempts,
                    _manager.spill_root, _manager.debug):
                _manager = cfg
                replaced = True
        mgr = _manager
    if replaced:
        # a new manager orphans batches registered with the old one —
        # evict the device-resident scan cache so nothing keeps
        # accounting against the dead arbiter.  Outside _manager_lock:
        # eviction takes the scan-cache lock (tier 0) and each close
        # talks to its own batch's arbiter, never the module global.
        from spark_rapids_tpu.exec.basic import clear_scan_cache
        clear_scan_cache()
    return mgr


def peek_manager() -> Optional[DeviceMemoryManager]:
    """The process arbiter if one exists — never creates (the cancel
    reclamation path must not instantiate state as a side effect)."""
    return _manager


def reset_manager() -> None:
    global _manager
    with _manager_lock:
        _manager = None


# pull-based gauges over the CURRENT manager (0 before the first query
# builds one); producers pay nothing, the sampler reads at snapshot time
TM.REGISTRY.gauge(
    "tpuq_hbm_reserved_bytes", "bytes currently reserved in HBM",
    fn=lambda: _manager._reserved if _manager is not None else 0)
TM.REGISTRY.gauge(
    "tpuq_hbm_watermark_bytes", "peak reserved bytes (this manager)",
    fn=lambda: (_manager.metrics["peakReserved"]
                if _manager is not None else 0))
TM.REGISTRY.gauge(
    "tpuq_hbm_budget_bytes", "HBM budget the arbiter hands out",
    fn=lambda: _manager.budget if _manager is not None else 0)
TM.REGISTRY.gauge(
    "tpuq_host_spill_used_bytes", "host spill tier bytes in use",
    fn=lambda: _manager._host_used if _manager is not None else 0)
TM.REGISTRY.gauge(
    "tpuq_spillable_batches", "live registered spillable batches",
    fn=lambda: len(_manager._spillables) if _manager is not None else 0)


def _build(conf) -> DeviceMemoryManager:
    if conf is None:
        return DeviceMemoryManager()
    from spark_rapids_tpu import conf as C
    return DeviceMemoryManager(
        budget=conf.get(C.POOL_SIZE) or None,
        alloc_fraction=conf.get(C.MEMORY_FRACTION),
        host_limit=conf.get(C.HOST_SPILL_STORAGE),
        spill_path=conf.get(C.SPILL_PATH),
        inject_oom_at=conf.get(C.FAULT_INJECT),
        retry_max_attempts=conf.get(C.RETRY_MAX),
        debug=str(conf.get(C.MEMORY_DEBUG)).upper() == "STDOUT",
        conf=conf,
    )


# ---------------------------------------------------------------------------
# the retry framework [REF: RmmRapidsRetryIterator.scala :: withRetry]
# ---------------------------------------------------------------------------

def split_batch_in_half(batch: DeviceBatch) -> List[DeviceBatch]:
    """Halve a batch by row range (the splitSpillableInHalfByRows
    analog).  Static slicing — each half keeps a pow-2 capacity."""
    from spark_rapids_tpu.parallel.shuffle import slice_batch
    cap = batch.capacity
    if cap <= 1:
        raise SplitAndRetryOOM("cannot split a 1-row batch")
    half = cap // 2
    return [slice_batch(batch, 0, half), slice_batch(batch, half, half)]


def with_retry(
    inputs: Iterable[DeviceBatch],
    closure: Callable[[DeviceBatch], object],
    max_attempts: Optional[int] = None,
    manager: Optional[DeviceMemoryManager] = None,
    allow_split: bool = True,
):
    """Run ``closure`` over each input batch with OOM rollback.

    On ``RetryOOM``: spill registered spillables and re-run the same
    batch.  On ``SplitAndRetryOOM`` (or repeated RetryOOM): split the
    batch in half by rows and process the halves independently — the
    caller's closure must be merge-friendly (partial aggregates, sorted
    runs, ...).  Yields one result per processed (sub-)batch.

    Attempts default to the unified ``RetryPolicy``
    (``spark.rapids.tpu.retry.maxAttempts``), and every OOM retry is
    accounted as an ``alloc``-domain retry in
    ``tpuq_retry_total{domain="alloc"}`` — OOM rollback and device-call
    retries share the one policy.

    ``inputs`` is consumed LAZILY — one upstream batch is live at a
    time, so spilling actually frees HBM instead of fighting a pinned
    input list.
    """
    mgr = manager or get_manager()
    if max_attempts is None:
        max_attempts = R.get_policy().max_attempts
    it = iter(inputs)
    work: List[Tuple[DeviceBatch, int]] = []  # pending (sub-)batches
    while True:
        cancel.check()
        if work:
            batch, attempts = work.pop(0)
        else:
            batch = next(it, None)
            if batch is None:
                return
            attempts = 0
        try:
            yield closure(batch)
        except SplitAndRetryOOM:
            if not allow_split:
                raise
            mgr.metrics["splitRetries"] += 1
            _TM_SPLIT_RETRY.inc()
            R.note_retry("alloc")
            halves = split_batch_in_half(batch)
            work = [(h, attempts + 1) for h in halves] + work
        except RetryOOM:
            if attempts + 1 >= max_attempts:
                R.note_exhausted()
                raise
            R.note_retry("alloc")
            # free device pressure INCREMENTALLY: spill victims until
            # roughly this batch's working set is free, not the whole
            # pool (draining everything evicts the scan cache on the
            # first transient OOM and forces full re-materialization)
            freed, target = 0, max(batch.nbytes(), 1)
            for s in list(mgr._spillables.values()):
                if s.tier == "device":
                    freed += s.spill_to_host()
                    if freed >= target:
                        break
            if attempts >= 1 and allow_split and batch.capacity > 1:
                mgr.metrics["splitRetries"] += 1
                _TM_SPLIT_RETRY.inc()
                halves = split_batch_in_half(batch)
                work = [(h, attempts + 1) for h in halves] + work
            else:
                work.insert(0, (batch, attempts + 1))
