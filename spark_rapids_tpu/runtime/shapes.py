"""The shape plane — canonical batch-shape bucketing.

THE compile-storm killer (SURVEY §7, ROADMAP item 4): every kernel in
this engine compiles per (op, schema, row-bucket), so the number of
DISTINCT buckets flowing through the exec pump bounds the number of XLA
compiles a sweep can trigger.  Most producers already emit pow-2
capacities, but join group slicing, sub-partitioning, and concat
trimming can emit stragglers — each a fresh bucket, each a fresh
compile of every downstream kernel.  This module pins every pumped
``DeviceBatch`` to a small canonical ladder of row buckets at the
operator boundary (exec/base.py wires it under the stats/trace pumps),
collapsing ``runtime/kernel_cache.py`` key shapes onto the ladder.

Padding is dead-row padding: appended rows carry ``sel=False`` (and
zeroed data/validity/lengths planes), which every kernel already
ignores — the same liveness contract filtering rides.  A compacted
batch stays compacted: pad rows extend the dead tail, so the
``compacted`` promise (live rows at the front) is preserved and
downstream consumers still skip the compaction kernel.

Policies (``spark.rapids.tpu.kernel.bucketing``):

* ``pow2``   — round capacity up to the next power of two, floored at
  ``spark.rapids.tpu.minBucketRows`` (the engine's native bucketing;
  makes stragglers conform).
* ``ladder`` — round up to the smallest rung of the explicit
  ``spark.rapids.tpu.kernel.bucketLadder`` list; capacities above the
  top rung (and rungs that would exceed
  ``spark.rapids.tpu.kernel.maxPadFraction`` of padding) fall back to
  pow2.
* ``off``    — pass batches through untouched.

The plane is observable end-to-end: bucket hits/misses and pad-waste
counters in the telemetry registry and per-op ``padded_rows`` in the
stats plane.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Optional, Tuple

from spark_rapids_tpu.runtime import telemetry as TM

_TM_HITS = TM.REGISTRY.counter(
    "tpuq_shape_bucket_hits_total",
    "pumped device batches whose capacity already sat on the bucket "
    "ladder (no padding)")
_TM_MISSES = TM.REGISTRY.counter(
    "tpuq_shape_bucket_misses_total",
    "pumped device batches padded up to a canonical bucket")
_TM_PAD_ROWS = TM.REGISTRY.counter(
    "tpuq_shape_pad_rows_total",
    "dead rows appended by shape-plane bucketing")
_TM_PAD_BYTES = TM.REGISTRY.counter(
    "tpuq_shape_pad_bytes_total",
    "physical bytes of shape-plane padding (pad-waste)")


@dataclasses.dataclass(frozen=True)
class ShapePolicy:
    """One immutable bucketing policy (the conf snapshot, parsed)."""

    mode: str = "off"                  # off | pow2 | ladder
    ladder: Tuple[int, ...] = ()       # strictly increasing rungs
    max_pad_fraction: float = 0.75     # ladder-rung pad budget
    min_bucket: int = 1 << 10

    @property
    def enabled(self) -> bool:
        return self.mode != "off"

    def bucket_for(self, capacity: int) -> int:
        """Canonical bucket (>= capacity) for a batch capacity.

        Ladder rungs are only taken within the pad budget; everything
        else (including capacities above the top rung) rounds pow2 —
        pow2 padding is at most half the bucket, so it always lands
        within the default budget and never needs its own check."""
        from spark_rapids_tpu.columnar.column import round_up_pow2
        capacity = max(int(capacity), 1)
        if self.mode == "ladder":
            for rung in self.ladder:
                if rung >= capacity:
                    if (rung - capacity) / rung <= self.max_pad_fraction:
                        return rung
                    break  # smallest fitting rung over budget: pow2
        return round_up_pow2(capacity, self.min_bucket)


# The active policy — module global, same pattern as lockdep.configure /
# telemetry.configure_sampler: the session snapshots conf once and every
# pump boundary reads one attribute.
_POLICY = ShapePolicy()
_LOCK = threading.Lock()


def configure(conf) -> ShapePolicy:
    """Install the policy from a RapidsConf snapshot (session init)."""
    from spark_rapids_tpu import conf as C
    mode = str(conf.get(C.KERNEL_BUCKETING)).lower()
    raw = str(conf.get(C.KERNEL_BUCKET_LADDER)).strip()
    ladder = tuple(int(x.strip()) for x in raw.split(",")) if raw else ()
    pol = ShapePolicy(
        mode=mode,
        ladder=ladder,
        max_pad_fraction=float(conf.get(C.KERNEL_MAX_PAD_FRACTION)),
        min_bucket=int(conf.get(C.MIN_BUCKET_ROWS)))
    global _POLICY
    with _LOCK:
        _POLICY = pol
    return pol


def current_policy() -> ShapePolicy:
    return _POLICY


def bucket_batch(batch, policy: Optional[ShapePolicy] = None):
    """(bucketed batch, padded row count) for one pumped DeviceBatch.

    Everything here is static host-side metadata — capacity and nbytes
    come from array SHAPES, so bucketing never forces a device sync.
    Non-DeviceBatch values (host batches crossing a transition) pass
    through untouched.
    """
    pol = policy if policy is not None else _POLICY
    if not pol.enabled:
        return batch, 0
    sel = getattr(batch, "sel", None)
    if sel is None:  # not a DeviceBatch
        return batch, 0
    cap = batch.capacity
    bucket = pol.bucket_for(cap)
    if bucket <= cap:
        _TM_HITS.inc()
        return batch, 0
    _TM_MISSES.inc()
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import DeviceBatch, _pad_col
    pad = bucket - cap
    cols = tuple(_pad_col(c, bucket) for c in batch.columns)
    out = DeviceBatch(batch.schema, cols,
                      jnp.pad(batch.sel, (0, pad)),
                      # dead-tail padding keeps live rows at the front,
                      # so the compacted promise survives
                      compacted=batch.compacted)
    _TM_PAD_ROWS.inc(pad)
    _TM_PAD_BYTES.inc(max(out.nbytes() - batch.nbytes(), 0))
    return out, pad


def retarget_bucket(rows: int) -> int:
    """Canonical bucket for an adaptive row target (adaptive plane's
    dynamic batch retargeting): when bucketing is on, snap the target
    to the ladder so retargeted reads coalesce onto compile-cached
    batch shapes instead of minting fresh (op, schema, bucket) keys;
    with the plane off, pow-2 round-up keeps the target on the native
    capacities producers already emit."""
    from spark_rapids_tpu.columnar.column import round_up_pow2
    rows = max(int(rows), 1)
    pol = _POLICY
    if pol.enabled:
        return pol.bucket_for(rows)
    return round_up_pow2(rows)


def snapshot() -> Tuple[int, int, int, int]:
    """(hits, misses, pad_rows, pad_bytes) so far."""
    return (_TM_HITS.value, _TM_MISSES.value,
            _TM_PAD_ROWS.value, _TM_PAD_BYTES.value)
