"""Fused multi-column hash aggregation — single-pass hash group layout.

The jnp reference (exec.aggregate.segment_groupby) stably sorts the
full fused key encoding (up to GROUP_HASH_LIMB_CAP limbs, or a 2-limb
128-bit murmur for wide tuples) and diffs adjacent sorted rows for
group boundaries.  The fused backend replaces that multi-operand sort
with hash_layout.hash_group_layout: ONE 64-bit hash limb sorted, full
keys compared only between ADJACENT sorted rows — the same downstream
segmented scans then reduce the values.  Group ORDER under the fused
layout is hash order, not key order; Spark leaves a hash aggregate's
output order undefined, and the engine's merge passes re-group by key,
so only the layout — never the group content — differs from the
reference.  A 64-bit collision (distinct keys, same hash) is detected
exactly and surfaces as ``ok = False`` for the dispatcher's fallback
to the sort-based reference.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax.numpy as jnp

from spark_rapids_tpu.kernels import hash_layout as HL


def group_layout_fused(key_limbs: List[jnp.ndarray],
                       use_pallas: bool = False, payload=()
                       ) -> Optional[Tuple[jnp.ndarray, List[jnp.ndarray],
                                           jnp.ndarray, jnp.ndarray,
                                           list]]:
    """(perm, sorted_key_limbs, boundary, ok, moved payload) for a
    grouped batch, or None when the key limbs are unhashable (raw-f64
    limb: DoubleType grouping keys stay on the exact reference; static
    per instance).

    ``key_limbs`` is ops.ordering.group_sort_limbs' KEY limb set — the
    dead-row flag is fused into the first limb, so dead rows land in
    their own hash groups; the caller's live-row masking (num_groups,
    compaction rank) needs no change.  ``payload`` is the caller's
    columns, returned in the layout's row order.
    """
    if not HL.limbs_hashable(key_limbs):
        return None
    perm, kl_s, boundary, _, ok, moved = HL.hash_group_layout(
        key_limbs, use_pallas=use_pallas, payload=payload)
    return perm, kl_s, boundary, ok, moved
