"""Shared device primitives of the kernel plane.

[REF: libcudf's join/groupby kernels share one hashing core
 (``cudf::hashing::detail``) so the build side of a join and the probe
 table of a group-by agree bit-for-bit; this module is the TPU analog.]

Everything here is DEVICE code traced inside ``cached_kernel`` builders:
no host materialization, no data-dependent Python control flow (the
``kernel-purity`` lint rule gates exactly that).  The core primitive is
the **hash-grouped layout**: instead of stably sorting the full
multi-limb key encoding (sort operand count is the dominant TPU compile
cost — see ops/ordering.py), rows are stably sorted by ONE 64-bit hash
limb, the key limbs and the caller's columns follow in one row gather,
and group boundaries are recovered by comparing the full key limbs of
adjacent sorted rows.  A 64-bit collision between
distinct keys in the same batch is detected exactly (any offending pair
is adjacent after the hash sort) and surfaces as ``ok = False`` so the
dispatcher can fall back to the exact sort-based reference — the fused
backends are *probabilistically fast, deterministically correct*.

The hash itself is computed entirely in uint32 arithmetic (two parallel
murmur3-finalizer lanes with cross-mixing): TPU has no native 64-bit
path, and keeping the mix 32-bit makes the Pallas variant in
pallas_backend.py a line-for-line transcription.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

# murmur3 fmix32 constants — the exact-fallback ladder makes hash
# quality a latency knob, not a correctness one
_C1 = 0x85EBCA6B
_C2 = 0xC2B2AE35
_SEED_HI = 0x9E3779B9
_SEED_LO = 0x85EBCA77


def _fmix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 32-bit finalizer (wrapping uint32 arithmetic)."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(_C1)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(_C2)
    x = x ^ (x >> jnp.uint32(16))
    return x


def mix_rounds(hi: jnp.ndarray, lo: jnp.ndarray,
               wh: jnp.ndarray, wl: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold one 64-bit word (as two u32 lanes) into the running state.

    Two fmix32 lanes with cross-feedback: each output bit depends on
    every input bit of both words after the two rounds.  Pure uint32
    ops — this is the function pallas_backend.hash_pairs transcribes.
    """
    hi = hi ^ wh
    lo = lo ^ wl
    hi = _fmix32(hi + lo + jnp.uint32(_SEED_HI))
    lo = _fmix32(lo + hi + jnp.uint32(_SEED_LO))
    return hi, lo


def limbs_hashable(limbs: List[jnp.ndarray]) -> bool:
    """Trace-time gate: the hash path needs unsigned-integer limbs.

    A raw float64 limb (DoubleType keys ride one — no 64-bit bitcast
    compiles on TPU, see ops/ordering.py) cannot be hashed without the
    bitcast the encoding exists to avoid, so such key sets stay on the
    exact sort-based reference.  Static per kernel instance: limb
    dtypes are schema-determined, so this never retraces.
    """
    return all(jnp.issubdtype(l.dtype, jnp.unsignedinteger)
               for l in limbs)


def split_u64(limb: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """uint64 limb → (hi, lo) uint32 lanes (shift+convert, no bitcast)."""
    l64 = limb.astype(jnp.uint64)
    return ((l64 >> jnp.uint64(32)).astype(jnp.uint32),
            l64.astype(jnp.uint32))


def hash_limbs(limbs: List[jnp.ndarray],
               use_pallas: bool = False) -> jnp.ndarray:
    """64-bit hash of a row's fused key limbs, as a uint64 array.

    ``use_pallas`` routes the mixing loop through the Pallas VPU kernel
    (TPU backends); the jnp form is the bit-identical reference.
    """
    if use_pallas:
        from spark_rapids_tpu.kernels import pallas_backend as PB
        his = jnp.stack([split_u64(l)[0] for l in limbs])
        los = jnp.stack([split_u64(l)[1] for l in limbs])
        hi, lo = PB.hash_pairs(his, los)
    else:
        n = limbs[0].shape[0]
        hi = jnp.zeros((n,), jnp.uint32)
        lo = jnp.zeros((n,), jnp.uint32)
        for l in limbs:
            wh, wl = split_u64(l)
            hi, lo = mix_rounds(hi, lo, wh, wl)
    return (hi.astype(jnp.uint64) << jnp.uint64(32)) | lo.astype(
        jnp.uint64)


def seg_scan(values: jnp.ndarray, boundary: jnp.ndarray,
             op) -> jnp.ndarray:
    """Inclusive segmented scan (same combiner shape as
    exec.aggregate.segmented_scan, local so the kernel plane stays a
    leaf below the exec layer)."""
    def comb(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, op(av, bv)), af | bf
    v, _ = jax.lax.associative_scan(comb, (values, boundary))
    return v


def run_lengths(boundary: jnp.ndarray) -> jnp.ndarray:
    """Per-row length of the row's run (``boundary`` marks run starts).

    Forward segmented count, then a reversed keep-first scan broadcasts
    each run's final count back over the whole run — scatter-free (XLA
    scatter lowers to a serial loop on TPU).
    """
    n = boundary.shape[0]
    rn = seg_scan(jnp.ones((n,), jnp.int32), boundary, jnp.add)
    is_end = jnp.concatenate([boundary[1:], jnp.ones((1,), jnp.bool_)])
    filled = seg_scan(rn[::-1], is_end[::-1], lambda a, b: a)
    return filled[::-1]


def _adjacent_neq(limbs: List[jnp.ndarray]) -> jnp.ndarray:
    """row i differs from row i-1 in any limb (row 0 → False)."""
    n = limbs[0].shape[0]
    neq = jnp.zeros((n,), jnp.bool_)
    for l in limbs:
        neq = neq | jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), l[1:] != l[:-1]])
    return neq


def lower_bound(sorted_limb: jnp.ndarray, queries: jnp.ndarray,
                le: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """First index whose entry is >= the query (or > when ``le[q]``).

    Fixed-step branchless bisection (same shape as exec.join._lex_search
    but over ONE limb — the whole point of the hash layout).  ``le`` is
    a per-query flag switching to upper-bound counting.
    """
    import math
    n = int(sorted_limb.shape[0])
    steps = max(1, int(math.ceil(math.log2(max(n, 2)))) + 1)
    lo = jnp.zeros(queries.shape, jnp.int32)
    hi = jnp.full(queries.shape, n, jnp.int32)
    for _ in range(steps):
        mid = (lo + hi) >> 1
        v = jnp.take(sorted_limb, jnp.clip(mid, 0, n - 1))
        go_right = v < queries
        if le is not None:
            go_right = go_right | (le & (v == queries))
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return lo


def hash_group_layout(key_limbs: List[jnp.ndarray],
                      use_pallas: bool = False, payload=()):
    """Hash-grouped row layout: the fused group-by/build-side core.

    Returns ``(perm, sorted_key_limbs, boundary, sorted_hash, ok,
    moved)``: rows stably ordered by the 64-bit key hash (``perm``),
    group starts under that order (``boundary``, from FULL-key adjacent
    comparison), ``ok`` — False iff two adjacent sorted rows share the
    hash but not the key, i.e. a 64-bit collision made distinct keys
    non-contiguous — and ``payload``'s arrays in the sorted order.  Any
    colliding pair is adjacent after the hash sort, so the detection is
    exact; callers must fall back to the sort-based reference when
    ``ok`` is False (probability ~n²/2⁶⁴ per batch).

    The key limbs and the payload come into the hash order together
    (``ops.ordering.sort_rows``: one row gather, not a take a limb).

    Caller contract: ``limbs_hashable(key_limbs)`` is True, and the
    limbs encode the full grouping equivalence (nulls flagged, NaNs
    canonicalized, -0.0 normalized — ops/ordering.py does all three).
    """
    from spark_rapids_tpu.ops import ordering as ORD
    h = hash_limbs(key_limbs, use_pallas=use_pallas)
    nk = len(key_limbs)
    (sorted_h,), perm, moved = ORD.sort_rows(
        [h], list(key_limbs) + list(payload))
    kl_s, moved = moved[:nk], moved[nk:]
    same_h = jnp.concatenate([jnp.zeros((1,), jnp.bool_),
                              sorted_h[1:] == sorted_h[:-1]])
    key_neq = _adjacent_neq(kl_s)
    boundary = key_neq.at[0].set(True)
    ok = ~jnp.any(same_h & key_neq)
    return perm, kl_s, boundary, sorted_h, ok, moved
