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

from typing import List, Tuple

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


def fill_next(cols, is_src: jnp.ndarray) -> tuple:
    """Per slot, each of ``cols`` at the first slot at or after it where
    ``is_src`` holds: a reversed keep-first scan, one pass for all the
    columns — gather- and scatter-free (XLA scatter lowers to a serial
    loop on TPU).  Slots past the last source get the LAST slot's values:
    the caller masks them (``match_fused``'s count is 0 there)."""
    def comb(a, b):
        *av, af = a
        *bv, bf = b
        return (*[jnp.where(bf, y, x) for x, y in zip(av, bv)], af | bf)
    return jax.lax.associative_scan(
        comb, (*cols, is_src), reverse=True)[:-1]


def _adjacent_neq(limbs: List[jnp.ndarray]) -> jnp.ndarray:
    """row i differs from row i-1 in any limb (row 0 → False)."""
    n = limbs[0].shape[0]
    neq = jnp.zeros((n,), jnp.bool_)
    for l in limbs:
        neq = neq | jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), l[1:] != l[:-1]])
    return neq


def merge_sorted(sorted_limb: jnp.ndarray, queries: jnp.ndarray,
                 side: str = "left", riders=()):
    """Table and queries stably sorted TOGETHER, once: ``(keys, perm,
    is_tab, before, moved)`` in the merged order — the keys, each slot's
    place in the concatenation, whether it is a table slot, the table
    slots ahead of it (a query's RANK in the table: a prefix count, no
    gather) and ``riders``, (query column, table column) pairs that
    come along as payload operands.

    Equals go by their place in the concatenation: queries first for
    ``"left"`` (a query sorts ahead of its equals in the table: the
    strict lower bound), table first for ``"right"``.
    """
    if queries.dtype != sorted_limb.dtype:  # concatenate would promote
        raise TypeError(f"merge_sorted: {queries.dtype} queries against "
                        f"a {sorted_limb.dtype} table")
    n, q = int(sorted_limb.shape[0]), int(queries.shape[0])
    left = side == "left"

    def cat(of_queries, of_table):
        return jnp.concatenate([of_queries, of_table] if left
                               else [of_table, of_queries])

    keys, perm, *moved = jax.lax.sort(
        (cat(queries, sorted_limb), jnp.arange(n + q, dtype=jnp.int32))
        + tuple(cat(a, b) for a, b in riders), num_keys=1, is_stable=True)
    is_tab = (perm >= q) if left else (perm < n)
    tab = is_tab.astype(jnp.int32)
    return keys, perm, is_tab, jnp.cumsum(tab) - tab, moved


def unmerge(perm: jnp.ndarray, cols, q: int, side: str = "left") -> list:
    """The queries' slots of merged-order ``cols``, back in query order:
    one sort on ``merge_sorted``'s permutation (distinct keys, so no
    stability to pay for) — the scatter-free way to invert it."""
    n = int(perm.shape[0]) - q
    res = jax.lax.sort((perm, *cols), num_keys=1, is_stable=False)
    return [c[:q] if side == "left" else c[n:] for c in res[1:]]


def rank_sorted(sorted_limb: jnp.ndarray, queries: jnp.ndarray,
                side: str = "left") -> jnp.ndarray:
    """``int32[q]``: per query, the table entries below it (``"left"``:
    the lower bound) or not above it (``"right"``:
    ``jnp.searchsorted(..., side="right")``'s integer).

    A lower bound is a rank, and a rank needs no gather: ``merge_sorted``
    counts it and ``unmerge`` brings it back to query order — two sorts
    of n + q slots where a bisection takes ⌈log2 n⌉ + 1 dependent takes
    of q indices (the chip's prices: docs/kernels.md, "Fused hash join").
    """
    q = int(queries.shape[0])
    _, perm, _, before, _ = merge_sorted(sorted_limb, queries, side)
    return unmerge(perm, [before], q, side)[0]


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
