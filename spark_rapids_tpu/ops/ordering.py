"""Orderable-key encoding: any column → uint64 key columns whose unsigned
lexicographic order equals the SQL ordering.

This is the engine's device ordering primitive, shared by sort, sort-based
groupby, and sort-merge join (the roles cuDF's typed comparators play in
the reference [REF: cudf cpp/src/sort/ :: row lexicographic comparators]).
TPU-first: ``lax.sort`` is a fast multi-operand bitonic/merge sort but only
sorts ascending by unsigned key — so ordering semantics (descending,
nulls-first/last, NaN-last, -0.0 == 0.0 is NOT applied: Spark sorts by
total order where -0.0 < 0.0 is false; Spark treats them equal in
comparisons but sort is stable so either order is accepted by tests via
full-row comparison) are baked into the key encoding:

* signed ints: flip the sign bit → unsigned order == signed order
* floats: IEEE trick (negative → ~bits, else bits | sign) → total order
  with NaN greatest (Spark: NaN last ascending — matches)
* strings: big-endian packing of the padded byte matrix into ceil(W/8)
  uint64 limbs → unsigned limb order == bytewise (memcmp) order, which is
  Spark's UTF8String binary ordering
* bool/date/timestamp/decimal map through their physical ints
* descending: bitwise NOT of every key limb
* nulls: an extra leading key limb (0/1) positions nulls first or last
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.columnar.column import DeviceBatch, DeviceColumn


def _i_to_u64(x: jnp.ndarray) -> jnp.ndarray:
    """Signed int (any width) → order-preserving uint64."""
    x64 = x.astype(jnp.int64)
    return (x64.astype(jnp.uint64)) ^ jnp.uint64(1 << 63)


# A key "part" is (array, bits): an order-preserving unsigned value held
# in a uint64 array occupying the low `bits` bits — or (array, "f64") for
# a raw float64 limb (unfusable: no 64-bit bitcast compiles on TPU).
# ``fuse_parts`` then packs consecutive parts into as few uint64 sort
# operands as possible: sort operand count is the dominant TPU compile
# cost (~25-60 s per extra operand at 128k rows, measured), so a typical
# (dead, null, int32-key) triple becomes ONE operand instead of three.
Part = Tuple[jnp.ndarray, object]


def _int_part(x: jnp.ndarray, width: int, ascending: bool) -> Part:
    if width == 64:
        u = _i_to_u64(x)
        return ((~u if not ascending else u), 64)
    bias = jnp.int64(1 << (width - 1))
    u = (x.astype(jnp.int64) + bias).astype(jnp.uint64)
    if not ascending:
        u = u ^ jnp.uint64((1 << width) - 1)
    return (u, width)


def _flag_part(flag_is_one: jnp.ndarray) -> Part:
    return (flag_is_one.astype(jnp.uint64), 1)


def _f32_orderable_u32(x: jnp.ndarray, normalize_zero: bool) -> jnp.ndarray:
    import jax
    canon = jnp.where(jnp.isnan(x), jnp.asarray(np.nan, jnp.float32), x)
    if normalize_zero:
        canon = jnp.where(canon == 0.0, jnp.asarray(0.0, jnp.float32),
                          canon)
    bits = jax.lax.bitcast_convert_type(canon.astype(jnp.float32),
                                        jnp.uint32)
    neg = (bits >> jnp.uint32(31)) != 0
    return jnp.where(neg, ~bits, bits | jnp.uint32(1 << 31))


def fuse_parts(parts: List[Part]) -> List[jnp.ndarray]:
    """Pack consecutive uint parts into shared uint64 limbs (big-endian:
    earlier = more significant), flushing around raw-float parts."""
    limbs: List[jnp.ndarray] = []
    acc = None
    used = 0
    for arr, bits in parts:
        if bits == "f64":
            if acc is not None:
                limbs.append(acc)
                acc, used = None, 0
            limbs.append(arr)
            continue
        if acc is None:
            acc, used = arr, bits
        elif used + bits <= 64:
            acc = (acc << jnp.uint64(bits)) | arr
            used += bits
        else:
            limbs.append(acc)
            acc, used = arr, bits
    if acc is not None:
        limbs.append(acc)
    return limbs


def _string_parts(data: jnp.ndarray, lengths: jnp.ndarray) -> List[Part]:
    """uint8[B,W] + len → big-endian packed byte parts + a length part.

    Bytes beyond each row's length are zeroed so 'ab' < 'ab\\x00…' padding
    can't corrupt comparisons; the trailing length part disambiguates
    real NUL bytes ('a' vs 'a\\0').  The final byte chunk is annotated
    with its true bit width so short strings fuse with neighbors.
    """
    b, w = data.shape
    colidx = jnp.arange(w, dtype=jnp.int32)
    masked = jnp.where(colidx[None, :] < lengths[:, None], data,
                       jnp.uint8(0))
    parts: List[Part] = []
    for i in range(0, w, 8):
        chunk = masked[:, i:i + 8].astype(jnp.uint64)
        nbytes = chunk.shape[1]
        limb = jnp.zeros((b,), jnp.uint64)
        for j in range(nbytes):
            limb = (limb << jnp.uint64(8)) | chunk[:, j]
        parts.append((limb, 8 * nbytes))
    parts.append((lengths.astype(jnp.int64).astype(jnp.uint64), 32))
    return parts


_INT_WIDTH = {T.ByteType: 8, T.ShortType: 16, T.IntegerType: 32,
              T.DateType: 32, T.LongType: 64, T.TimestampType: 64}


def column_order_parts(col: DeviceColumn, ascending: bool = True,
                       nulls_first: bool = True,
                       distinguish_neg_zero: bool = True) -> List[Part]:
    """Encode one column as key parts (most-significant first).

    Parts are width-annotated unsigned values (fused downstream) except
    float64, which stays a RAW float limb: XLA's ``lax.sort`` comparator
    is IEEE total order (-NaN < -inf < … < -0/+0 < … < +inf < NaN, zeros
    tied), which matches Java ``Double.compare`` (Spark's ordering) once
    NaNs are canonicalized and the zero tie is broken by a trailing
    sign part.  Raw f64 avoids 64-bit bitcasts, which the TPU
    x64-rewrite pass cannot compile (probed on the real chip); f32 CAN
    bitcast, so it rides orderable u32 bits.
    """
    dt = col.dtype
    parts: List[Part]
    if isinstance(dt, (T.StringType, T.BinaryType)):
        parts = _string_parts(col.data, col.lengths)
        if not ascending:
            parts = [(a ^ jnp.uint64((1 << b) - 1), b) for a, b in parts]
    elif isinstance(dt, T.FloatType):
        u = _f32_orderable_u32(col.data,
                               normalize_zero=not distinguish_neg_zero)
        if not ascending:
            u = ~u
        parts = [(u.astype(jnp.uint64), 32)]
    elif isinstance(dt, T.DoubleType):
        # NaN placement rides its own part: XLA negation does not flip
        # NaN's sign, so descending-by-negation alone would sort NaN
        # last instead of first.  Spark: NaN greatest.
        isn = jnp.isnan(col.data)
        nan_part = _flag_part(isn if ascending else ~isn)
        zero = jnp.zeros((), col.data.dtype)
        val = jnp.where(isn, zero, col.data)
        parts = [nan_part, (val if ascending else -val, "f64")]
        if distinguish_neg_zero:
            # XLA's sort treats -0.0 == 0.0; Spark orders -0.0 < 0.0.
            # signbit needs a bitcast, so detect the sign via 1/x.
            neg_zero = (col.data == zero) & ((jnp.ones(
                (), col.data.dtype) / col.data) < zero)
            parts.append(_flag_part(~neg_zero if ascending else neg_zero))
    elif isinstance(dt, T.BooleanType):
        parts = [(col.data.astype(jnp.uint64)
                  if ascending else (~col.data).astype(jnp.uint64), 1)]
    elif isinstance(dt, T.DecimalType):
        if dt.precision > T.DecimalType.MAX_LONG_DIGITS:
            # decimal128 [B,2]: signed-biased hi limb, then the lo
            # limb's raw (unsigned-ordered) bit pattern
            h = col.data[:, 0]
            l = col.data[:, 1]
            hp = _int_part(h, 64, ascending)
            lu = l.astype(jnp.uint64)
            if not ascending:
                lu = ~lu
            parts = [hp, (lu, 64)]
        else:
            parts = [_int_part(col.data, 64, ascending)]
    else:  # integral, date, timestamp
        parts = [_int_part(col.data, _INT_WIDTH[type(dt)], ascending)]
    # null part: orders independently of direction: nulls_first ⇒ nulls 0
    if col.validity is not None:
        np_ = _flag_part(col.validity if nulls_first else ~col.validity)
        # also zero data parts of nulls for deterministic grouping
        parts = [(jnp.where(col.validity, a, jnp.zeros((), a.dtype)), b)
                 for a, b in parts]
        parts = [np_] + parts
    return parts


def column_order_keys(col: DeviceColumn, ascending: bool = True,
                      nulls_first: bool = True,
                      distinguish_neg_zero: bool = True
                      ) -> List[jnp.ndarray]:
    """Single-column convenience wrapper: encode + fuse."""
    return fuse_parts(column_order_parts(
        col, ascending, nulls_first, distinguish_neg_zero))


def limb_neq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Inequality under the grouping equivalence: NaN == NaN (one group),
    and IEEE -0.0 == 0.0 (Spark normalizes float group keys)."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        return (a != b) & ~(jnp.isnan(a) & jnp.isnan(b))
    return a != b


def batch_group_parts(cols: List[DeviceColumn]) -> List[Part]:
    """Key parts for GROUP BY (direction irrelevant; nulls one group;
    -0.0 and 0.0 one group — Spark normalizes float grouping keys)."""
    out: List[Part] = []
    for c in cols:
        out.extend(column_order_parts(c, True, True,
                                      distinguish_neg_zero=False))
    return out


# above this many fused limbs, group sorts switch to the 128-bit
# key-tuple hash: lax.sort compile cost grows superlinearly PER OPERAND
# on TPU (measured: ~21 s at 2 operands; a ~10-limb multi-string key
# set ran >25 min without finishing)
GROUP_HASH_LIMB_CAP = 3


def group_sort_limbs(cols: List[DeviceColumn], sel,
                     tail_parts: List[Part] = ()
                     ) -> Tuple[List[jnp.ndarray], List[jnp.ndarray]]:
    """(sort limbs, key-only limbs) for GROUP BY segmentation.

    Narrow key tuples keep the exact lexicographic encoding (group
    output order = key order, stable for existing behavior), with any
    ``tail_parts`` (contrib flags, value order) fused into the same
    limb set's spare bits.  WIDE tuples (fused encoding >
    GROUP_HASH_LIMB_CAP limbs — e.g. several string keys, the TPC-H
    q10 shape) sort by a 128-bit hash of the normalized key tuple
    instead: grouping only needs equal-keys-contiguous, a hash
    aggregate's group order is undefined in Spark anyway, and distinct
    keys merge only on a full 128-bit collision (~2^-128 — four
    murmur3 passes with independent seeds).  Boundary detection must
    use the returned KEY limbs (tail parts must not split groups).
    """
    key_parts = [_flag_part(~sel)] + batch_group_parts(cols)
    exact = fuse_parts(key_parts)
    if len(exact) <= GROUP_HASH_LIMB_CAP:
        if not tail_parts:
            return exact, exact
        return fuse_parts(key_parts + list(tail_parts)), exact
    from spark_rapids_tpu.ops import hashing as HH
    n = int(sel.shape[0])

    def tuple_hash(seed: int) -> jnp.ndarray:
        h = jnp.full((n,), np.uint32(seed), jnp.uint32)
        for c in cols:
            dt = c.dtype
            data = c.data
            valid = c.valid_mask()
            # the per-column null flag ALWAYS mixes in: hash_column
            # leaves h unchanged for null rows, so without this,
            # (null, x) and (x, null) would hash identically on every
            # seed — a systematic merge, not a 2^-128 collision
            h = HH._mix_h1(h, HH._mix_k1(valid.astype(jnp.uint32),
                                         jnp), jnp)
            if isinstance(dt, T.DoubleType):
                from spark_rapids_tpu.parallel.shuffle import (
                    _hash_f64_tpu_safe)
                h = jnp.where(valid, _hash_f64_tpu_safe(data, h), h)
                continue
            if isinstance(dt, T.FloatType):
                data = jnp.where(data == 0.0,
                                 jnp.zeros((), data.dtype), data)
            h = HH.hash_column((data, c.lengths), dt, h, valid, jnp)
        return h

    h = [tuple_hash(s).astype(jnp.uint64)
         for s in (42, 0x5F3759DF, 0x9E3779B9, 0x85EBCA6B)]
    h64a = (h[0] << jnp.uint64(32)) | h[1]
    h64b = (h[2] << jnp.uint64(32)) | h[3]
    key_limbs = fuse_parts(
        [_flag_part(~sel), (h64a, 64), (h64b, 64)])
    if not tail_parts:
        return key_limbs, key_limbs
    return key_limbs + fuse_parts(list(tail_parts)), key_limbs


def sort_by_keys(limbs: List[jnp.ndarray]
                 ) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """Stable lexicographic sort; returns (sorted limbs, permutation).

    The trailing iota doubles as stabilizer AND permutation output:
    nothing but the keys rides the sort, because every operand of a
    1 M-row ``lax.sort`` costs the TPU compiler 12 s (measured on the
    v5e's host, docs/kernels.md "Moving rows").  Callers that want
    whole columns in the sorted order use ``sort_rows`` — never a
    ``jnp.take(x, perm)`` a column.
    """
    import jax
    n = limbs[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    operands = tuple(limbs) + (iota,)
    res = jax.lax.sort(operands, num_keys=len(limbs) + 1)
    return list(res[:len(limbs)]), res[-1]


def sort_rows(limbs: List[jnp.ndarray], payload=()
              ) -> Tuple[List[jnp.ndarray], jnp.ndarray, list]:
    """``sort_by_keys`` that brings rows along: returns (sorted limbs,
    permutation, moved payload), ``moved[i]`` being ``payload[i]`` in
    the sorted order (``take_rows``)."""
    sorted_limbs, perm = sort_by_keys(limbs)
    return sorted_limbs, perm, take_rows(payload, perm)


# widest row matrix one gather moves: on the v5e a uint32[1 M, K] row
# gather takes 4.2 ms at K = 8, 7.5 at 16, 9.6 at 24 — and 39.5 at 32
# (chip run, PR 27; a 1-D uint32[1 M] take is 8.2 ms)
_MAX_STACK = 24


def _take_stacked(cols: List[jnp.ndarray], perm: jnp.ndarray):
    """``jnp.take(jnp.stack(cols, 1), perm, 0)``, in as few row gathers
    of at most ``_MAX_STACK`` columns as ``cols`` needs."""
    if not cols:
        return None
    n = -(-len(cols) // _MAX_STACK)  # gathers
    k = -(-len(cols) // n)           # columns a gather, balanced
    parts = [jnp.take(jnp.stack(cols[i:i + k], axis=1), perm, axis=0)
             for i in range(0, len(cols), k)]
    return parts[0] if n == 1 else jnp.concatenate(parts, axis=1)


def take_rows(arrays, perm: jnp.ndarray) -> list:
    """``[jnp.take(x, perm, axis=0) for x in arrays]``, bit for bit, in
    two gathers (more only past ``_MAX_STACK`` columns) instead of one
    a column.

    A gather by a permutation pays per INDEX on the TPU, not per byte
    (docs/kernels.md "Moving rows": ~16 ms a 1 M-row column whatever
    its width), so the columns are stacked into row matrices and each
    row moves once: every integer kind packs into the columns of ONE
    ``uint32[B, K]`` — booleans a bit each, ``uint8[B, W]`` byte
    matrices (strings) four bytes a column, 8/16-bit integers widened,
    64-bit integers as two halves, ``float32`` by its bits — and the
    ``float64`` columns stack into one ``float64[B, K]`` (no 64-bit
    bitcast compiles on TPU, so doubles cannot join the words).  All
    shift, convert and 32-bit bitcast: exact.  ``None`` entries pass
    through, an array given twice moves once, other 2-D columns
    (decimal128 ``int64[B, 2]``) go column by column.
    """
    import jax
    words: List[jnp.ndarray] = []    # uint32[B] columns
    doubles: List[jnp.ndarray] = []  # float64[B] columns
    flags: List[jnp.ndarray] = []    # bool[B], 32 to a word
    u32, u64 = jnp.uint32, jnp.uint64

    def word(x) -> int:
        words.append(x)
        return len(words) - 1

    def pack(x):
        """Queue ``x``'s bits; return the recipe ``unpack`` reads."""
        dt = x.dtype
        if x.ndim == 2 and dt == jnp.uint8:
            w = x.shape[1]
            cols = []
            for i in range(0, w, 4):
                acc = x[:, i].astype(u32)
                for j in range(1, min(4, w - i)):
                    acc = acc | (x[:, i + j].astype(u32) << u32(8 * j))
                cols.append(word(acc))
            return ("bytes", cols, w)
        if x.ndim == 2:
            return ("cols", [pack(x[:, j]) for j in range(x.shape[1])])
        if dt == jnp.bool_:
            flags.append(x)
            return ("flag", len(flags) - 1)
        if dt == jnp.float64:
            doubles.append(x)
            return ("double", len(doubles) - 1)
        if dt.itemsize == 8:
            bits = x.astype(u64)
            lo = word(bits.astype(u32))
            return ("wide", word((bits >> u64(32)).astype(u32)), lo, dt)
        if dt.itemsize == 4:
            return ("word", word(jax.lax.bitcast_convert_type(x, u32)), dt)
        if not jnp.issubdtype(dt, jnp.integer):
            raise TypeError(f"take_rows cannot pack a {dt} column")
        # 8/16-bit integers: sign-extend or zero-extend, narrow back
        return ("narrow", word(x.astype(jnp.int32).astype(u32)), dt)

    recipes = {}
    for x in arrays:
        if x is not None and id(x) not in recipes:
            recipes[id(x)] = pack(x)
    first_flag_word = len(words)
    for i in range(0, len(flags), 32):
        acc = flags[i].astype(u32)
        for j, f in enumerate(flags[i + 1:i + 32], 1):
            acc = acc | (f.astype(u32) << u32(j))
        words.append(acc)
    w_m = _take_stacked(words, perm)
    d_m = _take_stacked(doubles, perm)

    def unpack(r):
        kind = r[0]
        if kind == "bytes":
            _, cols, w = r
            return jnp.stack(
                [(w_m[:, cols[i // 4]] >> u32(8 * (i % 4))
                  ).astype(jnp.uint8) for i in range(w)], axis=1)
        if kind == "cols":
            return jnp.stack([unpack(c) for c in r[1]], axis=1)
        if kind == "flag":
            bits = w_m[:, first_flag_word + r[1] // 32]
            return ((bits >> u32(r[1] % 32)) & u32(1)) != 0
        if kind == "double":
            return d_m[:, r[1]]
        if kind == "wide":
            _, hi, lo, dt = r
            return ((w_m[:, hi].astype(u64) << u64(32))
                    | w_m[:, lo].astype(u64)).astype(dt)
        if kind == "word":
            return jax.lax.bitcast_convert_type(w_m[:, r[1]], r[2])
        assert kind == "narrow"
        return w_m[:, r[1]].astype(jnp.int32).astype(r[2])

    out = {k: unpack(r) for k, r in recipes.items()}
    return [None if x is None else out[id(x)] for x in arrays]


# ----------------------------------------------------------------------------
# Host (numpy oracle) twin
# ----------------------------------------------------------------------------

def np_order_keys(data: np.ndarray, validity: Optional[np.ndarray],
                  dt: T.DataType, ascending: bool = True,
                  nulls_first: bool = True) -> List[np.ndarray]:
    if isinstance(dt, (T.StringType, T.BinaryType)):
        # host strings are object arrays — map to sortable tuples via bytes
        enc = np.array([
            v.encode() if isinstance(v, str) else bytes(v) for v in data
        ], dtype=object)
        mx = max((len(v) for v in enc), default=0)
        limbs = []
        padded = np.zeros((len(enc), mx + 1), dtype=np.uint8)
        for i, v in enumerate(enc):
            padded[i, :len(v)] = np.frombuffer(v, np.uint8)
        wpad = (-(mx + 1)) % 8
        padded = np.pad(padded, ((0, 0), (0, wpad)))
        for i in range(padded.shape[1] // 8):
            limb = np.zeros(len(enc), np.uint64)
            for j in range(8):
                limb = (limb << np.uint64(8)) | padded[:, i * 8 + j].astype(np.uint64)
            limbs.append(limb)
        limbs.append(np.array([len(v) for v in enc], np.uint64))
    elif isinstance(dt, T.DecimalType) and data.dtype == object:
        # decimal128 host rep: python ints — split to biased hi + lo
        hi = np.array([int(v) >> 64 for v in data], dtype=np.int64)
        lo = np.array([int(v) & 0xFFFFFFFFFFFFFFFF for v in data],
                      dtype=np.uint64)
        hi_u = (hi.astype(np.int64) ^ np.int64(-(1 << 63))).view(
            np.uint64)
        limbs = [hi_u, lo]  # the shared tail applies the desc flip
    elif isinstance(dt, T.FloatType):
        canon = np.where(np.isnan(data), np.float32(np.nan),
                         data.astype(np.float32))
        bits = canon.view(np.uint32)
        neg = (bits >> np.uint32(31)) != 0
        limbs = [np.where(neg, ~bits, bits | np.uint32(1 << 31)).astype(np.uint64)]
    elif isinstance(dt, T.DoubleType):
        canon = np.where(np.isnan(data), np.nan, data.astype(np.float64))
        bits = canon.view(np.uint64)
        neg = (bits >> np.uint64(63)) != 0
        limbs = [np.where(neg, ~bits, bits | np.uint64(1 << 63))]
    elif isinstance(dt, T.BooleanType):
        limbs = [data.astype(np.uint64)]
    else:
        limbs = [(data.astype(np.int64).view(np.uint64)) ^ np.uint64(1 << 63)]
    if not ascending:
        limbs = [~l for l in limbs]
    if validity is not None:
        nl = np.where(validity, np.uint64(1 if nulls_first else 0),
                      np.uint64(0 if nulls_first else 1))
        limbs = [np.where(validity, l, np.uint64(0)) for l in limbs]
        limbs = [nl] + limbs
    return limbs
