"""Device columnar data model — the ``GpuColumnVector`` analog.

[REF: sql-plugin/../GpuColumnVector.java :: GpuColumnVector,
 RapidsHostColumnVector] — but re-designed TPU-first instead of mirroring
cuDF's pointer-based layout:

* Every device column is a set of **fixed-shape** jax arrays padded to a
  power-of-two row bucket, so each (op, schema, bucket) pair compiles once
  and the XLA executable cache stays hot.  This is THE core TPU-idiom
  decision (SURVEY.md §7): cuDF kernels handle dynamic sizes natively, XLA
  wants static shapes.
* Row liveness is a boolean ``sel`` mask on the batch (covers both padding
  and not-yet-compacted filter results).  Data-dependent row counts never
  escape into shapes; compaction happens at deliberate points (shuffle,
  join build, host transfer) via a stable sort on the mask.
* Strings/binary are padded byte matrices ``uint8[B, W]`` + ``lengths
  int32[B]`` rather than cuDF's offset+chars layout — irregular layouts are
  hostile to the MXU/VPU; a padded matrix vectorizes substring/compare/hash.
* Decimals (precision <= 18) are scaled int64.
* Null validity is a separate ``bool[B]`` mask (True = valid), independent
  of ``sel``.

Host representation is a ``pyarrow.Table`` — the host mirror / transfer
format (the JCudf/host-column analog), and what the CPU-fallback operators
consume.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu.columnar import dtypes as T
from spark_rapids_tpu.runtime.device import ensure_initialized


def round_up_pow2(n: int, min_bucket: int = 1024) -> int:
    """Row bucket for n rows: next power of two, floored at min_bucket."""
    b = max(int(min_bucket), 1)
    while b < n:
        b <<= 1
    return b


@dataclasses.dataclass
class DeviceColumn:
    """One SQL column on device.

    data:     jnp array [B] (fixed width types) or uint8 [B, W]
              (string/binary) or elem[B, W] (array<numeric>)
    validity: jnp bool [B], True = valid; None = all valid
    lengths:  jnp int32 [B] for string/binary/array; None otherwise
    evalid:   jnp bool [B, W] element validity for array columns whose
              elements may be null; None = all elements valid
    """

    dtype: T.DataType
    data: jax.Array
    validity: Optional[jax.Array] = None
    lengths: Optional[jax.Array] = None
    evalid: Optional[jax.Array] = None

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def is_string(self) -> bool:
        return self.lengths is not None

    def valid_mask(self) -> jax.Array:
        if self.validity is None:
            return jnp.ones((self.capacity,), dtype=jnp.bool_)
        return self.validity

    def with_validity(self, validity: Optional[jax.Array]) -> "DeviceColumn":
        return DeviceColumn(self.dtype, self.data, validity, self.lengths,
                            self.evalid)

    def gather(self, idx: jax.Array) -> "DeviceColumn":
        """Row gather, one take a leaf: what ``sort_batch``, the window's
        partition sort, ``explode``'s row index, the join's output
        gather and ``partition_layout`` still move rows by (compaction
        and the out-of-core split go through ``ops.ordering.take_rows``:
        docs/kernels.md "Moving rows")."""
        data = jnp.take(self.data, idx, axis=0)
        validity = None if self.validity is None else jnp.take(self.validity, idx)
        lengths = None if self.lengths is None else jnp.take(self.lengths, idx)
        evalid = None if self.evalid is None else jnp.take(
            self.evalid, idx, axis=0)
        return DeviceColumn(self.dtype, data, validity, lengths, evalid)

    def nbytes(self) -> int:
        n = self.data.size * self.data.dtype.itemsize
        if self.validity is not None:
            n += self.validity.size
        if self.lengths is not None:
            n += self.lengths.size * 4
        if self.evalid is not None:
            n += self.evalid.size
        return n


def _col_flatten(c: DeviceColumn):
    return (c.data, c.validity, c.lengths, c.evalid), c.dtype


def _col_unflatten(dtype, children):
    data, validity, lengths, evalid = children
    return DeviceColumn(dtype, data, validity, lengths, evalid)


jax.tree_util.register_pytree_node(DeviceColumn, _col_flatten, _col_unflatten)


@dataclasses.dataclass
class DeviceBatch:
    """A columnar batch on device — the ``ColumnarBatch`` of this engine.

    columns are positional; ``schema`` carries names/types (static metadata).
    ``sel`` is the live-row mask: padding rows and filtered-out rows are
    False.  All operators consume/produce ``sel`` instead of changing shapes.

    ``compacted`` (static metadata) promises live rows sit at the front
    (sel == arange < n) — lets consumers skip the compaction kernel.
    """

    schema: T.StructType
    columns: Tuple[DeviceColumn, ...]
    sel: jax.Array  # bool[B]
    compacted: bool = False

    @property
    def capacity(self) -> int:
        return int(self.sel.shape[0])

    def num_rows(self) -> jax.Array:
        """Live row count (device scalar)."""
        return jnp.sum(self.sel.astype(jnp.int32))

    def num_rows_host(self) -> int:
        return int(self.num_rows())

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]

    def column_by_name(self, name: str) -> DeviceColumn:
        return self.columns[self.schema.field_index(name)]

    def with_columns(self, cols, schema=None) -> "DeviceBatch":
        return DeviceBatch(schema or self.schema, tuple(cols), self.sel)

    def with_sel(self, sel: jax.Array) -> "DeviceBatch":
        return DeviceBatch(self.schema, self.columns, sel)

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.columns) + self.sel.size


def _batch_flatten(b: DeviceBatch):
    return (b.columns, b.sel), (b.schema, b.compacted)


def _batch_unflatten(aux, children):
    columns, sel = children
    schema, compacted = aux
    return DeviceBatch(schema, tuple(columns), sel, compacted)


jax.tree_util.register_pytree_node(DeviceBatch, _batch_flatten, _batch_unflatten)


# ---------------------------------------------------------------------------
# Compaction: gather live rows to the front (stable).  The deliberate
# dynamic→static boundary; called before shuffle/join-build/host transfer.
# ---------------------------------------------------------------------------

def live_bucket(rows: int, capacity: int) -> int:
    """Slots a compaction keeps for ``rows`` live rows of a
    ``capacity``-slot batch: their pow-2 bucket, floored at 8 — what
    ``_concat_compacted_fast`` would cut the batch to anyway."""
    return min(capacity, max(8, round_up_pow2(max(rows, 1), 8)))


def _compact_order(sel: jax.Array):
    # Stable argsort on "dead" flag: live rows first, in order, then
    # the dead ones.  One lax.sort, in a program of its own keyed by
    # capacity alone: a 1 M-row sort compiles for tens of seconds, so
    # no schema and no bucket may multiply it.
    order = jnp.argsort((~sel).astype(jnp.int8), stable=True)
    return order, jnp.sum(sel.astype(jnp.int32))


def _compact_take(bucket: int):
    def run(batch: DeviceBatch, order, count) -> DeviceBatch:
        from spark_rapids_tpu.ops.ordering import take_rows
        leaves, columns = jax.tree_util.tree_flatten(batch.columns)
        # a gather pays per index: only the bucket's, and every leaf
        # in one packed row gather (docs/kernels.md "Moving rows")
        cols = jax.tree_util.tree_unflatten(
            columns, take_rows(leaves, order[:bucket]))
        sel = jnp.arange(bucket, dtype=jnp.int32) < count
        return DeviceBatch(batch.schema, cols, sel, compacted=True)
    return run


def compact(batch: DeviceBatch, rows: Optional[int] = None) -> DeviceBatch:
    """Live rows to the front, stable.  ``rows`` is the batch's live
    count where the caller has measured it: the result then keeps their
    bucket (``live_bucket``) of the capacity, and only that many rows
    move.  Rows past the count hold what the order names there (dead
    rows, in order), masked by ``sel``."""
    if batch.compacted:
        return batch
    cap = batch.capacity
    if rows is not None and rows >= cap:
        # every slot live: compacted by definition, nothing to launch
        return DeviceBatch(batch.schema, batch.columns, batch.sel,
                           compacted=True)
    bucket = cap if rows is None else live_bucket(rows, cap)
    from spark_rapids_tpu.runtime.kernel_cache import (
        cached_kernel, fingerprint)
    order, count = cached_kernel(
        ("compact_order",), lambda: _compact_order)(batch.sel)
    return cached_kernel(
        ("compact_take", fingerprint(batch.schema), bucket),
        lambda: _compact_take(bucket))(batch, order, count)


# ---------------------------------------------------------------------------
# Host (pyarrow) <-> device conversion — the Row/ColumnarToRow analog pair
# [REF: GpuRowToColumnarExec.scala, GpuColumnarToRowExec.scala]
# ---------------------------------------------------------------------------

def _string_to_matrix(arr: pa.Array) -> Tuple[np.ndarray, np.ndarray]:
    """Arrow string/binary array -> (uint8[B,W] matrix, int32 lengths)."""
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    if pa.types.is_large_string(arr.type) or pa.types.is_large_binary(arr.type):
        arr = arr.cast(pa.string() if pa.types.is_large_string(arr.type) else pa.binary())
    n = len(arr)
    # offsets/data straight from arrow buffers; nulls handled via validity
    buffers = arr.buffers()
    offs = np.frombuffer(buffers[1], dtype=np.int32, count=n + 1, offset=arr.offset * 4)
    data = np.frombuffer(buffers[2], dtype=np.uint8) if buffers[2] is not None else np.zeros(0, np.uint8)
    lengths = (offs[1:] - offs[:-1]).astype(np.int32)
    null_mask = np.asarray(arr.is_null())
    lengths = np.where(null_mask, 0, lengths).astype(np.int32)
    w = round_up_pow2(int(lengths.max()) if n else 1, 8)
    mat = np.zeros((n, w), dtype=np.uint8)
    total = int(lengths.sum())
    if total:
        starts = np.concatenate([[0], np.cumsum(lengths)[:-1]]).astype(np.int64)
        row_idx = np.repeat(np.arange(n), lengths)
        col_idx = np.arange(total) - np.repeat(starts, lengths)
        src_pos = np.repeat(offs[:-1].astype(np.int64), lengths) + col_idx
        mat[row_idx, col_idx] = data[src_pos]
    return mat, lengths


def _matrix_to_string(mat: np.ndarray, lengths: np.ndarray,
                      validity: Optional[np.ndarray], binary: bool) -> pa.Array:
    n, w = mat.shape
    lengths = lengths.astype(np.int64)
    col = np.arange(w)[None, :]
    mask2d = col < lengths[:, None]
    flat = mat[mask2d]
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    typ = pa.binary() if binary else pa.string()
    null_buf = None
    if validity is not None and not validity.all():
        null_buf = pa.py_buffer(np.packbits(validity, bitorder="little").tobytes())
    return pa.Array.from_buffers(
        typ, n,
        [null_buf, pa.py_buffer(offsets.tobytes()), pa.py_buffer(flat.tobytes())],
        null_count=-1 if null_buf is not None else 0,
    )


def _list_to_matrix(arr: pa.Array, elem_dt: T.DataType):
    """Arrow list array -> (elem[B, W] padded matrix, int32 lengths,
    optional bool[B, W] element validity)."""
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    if pa.types.is_large_list(arr.type):
        arr = arr.cast(pa.list_(arr.type.value_type))
    n = len(arr)
    offs = np.asarray(arr.offsets)
    lengths = (offs[1:] - offs[:-1]).astype(np.int32)
    lengths = np.where(np.asarray(arr.is_null()), 0, lengths)
    npdt = T.to_numpy_dtype(elem_dt)
    fill = pa.scalar(False if isinstance(elem_dt, T.BooleanType) else 0,
                     type=arr.type.value_type)
    values = np.asarray(arr.values.fill_null(fill)).astype(npdt, copy=False)
    evalues = None
    if arr.values.null_count:
        evalues = ~np.asarray(arr.values.is_null())
    w = round_up_pow2(int(lengths.max()) if n else 1, 1)
    mat = np.zeros((n, w), dtype=npdt)
    emask = None if evalues is None else np.ones((n, w), dtype=bool)
    total = int(lengths.sum())
    if total:
        row_idx = np.repeat(np.arange(n), lengths)
        col_idx = (np.arange(total)
                   - np.repeat(np.cumsum(lengths) - lengths, lengths))
        src = (np.repeat(offs[:-1].astype(np.int64), lengths)
               + col_idx)
        mat[row_idx, col_idx] = values[src]
        if emask is not None:
            emask[row_idx, col_idx] = evalues[src]
    return mat, lengths, emask


def _decimal_to_int64(arr: pa.Array) -> np.ndarray:
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    buf = arr.buffers()[1]
    raw = np.frombuffer(buf, dtype=np.int64, count=2 * len(arr),
                        offset=arr.offset * 16)
    low, high = raw[0::2].copy(), raw[1::2]
    # precision<=18 fits in the low limb; high must be sign extension
    if not np.array_equal(high, low >> 63):
        raise OverflowError("decimal value exceeds 18 digits")
    return low


def _decimal_to_hilo(arr: pa.Array) -> np.ndarray:
    """decimal128 arrow column -> int64[n, 2] (hi, lo bit patterns)."""
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    buf = arr.buffers()[1]
    raw = np.frombuffer(buf, dtype=np.int64, count=2 * len(arr),
                        offset=arr.offset * 16)
    out = np.empty((len(arr), 2), dtype=np.int64)
    out[:, 0] = raw[1::2]  # hi
    out[:, 1] = raw[0::2]  # lo (bit pattern)
    return out


def arrow_column_to_device(arr, dt: T.DataType) -> DeviceColumn:
    ensure_initialized()
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type) and (
            not isinstance(dt, (T.StringType, T.BinaryType))
            or len(arr.dictionary) == 0
            or arr.dictionary.null_count > 0):
        # device dict decode handles only string dictionaries with no
        # null VALUES (index-level nulls are fine); everything else
        # decodes to plain first — is_null() on a DictionaryArray does
        # NOT see nulls stored in the dictionary values
        arr = arr.cast(arr.type.value_type)
    null_mask = np.asarray(arr.is_null())
    validity_np = ~null_mask if null_mask.any() else None

    if (pa.types.is_dictionary(arr.type)
            and isinstance(dt, (T.StringType, T.BinaryType))):
        # device dictionary DECODE [REF: SURVEY N6 phase-2]: transfer
        # int32 indices + the (small) dictionary byte matrix and expand
        # with a device gather — H2D bytes drop from n*W to n*4 + D*W
        idx = np.asarray(arr.indices.fill_null(0)).astype(np.int32)
        dmat, dlens = _string_to_matrix(arr.dictionary)
        d_idx = jnp.asarray(idx)
        data = jnp.take(jnp.asarray(dmat), d_idx, axis=0)
        lengths = jnp.take(jnp.asarray(dlens), d_idx)
        return DeviceColumn(
            dt, data,
            None if validity_np is None else jnp.asarray(validity_np),
            lengths)

    if isinstance(dt, (T.StringType, T.BinaryType)):
        mat, lengths = _string_to_matrix(arr)
        return DeviceColumn(
            dt, jnp.asarray(mat),
            None if validity_np is None else jnp.asarray(validity_np),
            jnp.asarray(lengths),
        )
    if isinstance(dt, T.ArrayType):
        # padded element matrix [B, W] + lengths — the same layout
        # collect_list produces and Generate/explode consumes.  Element
        # nulls ride in an optional [B, W] evalid plane.
        mat, lengths, emask = _list_to_matrix(arr, dt.element_type)
        return DeviceColumn(
            dt, jnp.asarray(mat),
            None if validity_np is None else jnp.asarray(validity_np),
            jnp.asarray(lengths),
            None if emask is None else jnp.asarray(emask),
        )
    if isinstance(dt, T.DecimalType):
        if dt.precision > T.DecimalType.MAX_LONG_DIGITS:
            data = _decimal_to_hilo(arr)
            data[null_mask] = 0
            return DeviceColumn(
                dt, jnp.asarray(data),
                None if validity_np is None else jnp.asarray(validity_np))
        data = _decimal_to_int64(arr)
        data = np.where(null_mask, 0, data)
    else:
        npdt = T.to_numpy_dtype(dt)
        if isinstance(dt, T.DateType):
            if not pa.types.is_date32(arr.type):
                arr = arr.cast(pa.date32())
            casted = arr.cast(pa.int32())
        elif isinstance(dt, T.TimestampType):
            # normalize any unit/tz to the device rep: micros since epoch UTC
            if arr.type.unit != "us":
                arr = arr.cast(pa.timestamp("us", tz=arr.type.tz))
            casted = arr.cast(pa.int64())
        elif isinstance(dt, T.BooleanType):
            casted = arr.cast(pa.int8())
        else:
            casted = arr
        data = np.asarray(casted.fill_null(0))
        if isinstance(dt, T.BooleanType):
            data = data.astype(np.bool_)
        data = data.astype(npdt, copy=False)
    return DeviceColumn(
        dt, jnp.asarray(data),
        None if validity_np is None else jnp.asarray(validity_np),
    )


def _pad_col(c: DeviceColumn, bucket: int) -> DeviceColumn:
    n = c.capacity
    if n == bucket:
        return c
    pad = bucket - n
    if c.data.ndim == 2:
        data = jnp.pad(c.data, ((0, pad), (0, 0)))
    else:
        data = jnp.pad(c.data, (0, pad))
    validity = c.validity
    if validity is not None:
        validity = jnp.pad(validity, (0, pad))
    lengths = c.lengths
    if lengths is not None:
        lengths = jnp.pad(lengths, (0, pad))
    evalid = c.evalid
    if evalid is not None:
        evalid = jnp.pad(evalid, ((0, pad), (0, 0)), constant_values=True)
    return DeviceColumn(c.dtype, data, validity, lengths, evalid)


def pad_batch(batch: DeviceBatch, capacity: int) -> DeviceBatch:
    """Grow a batch's row capacity (pad rows are dead)."""
    if batch.capacity >= capacity:
        return batch
    cols = tuple(_pad_col(c, capacity) for c in batch.columns)
    sel = jnp.pad(batch.sel, (0, capacity - batch.capacity))
    return DeviceBatch(batch.schema, cols, sel)


def host_to_device(table: pa.Table, bucket: Optional[int] = None,
                   min_bucket: int = 1024) -> DeviceBatch:
    """pyarrow.Table -> padded DeviceBatch."""
    n = table.num_rows
    b = bucket or round_up_pow2(max(n, 1), min_bucket)
    fields = []
    cols = []
    for name, col in zip(table.column_names, table.columns):
        dt = T.from_arrow(col.type)
        dc = arrow_column_to_device(col, dt)
        cols.append(_pad_col(dc, b))
        fields.append(T.StructField(name, dt))
    sel = jnp.arange(b, dtype=jnp.int32) < n
    return DeviceBatch(T.StructType(tuple(fields)), tuple(cols), sel)


def device_to_host(batch: DeviceBatch, already_compact: bool = False) -> pa.Table:
    """DeviceBatch -> pyarrow.Table (compacts first).

    The ``transfer`` failure domain wraps the WHOLE transfer body, so a
    transient injected fault retries the actual D2H — the recovery the
    shim exists to prove [REF: faultinj analog, N15].  Retry exhaustion
    degrades to the plain synchronous pull path (no overlapped async
    prefetch)."""
    from spark_rapids_tpu.runtime import resilience as R
    if R.active():
        def attempt():
            R.INJECTOR.on("transfer")
            return _device_to_host_impl(batch, already_compact)

        def degrade():
            return _device_to_host_impl(batch, already_compact,
                                        prefetch=False)

        return R.run_guarded("transfer", attempt, op="device_to_host",
                             degrade=degrade)
    return _device_to_host_impl(batch, already_compact)


def _device_to_host_impl(batch: DeviceBatch, already_compact: bool,
                         prefetch: bool = True) -> pa.Table:
    """All device buffers are pulled with ONE overlapped transfer round
    trip: sequential ``np.asarray`` pulls cost a full device round trip
    EACH (no chip measurement of that cost yet), so every buffer is
    prefetched with ``copy_to_host_async`` first and the row count
    comes from the host copy of ``sel`` instead of a device
    reduction."""
    if not already_compact:
        batch = compact(batch)
    bufs = [batch.sel]
    for c in batch.columns:
        bufs.append(c.data)
        if c.validity is not None:
            bufs.append(c.validity)
        if c.lengths is not None:
            bufs.append(c.lengths)
        if c.evalid is not None:
            bufs.append(c.evalid)
    if prefetch:
        for b in bufs:
            b.copy_to_host_async()
    n = int(np.count_nonzero(np.asarray(batch.sel)))
    arrays = []
    names = []
    for f, c in zip(batch.schema.fields, batch.columns):
        names.append(f.name)
        validity = None
        if c.validity is not None:
            validity = np.asarray(c.validity)[:n]
        if isinstance(f.dtype, T.ArrayType):
            # padded element matrix [B, L] + lengths → arrow list array
            mat = np.asarray(c.data)[:n]
            lengths = np.asarray(c.lengths)[:n].astype(np.int64)
            offsets = np.zeros(n + 1, np.int32)
            np.cumsum(lengths, out=offsets[1:])
            total = int(offsets[-1])
            emask_flat = None
            if total:
                ii = np.repeat(np.arange(n), lengths)
                jj = (np.arange(total)
                      - np.repeat(offsets[:-1].astype(np.int64), lengths))
                values = mat[ii, jj]
                if c.evalid is not None:
                    emask_flat = ~np.asarray(c.evalid)[:n][ii, jj]
            else:
                values = np.zeros(0, mat.dtype)
            elem = pa.array(values,
                            type=T.to_arrow(f.dtype.element_type),
                            mask=emask_flat)
            arr = pa.ListArray.from_arrays(pa.array(offsets), elem)
            if validity is not None and not validity.all():
                arr = pa.ListArray.from_arrays(
                    pa.array(offsets), elem,
                    mask=pa.array(~validity))
            arrays.append(arr)
            continue
        if c.is_string:
            mat = np.asarray(c.data)[:n]
            lengths = np.asarray(c.lengths)[:n]
            arrays.append(_matrix_to_string(
                mat, lengths, validity, isinstance(f.dtype, T.BinaryType)))
            continue
        data = np.asarray(c.data)[:n]
        if isinstance(f.dtype, T.DecimalType):
            # build decimal128 buffers directly: 16-byte little-endian
            # two's complement = (low=int64 unscaled, high=sign extension
            # for <=18; real hi lane for decimal128)
            raw = np.empty(2 * n, dtype=np.int64)
            if data.ndim == 2:
                raw[0::2] = data[:, 1]
                raw[1::2] = data[:, 0]
            else:
                low = data.astype(np.int64)
                raw[0::2] = low
                raw[1::2] = low >> 63
            null_buf = None
            if validity is not None and not validity.all():
                null_buf = pa.py_buffer(
                    np.packbits(validity, bitorder="little").tobytes())
            arrays.append(pa.Array.from_buffers(
                T.to_arrow(f.dtype), n,
                [null_buf, pa.py_buffer(raw.tobytes())],
                null_count=-1 if null_buf is not None else 0))
            continue
        if isinstance(f.dtype, T.DateType):
            base = pa.array(data.astype(np.int32), type=pa.int32())
            arr = base.cast(pa.date32())
        elif isinstance(f.dtype, T.TimestampType):
            base = pa.array(data.astype(np.int64), type=pa.int64())
            arr = base.cast(pa.timestamp("us", tz="UTC"))
        else:
            arr = pa.array(data, type=T.to_arrow(f.dtype))
        if validity is not None and not validity.all():
            arr = pa.Array.from_buffers(
                arr.type, n,
                [pa.py_buffer(np.packbits(validity, bitorder="little").tobytes())]
                + list(arr.buffers()[1:]),
                null_count=-1,
            )
        arrays.append(arr)
    return pa.table(arrays, names=names)


def empty_batch(schema: T.StructType, bucket: int = 1024) -> DeviceBatch:
    ensure_initialized()
    cols = []
    for f in schema.fields:
        if isinstance(f.dtype, (T.StringType, T.BinaryType)):
            cols.append(DeviceColumn(
                f.dtype, jnp.zeros((bucket, 8), jnp.uint8),
                None, jnp.zeros((bucket,), jnp.int32)))
        elif (isinstance(f.dtype, T.DecimalType)
              and f.dtype.precision > T.DecimalType.MAX_LONG_DIGITS):
            cols.append(DeviceColumn(
                f.dtype, jnp.zeros((bucket, 2), jnp.int64)))
        else:
            npdt = T.to_numpy_dtype(f.dtype)
            cols.append(DeviceColumn(f.dtype, jnp.zeros((bucket,), npdt)))
    sel = jnp.zeros((bucket,), jnp.bool_)
    return DeviceBatch(schema, tuple(cols), sel)
