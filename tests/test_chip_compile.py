"""Compiles for a DESCRIBED TPU v5e (no chip attached).

The one file that holds such compiles: the TPU library belongs to one
process, so the topology is described inside a module-scoped fixture
(never at import) and every case compiles in this process.  Each case
lowers a main-path kernel under x64 at a shape the SF1 run uses and
hands it to the chip's own compiler — Mosaic included, which
``interpret=True`` tests never meet.  Nothing runs: a compile that
passes says nothing about results or times.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.runtime.device import ensure_initialized

BATCH_ROWS = 1 << 20  # spark.rapids.tpu.batchRows default
SMALL = 1 << 11  # keeps each sort compile to a few seconds here


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    ensure_initialized()  # x64 on, as every session has it
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry written for a described chip cannot be read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("limbs,n", [(1, 48), (2, SMALL), (3, BATCH_ROWS)])
def test_hash_pairs_compiles(one_chip, limbs, n):
    from spark_rapids_tpu.kernels import pallas_backend as PB
    assert jax.config.jax_enable_x64
    c = _compile(PB.hash_pairs, one_chip,
                 ((limbs, n), jnp.uint32), ((limbs, n), jnp.uint32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("limbs", [1, 2, 3])
def test_hash_limbs_pallas_compiles(one_chip, limbs):
    from spark_rapids_tpu.kernels import hash_layout as HL
    c = _compile(lambda *ls: HL.hash_limbs(list(ls), use_pallas=True),
                 one_chip, *[((BATCH_ROWS,), jnp.uint64)] * limbs)
    assert "tpu_custom_call" in c.as_text()


# (key limbs L, build slots n, probe slots q): small, then a Q3 probe
# group's shape (262 144 streamed slots against join 1's 262 144-slot
# output) at one limb, as every cell's keys fuse, and at the two and
# three limbs of multi-column and string keys: each limb is one more
# payload operand of both sorts, which the TPU compiler pays for in
# seconds (docs/kernels.md "Fused hash join" has each case's)
@pytest.mark.parametrize("limbs,n,q", [
    (1, SMALL, SMALL), (1, 1 << 18, 1 << 18), (2, 1 << 18, 1 << 18),
    (3, 1 << 18, 1 << 18)])
def test_match_fused_compiles(one_chip, limbs, n, q):
    from spark_rapids_tpu.kernels import hash_join as KNJ

    def fn(excl, *ls):
        return KNJ.match_fused(list(ls[:limbs]), list(ls[limbs:]), excl,
                               use_pallas=True)

    c = _compile(fn, one_chip, ((n,), jnp.bool_),
                 *[((q,), jnp.uint64)] * limbs,
                 *[((n,), jnp.uint64)] * limbs)
    assert "tpu_custom_call" in c.as_text()


def test_group_layout_fused_compiles(one_chip):
    from spark_rapids_tpu.kernels import hash_agg as KNA

    def fn(k0, k1):
        return KNA.group_layout_fused([k0, k1], use_pallas=True)

    c = _compile(fn, one_chip, ((SMALL,), jnp.uint64),
                 ((SMALL,), jnp.uint64))
    assert "tpu_custom_call" in c.as_text()


def test_q1_shaped_groupby_compiles(one_chip):
    # the whole row mover under x64: strings, doubles and a long stacked
    # into one word matrix and one double matrix a permutation
    from test_kernels import _q1_shaped_args, _q1_shaped_groupby
    c = _compile(_q1_shaped_groupby("pallas"), one_chip,
                 *_q1_shaped_args(SMALL))
    hlo = c.as_text()
    assert "tpu_custom_call" in hlo
    # a double is two f32 on the chip: 2 permutations x (words, hi, lo)
    assert hlo.count(" gather(") == 6


@pytest.mark.parametrize("gcap", [8, 256])
def test_q1_shaped_sort_free_compiles(one_chip, gcap):
    # a batch folded into a dictionary of gcap groups: masked reductions
    # only, and the [G, B] mask is never stored
    from test_agg_sort_free import q1_shaped_sort_free
    fn, shapes = q1_shaped_sort_free(BATCH_ROWS, gcap)
    c = _compile(fn, one_chip, *shapes)
    hlo = c.as_text()
    for op in (" sort(", " gather(", " scatter("):
        assert op not in hlo, op
    assert c.memory_analysis().temp_size_in_bytes < 16 * BATCH_ROWS


def test_compact_order_compiles(one_chip):
    # the sort of the dead flag alone: one program a capacity
    from spark_rapids_tpu.columnar import column as C
    _compile(C._compact_order, one_chip, ((SMALL,), jnp.bool_))


@pytest.mark.parametrize("bucket", [1 << 14, BATCH_ROWS])
def test_compact_take_compiles(one_chip, bucket):
    # Q14's filtered scan batch (a long, two doubles, a date) gathered
    # at its live bucket and, as every other caller has it, at capacity
    from spark_rapids_tpu.columnar import column as C
    from spark_rapids_tpu.columnar import dtypes as T

    def leaf(dt):
        return jax.ShapeDtypeStruct((BATCH_ROWS,), dt, sharding=one_chip)

    kinds = [("l_partkey", T.LongT, jnp.int64),
             ("l_extendedprice", T.DoubleT, jnp.float64),
             ("l_discount", T.DoubleT, jnp.float64),
             ("l_shipdate", T.DateT, jnp.int32)]
    batch = C.DeviceBatch(
        T.StructType(tuple(T.StructField(n, t, True) for n, t, _ in kinds)),
        tuple(C.DeviceColumn(t, leaf(d)) for _, t, d in kinds),
        leaf(jnp.bool_))
    c = jax.jit(C._compact_take(bucket)).lower(
        batch, leaf(jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    # one word matrix, and a double is two f32 on the chip
    assert c.as_text().count(" gather(") == 3


# on a TPU `auto` resolves the sort kernel to the tiled ("fused") form
# (kernels.resolve with supports_pallas=False); "jnp" is its t == 1 arm
@pytest.mark.parametrize("backend", ["fused", "jnp"])
def test_sort_perm_compiles(one_chip, backend):
    from spark_rapids_tpu.kernels import segmented_sort as KNS

    def fn(k0, k1):
        return KNS.sort_perm([k0, k1], backend=backend)

    _compile(fn, one_chip, ((SMALL,), jnp.uint64), ((SMALL,), jnp.uint64))


def test_q6_step_compiles(one_chip):
    import __graft_entry__ as G
    fn, args = G.entry()
    shapes = [((BATCH_ROWS,), np.asarray(a).dtype) for a in args]
    _compile(fn, one_chip, *shapes)


def _nullable_batch(one_chip, rows, kinds):
    """A compacted batch of nullable columns, (name, type, leaf dtype or
    a string's byte width) each."""
    from spark_rapids_tpu.columnar import column as C
    from spark_rapids_tpu.columnar import dtypes as T

    def leaf(dt, *width):
        return jax.ShapeDtypeStruct((rows,) + width, dt, sharding=one_chip)

    def column(t, d):
        if isinstance(d, int):
            return C.DeviceColumn(t, leaf(jnp.uint8, d), leaf(jnp.bool_),
                                  leaf(jnp.int32))
        return C.DeviceColumn(t, leaf(d), leaf(jnp.bool_))

    return C.DeviceBatch(
        T.StructType(tuple(T.StructField(n, t, True) for n, t, _ in kinds)),
        tuple(column(t, d) for _, t, d in kinds),
        leaf(jnp.bool_), compacted=True)


def _q18_partials(one_chip, rows):
    """The buffer batch of Q18's sub-aggregate: the order's key, the
    sum's double and its count of non-null addends."""
    from spark_rapids_tpu.columnar import dtypes as T
    return _nullable_batch(one_chip, rows, [
        ("k0", T.LongT, jnp.int64), ("b0", T.DoubleT, jnp.float64),
        ("b1", T.LongT, jnp.int64)])


def _q18_final_partials(one_chip, rows):
    """The buffer batch of Q18's final aggregate: five keys, the
    customer's name (18 bytes at a width of 32) among them."""
    from spark_rapids_tpu.columnar import dtypes as T
    return _nullable_batch(one_chip, rows, [
        ("k0", T.StringT, 32), ("k1", T.LongT, jnp.int64),
        ("k2", T.LongT, jnp.int64), ("k3", T.DateT, jnp.int32),
        ("k4", T.DoubleT, jnp.float64), ("b0", T.DoubleT, jnp.float64),
        ("b1", T.LongT, jnp.int64)])


def _row_gathers(compiled, rows):
    """Gathers of the compiled text that move ``rows`` rows."""
    return [line for line in compiled.as_text().splitlines()
            if " gather(" in line
            and f"[{rows}" in line.split(" gather(")[0]]


# a 1 M-slot partial of the sub-aggregate into Q18's five buckets, and
# the final aggregate's 24 partials of 2 048 slots coalesced into one
# chunk of 32 768
@pytest.mark.parametrize("partials,rows,key", [
    (_q18_partials, BATCH_ROWS, 0), (_q18_final_partials, 1 << 15, 2)])
def test_split_sort_compiles(one_chip, partials, rows, key):
    # the repartition merge's split: murmur3 of the key under x64, the
    # 2-operand sort, the bounds' search and one packed row gather
    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.ops.expressions import BoundReference
    from spark_rapids_tpu.parallel import shuffle as S
    batch = partials(one_chip, rows)
    pid_fn = S.make_pid_fn([BoundReference(key, T.LongT)], 5,
                           seed=0x41475242)
    c = jax.jit(S._split_sort(lambda b, aux: pid_fn(b), 5)).lower(
        batch, None).compile()
    assert " sort(" in c.as_text()
    # what take_rows needs: one word matrix, and the doubles' matrix is
    # two f32 on the chip; no leaf moves by itself
    assert len(_row_gathers(c, rows)) == 3


# a bucket's run of the sorted chunk at its power-of-two slice: a fifth
# of 754 k live rows (262 144) and of the last batch's 594 k; a twelfth
# of the final aggregate's ≈ 23 k rows
@pytest.mark.parametrize("partials,rows,size", [
    (_q18_partials, BATCH_ROWS, 1 << 17),
    (_q18_partials, BATCH_ROWS, 1 << 18),
    (_q18_final_partials, 1 << 15, 1 << 12)])
def test_split_cut_compiles(one_chip, partials, rows, size):
    from spark_rapids_tpu.parallel import shuffle as S
    batch = partials(one_chip, rows)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    c = jax.jit(S._split_cut(size)).lower(batch, scalar, scalar).compile()
    # a contiguous run is sliced out of the chunk, never gathered
    assert " gather(" not in c.as_text()
    assert "dynamic-slice(" in c.as_text()
