"""Kernel plane tests: backend bit-identity matrix + dispatch ladder.

Two halves:

* kernel-level — the fused layouts (hash-grouped, tiled-rank) against
  the exact references over the nasty-input matrix: skewed keys,
  null-heavy, constant-key, zero-row, multi-limb, dead-row-padded;
* session-level — whole queries (join / agg / sort / window) run
  once per backend and compared, including pad-mask invariance on
  forcibly bucketed batches, plus the dispatch ladder's collision
  fallback and telemetry.

Bit-identity scope (docs/kernels.md): every structural output —
permutations, boundaries, match ranges, join/sort rows — and every
count/integer/min/max aggregate is exact across backends.  Float
segmented SUMS ride a global associative scan whose combine tree
depends on group placement, so fused-layout float sums can differ in
the last ulp (Spark has the same reduction-order sensitivity); those
compare under the harness's tight relative tolerance.
"""

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

# Kernel-level tests build uint64 limbs directly, without a session to
# trigger engine init — run the same one-time init a session would, so
# x64 is on and the limbs are real uint64 (not silently-truncated u32).
from spark_rapids_tpu.runtime.device import ensure_initialized

ensure_initialized()

from spark_rapids_tpu import kernels as KN
from spark_rapids_tpu.kernels import hash_agg as KNA
from spark_rapids_tpu.kernels import hash_join as KNJ
from spark_rapids_tpu.kernels import hash_layout as HL
from spark_rapids_tpu.kernels import segmented_sort as KNS
from spark_rapids_tpu.ops import ordering as ORD
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.utils.asserts import assert_tables_equal
from spark_rapids_tpu.utils.datagen import SkewedLongGen, skewed_null_table
from spark_rapids_tpu.utils.harness import tpu_session


@pytest.fixture(autouse=True)
def _reset_policy():
    """Sessions install the kernel policy globally; park it back at the
    default so test order can't leak a forced backend."""
    yield
    KN._POLICY = KN.KernelPolicy()


def _limb(a):
    return jnp.asarray(np.asarray(a, dtype=np.uint64))


def _limb_cases():
    rng = np.random.default_rng(7)
    n = 256
    return {
        "skewed": [_limb(SkewedLongGen(nullable=False)
                         .generate(rng, n).to_numpy())],
        "constant": [_limb(np.zeros(n))],
        "two_limb": [_limb(rng.integers(0, 8, n)),
                     _limb(rng.integers(0, 1 << 60, n))],
        "tiny": [_limb(rng.integers(0, 4, 8))],
    }


# ---------------------------------------------------------------------------
# kernel-level: segmented sort
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", sorted(_limb_cases()))
def test_sort_perm_bit_identical(case):
    limbs = _limb_cases()[case]
    ref_s, ref_p = ORD.sort_by_keys(limbs)
    fus_s, fus_p = KNS.sort_perm(limbs, backend="fused")
    assert np.array_equal(np.asarray(ref_p), np.asarray(fus_p))
    for r, f in zip(ref_s, fus_s):
        assert np.array_equal(np.asarray(r), np.asarray(f))


def test_sort_perm_f64_limb():
    # raw-f64 limbs (DoubleType order keys) sort exactly — the tiled
    # merge uses plain </==, valid for canonicalized NaN-free values
    rng = np.random.default_rng(11)
    limbs = [jnp.asarray(rng.standard_normal(128)),
             _limb(rng.integers(0, 5, 128))]
    ref_s, ref_p = ORD.sort_by_keys(limbs)
    fus_s, fus_p = KNS.sort_perm(limbs, backend="fused")
    assert np.array_equal(np.asarray(ref_p), np.asarray(fus_p))


def test_sort_perm_small_n_uses_reference():
    limbs = [_limb([3, 1, 2, 0])]
    _, p = KNS.sort_perm(limbs, backend="fused")
    assert np.asarray(p).tolist() == [3, 1, 2, 0]


# ---------------------------------------------------------------------------
# kernel-level: hash join layout
# ---------------------------------------------------------------------------

def _check_join(l_limbs, r_limbs, r_excl):
    res = KNJ.match_fused(l_limbs, r_limbs, jnp.asarray(r_excl))
    assert res is not None
    m, lo, perm, ok = res
    assert bool(ok)
    keys_r = list(zip(*[np.asarray(l).tolist() for l in r_limbs]))
    keys_l = list(zip(*[np.asarray(l).tolist() for l in l_limbs]))
    mm, ll, pp = np.asarray(m), np.asarray(lo), np.asarray(perm)
    for i, kv in enumerate(keys_l):
        expect = [j for j, rv in enumerate(keys_r)
                  if rv == kv and not r_excl[j]]
        assert mm[i] == len(expect), (i, kv)
        got = pp[ll[i] + np.arange(mm[i])].tolist()
        # original-index order within the range — what makes
        # _merge_join output byte-identical to the reference
        assert got == expect, (i, kv)


def test_join_skewed_keys():
    rng = np.random.default_rng(3)
    k = SkewedLongGen(nullable=False).generate(rng, 512).to_numpy()
    probe = rng.integers(0, 50, 256)
    _check_join([_limb(probe)], [_limb(k)],
                np.zeros(512, dtype=bool))


def test_join_excluded_rows_never_match():
    rng = np.random.default_rng(4)
    k = rng.integers(0, 10, 128)
    excl = rng.random(128) < 0.4
    _check_join([_limb(k)], [_limb(k)], excl)


def test_join_constant_and_multi_limb():
    n = 64
    _check_join([_limb(np.zeros(32))], [_limb(np.zeros(n))],
                np.zeros(n, dtype=bool))
    rng = np.random.default_rng(5)
    a, b = rng.integers(0, 4, n), rng.integers(0, 3, n)
    _check_join([_limb(a), _limb(b)], [_limb(a), _limb(b)],
                np.zeros(n, dtype=bool))


def test_join_unhashable_f64_returns_none():
    f = jnp.asarray(np.random.default_rng(6).standard_normal(32))
    assert KNJ.match_fused([f], [f],
                           jnp.zeros((32,), jnp.bool_)) is None


# ---------------------------------------------------------------------------
# kernel-level: hash agg layout + collision detection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", sorted(_limb_cases()))
def test_group_layout_matches_reference_groups(case):
    limbs = _limb_cases()[case]
    res = KNA.group_layout_fused(limbs)
    assert res is not None
    perm, kl_s, boundary, ok = res
    assert bool(ok)
    keys = list(zip(*[np.asarray(l).tolist() for l in limbs]))
    # same group count, and each hash-order group is key-pure
    assert int(jnp.sum(boundary)) == len(set(keys))
    pp, bb = np.asarray(perm), np.asarray(boundary)
    gid = np.cumsum(bb)
    by_group = {}
    for pos, row in enumerate(pp):
        by_group.setdefault(gid[pos], []).append(row)
    for rows in by_group.values():
        assert len({keys[r] for r in rows}) == 1
        assert rows == sorted(rows)  # stable: original-index order


def test_collision_detected_exactly(monkeypatch):
    monkeypatch.setattr(
        HL, "hash_limbs",
        lambda limbs, use_pallas=False: jnp.zeros(
            (int(limbs[0].shape[0]),), jnp.uint64))
    limbs = [_limb([1, 2, 1, 2])]
    *_, ok = HL.hash_group_layout(limbs)
    assert not bool(ok)
    m = KNJ.match_fused(limbs, limbs, jnp.zeros((4,), jnp.bool_))
    assert not bool(m[3])


def test_pallas_interpret_hash_bit_identical():
    rng = np.random.default_rng(8)
    from spark_rapids_tpu.kernels import pallas_backend as PB
    limbs = [_limb(rng.integers(0, 1 << 62, 512)),
             _limb(rng.integers(0, 9, 512))]
    ref = HL.hash_limbs(limbs)
    his = jnp.stack([HL.split_u64(l)[0] for l in limbs])
    los = jnp.stack([HL.split_u64(l)[1] for l in limbs])
    hi, lo = PB.hash_pairs(his, los, interpret=True)
    got = (hi.astype(jnp.uint64) << jnp.uint64(32)) | lo.astype(
        jnp.uint64)
    assert np.array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.parametrize("shape", [(1, 48), (2, 65536), (3, 1 << 20)])
def test_pallas_index_maps_are_int32_under_x64(shape):
    """Mosaic refuses an index map that mixes i64 and i32, and under
    x64 (every session) a Python literal traces as i64 — a fault
    ``interpret=True`` never meets.  Checked on the traced
    ``pallas_call`` itself, so it holds where no chip can be described
    (tests/test_chip_compile.py compiles the same shapes for a v5e)."""
    import jax
    from spark_rapids_tpu.kernels import pallas_backend as PB
    assert jax.config.jax_enable_x64
    x = jax.ShapeDtypeStruct(shape, jnp.uint32)
    calls = [e for e in jax.make_jaxpr(PB.hash_pairs)(x, x).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    mappings = calls[0].params["grid_mapping"].block_mappings
    assert len(mappings) == 4  # two inputs, two outputs
    for bm in mappings:
        for aval in bm.index_map_jaxpr.out_avals:
            assert aval.dtype == jnp.int32, bm


# ---------------------------------------------------------------------------
# dispatch ladder
# ---------------------------------------------------------------------------

def test_resolve_auto_degrades_off_tpu():
    KN._POLICY = KN.KernelPolicy(backend="auto")
    assert KN.resolve("join") in ("pallas", "fused")
    import jax
    if jax.default_backend() != "tpu":
        assert KN.resolve("join") == "fused"
        # the tiled sort only pays where operand count dominates; off
        # the chip auto keeps the reference sort
        assert KN.resolve("sort", supports_pallas=False) == "jnp"
    KN._POLICY = KN.KernelPolicy(backend="pallas")
    assert KN.resolve("sort", supports_pallas=False) == "fused"
    KN._POLICY = KN.KernelPolicy(backend="jnp")
    assert KN.resolve("agg") == "jnp"


def test_dispatch_falls_back_on_not_ok():
    calls = []

    def runner(be):
        def call():
            calls.append(be)
            if be == "fused":
                return "fused-result", jnp.asarray(False)
            return "jnp-result", None
        return call

    before = KN._TM_FALLBACK.child_values().get("agg", 0)
    out = KN.dispatch("agg", "fused", runner)
    assert out == "jnp-result"
    assert calls == ["fused", "jnp"]
    assert KN._TM_FALLBACK.child_values().get("agg", 0) == before + 1


def test_dispatch_counts_reference_rung_as_jnp():
    def runner(be):
        return lambda: ("payload", None)  # rung ran the reference
    before = KN._TM_DISPATCH.child_values().get("jnp", 0)
    assert KN.dispatch("join", "fused", runner) == "payload"
    assert KN._TM_DISPATCH.child_values().get("jnp", 0) == before + 1


def test_dispatch_rung_failure_propagates():
    # rung execution rides cached_kernel's retry/breaker/degrade
    # chokepoint; an error that escapes it is domain-tagged and must
    # surface — a silent descend here would let an injected/terminal
    # device fault masquerade as a successful fallback
    def runner(be):
        def call():
            if be == "fused":
                raise ValueError("broken rung")
            return 42, None
        return call
    with pytest.raises(ValueError, match="broken rung"):
        KN.dispatch("sort", "fused", runner)


# ---------------------------------------------------------------------------
# session-level: whole queries per backend
# ---------------------------------------------------------------------------

def _backends():
    return ["jnp", "fused"]


def _run_query(backend, df_builder, extra_conf=None):
    conf = {"spark.rapids.tpu.kernel.backend": backend}
    conf.update(extra_conf or {})
    return df_builder(tpu_session(conf)).toArrow()


def _jnp_vs(backend, df_builder, extra_conf=None, **cmp):
    ref = _run_query("jnp", df_builder, extra_conf)
    got = _run_query(backend, df_builder, extra_conf)
    assert_tables_equal(ref, got, **cmp)


def _join_tables(n=800, seed=0, null_ratio=0.0):
    left = skewed_null_table(n, seed=seed, null_ratio=max(null_ratio, .1))
    right = skewed_null_table(n // 4, seed=seed + 1,
                              null_ratio=max(null_ratio, .1))
    return left, right.rename_columns(["k", "v2", "s2"])


@pytest.mark.parametrize("how", ["inner", "left"])
def test_session_join_backends_identical(how):
    left, right = _join_tables()

    def q(s):
        return (s.createDataFrame(left)
                .join(s.createDataFrame(right), "k", how))
    # host-side row sort: a 5-key device orderBy would only pin row
    # order for the compare, at the price of a huge sort compile
    _jnp_vs("fused", q, ignore_order=True)


def test_session_join_null_heavy_string_key():
    # string join keys + nulls: exclusion flag path
    left = skewed_null_table(400, seed=3, null_ratio=0.5)
    right = skewed_null_table(100, seed=4, null_ratio=0.5)
    right = right.rename_columns(["k2", "v2", "s"])

    def q(s):
        return (s.createDataFrame(left)
                .join(s.createDataFrame(right), "s", "inner"))
    _jnp_vs("fused", q, ignore_order=True)


def test_session_join_zero_rows():
    left, right = _join_tables()
    empty = right.slice(0, 0)

    def q(s):
        return (s.createDataFrame(left)
                .join(s.createDataFrame(empty), "k", "left"))
    _jnp_vs("fused", q, ignore_order=True)


def test_session_agg_backends_identical():
    left, _ = _join_tables(n=1200, seed=9)

    def q(s):
        return (s.createDataFrame(left).groupBy("k")
                .agg(F.count("v").alias("c"),
                     F.min("v").alias("mn"), F.max("v").alias("mx"),
                     F.sum("v").alias("sv")))
    # float sums: last-ulp reduction-order sensitivity (docs/kernels.md)
    _jnp_vs("fused", q, approx_float=True, ignore_order=True)


def test_session_agg_constant_and_zero_rows():
    t = pa.table({"k": pa.array(np.zeros(300, np.int64)),
                  "v": pa.array(np.arange(300).astype(np.int64))})

    def q(s):
        return (s.createDataFrame(t).groupBy("k")
                .agg(F.count("v").alias("c"), F.sum("v").alias("sv")))
    _jnp_vs("fused", q, ignore_order=True)  # integer sums stay exact

    empty = t.slice(0, 0)

    def qe(s):
        return (s.createDataFrame(empty).groupBy("k")
                .agg(F.count("v").alias("c")))
    _jnp_vs("fused", qe)


def test_session_sort_window_backends_identical():
    left, _ = _join_tables(n=600, seed=12)

    def qsort(s):
        return s.createDataFrame(left).orderBy("v", "k", "s")
    _jnp_vs("fused", qsort)

    from spark_rapids_tpu.sql.window import Window

    def qwin(s):
        w = Window.partitionBy("k").orderBy("v")
        return (s.createDataFrame(left)
                .withColumn("rn", F.row_number().over(w)))
    _jnp_vs("fused", qwin, ignore_order=True)


def test_pad_mask_invariance_bucketed_batches():
    # forced bucketing (dead-row padding on every pumped batch) +
    # fused kernels vs no bucketing + jnp: kernels must never read
    # dead rows
    left, right = _join_tables(n=500, seed=21)
    pad = {"spark.rapids.tpu.kernel.bucketing": "ladder",
           "spark.rapids.tpu.kernel.bucketLadder": "8192",
           "spark.rapids.tpu.kernel.maxPadFraction": 0.99}

    def q(s):
        return (s.createDataFrame(left)
                .join(s.createDataFrame(right), "k", "inner")
                .groupBy("k").agg(F.count("v").alias("c")))
    ref = _run_query(
        "jnp", q, {"spark.rapids.tpu.kernel.bucketing": "off"})
    got = _run_query("fused", q, pad)
    assert_tables_equal(ref, got, ignore_order=True)


def test_kernel_backend_in_stats_and_counters():
    left, right = _join_tables(n=300, seed=30)
    before = dict(KN._TM_DISPATCH.child_values())
    s = tpu_session({"spark.rapids.tpu.kernel.backend": "fused",
                     "spark.rapids.tpu.stats.enabled": True})
    df = (s.createDataFrame(left)
          .join(s.createDataFrame(right), "k", "inner")
          .groupBy("k").agg(F.count("v").alias("c")))
    df.toArrow()
    after = dict(KN._TM_DISPATCH.child_values())
    assert sum(after.values()) > sum(before.values())
    assert after.get("fused", 0) > before.get("fused", 0)
    prof = s.last_profile() if hasattr(s, "last_profile") else None
    if prof:
        backends = [r.get("kernel_backend") for r in prof.get("ops", [])]
        assert any(b in ("fused", "mixed") for b in backends if b)
