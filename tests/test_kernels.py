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
# kernel-level: the probe's search is a merge rank
# ---------------------------------------------------------------------------

_TOPBIT = np.uint64(1 << 63)


def _rank_cases():
    """name → (sorted table, queries), both uint64: the cells' six
    (n, q) shapes scaled down by 256, then the corners."""
    rng = np.random.default_rng(34)

    def table(n, hi=1 << 62):
        return np.sort(rng.integers(0, hi, n, dtype=np.uint64))

    def mixed(t, q):  # half the queries hit an entry, half fall between
        return np.concatenate(
            [rng.choice(t, q - q // 2),
             rng.integers(0, 1 << 62, q // 2, dtype=np.uint64)])

    cases = {}
    for n, q in [(1024, 1024), (1024, 512), (1024, 256), (128, 1024),
                 (1024, 8), (512, 512)]:
        t = table(n)
        cases[f"n{n}_q{q}"] = (t, mixed(t, q))
    t = table(64)
    cases["n1"] = (t[:1], np.array([0, t[0], t[0] + 1, 1 << 62], np.uint64))
    cases["q1"] = (t, t[5:6])
    cases["all_equal"] = (np.full(96, 7, np.uint64),
                          np.array([6, 7, 7, 8], np.uint64))
    cases["duplicates"] = (table(256, hi=16), mixed(table(8, hi=16), 300))
    cases["queries_below"] = (t + np.uint64(10),
                              np.arange(10, dtype=np.uint64))
    cases["queries_above"] = (t, t[-1] + np.arange(1, 33, dtype=np.uint64))
    # excluded build rows carry the top bit and sort after every query
    top = np.sort(np.where(rng.random(200) < 0.4, _TOPBIT, np.uint64(0))
                  | rng.integers(0, 1 << 62, 200, dtype=np.uint64))
    cases["top_bit_entries"] = (top, mixed(top & ~_TOPBIT, 128))
    return cases


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("case", sorted(_rank_cases()))
def test_rank_sorted_matches_bisection(case, side):
    t, qs = _rank_cases()[case]
    got = np.asarray(HL.rank_sorted(jnp.asarray(t), jnp.asarray(qs), side))
    assert got.dtype == np.int32
    assert np.array_equal(got, np.searchsorted(t, qs, side=side))


def test_fill_next_reads_the_next_source_and_the_last_slot_past_it():
    vals = jnp.arange(10, 18, dtype=jnp.int32)
    src = jnp.asarray([0, 1, 0, 0, 1, 1, 0, 0], jnp.bool_)
    (got,) = HL.fill_next([vals], src)
    # past the last source: the LAST slot's value, the caller's to mask
    assert np.asarray(got).tolist() == [11, 11, 14, 14, 14, 15, 17, 17]


def test_rank_sorted_refuses_mixed_dtypes():
    with pytest.raises(TypeError):
        HL.rank_sorted(jnp.zeros((4,), jnp.uint64),
                       jnp.zeros((4,), jnp.int64))


def _jnp_rung(l_limbs, r_limbs, r_excl):
    """exec.join._match_ranges' reference rung, outside a session."""
    from spark_rapids_tpu.exec.join import _lex_search
    flag = [jnp.asarray(r_excl).astype(jnp.uint64)]
    sorted_limbs, perm = ORD.sort_by_keys(flag + list(r_limbs))
    q = [jnp.zeros_like(l_limbs[0])] + list(l_limbs)
    lo = _lex_search(sorted_limbs, q, "left")
    return _lex_search(sorted_limbs, q, "right") - lo, lo, perm


def _match_by_numpy(l_limbs, r_limbs, r_excl):
    """``match_fused``'s (m, lo, perm) as the kernel gave them while its
    search was a bisection and its reads were takes: the build side
    stably sorted by the hash limb, the lower bound, the run's length
    where hash and key limbs agree at the run start."""
    ll = [np.asarray(l) for l in l_limbs]
    rl = [np.asarray(l) for l in r_limbs]
    n = rl[0].shape[0]
    h_r = np.asarray(HL.hash_limbs(r_limbs)) >> np.uint64(1)
    h_q = np.asarray(HL.hash_limbs(l_limbs)) >> np.uint64(1)
    build = np.where(r_excl, h_r | _TOPBIT, h_r)
    perm = np.argsort(build, kind="stable").astype(np.int32)
    sorted_h = build[perm]
    lo = np.searchsorted(sorted_h, h_q, side="left").astype(np.int32)
    loc = np.clip(lo, 0, n - 1)
    hit = (sorted_h[loc] == h_q) & (lo < n)
    for r, l in zip(rl, ll):
        hit &= r[perm][loc] == l
    rlen = np.searchsorted(sorted_h, h_q, side="right") - lo
    return np.where(hit, rlen, 0).astype(np.int32), lo, perm


@pytest.mark.parametrize("q_of_n", [1, 0.5, 8], ids=["q=n", "q=n/2", "q=8n"])
def test_match_fused_against_bisection_and_jnp_rung(q_of_n):
    n = 384
    q = int(n * q_of_n)
    rng = np.random.default_rng(q)
    r_limbs = [_limb(rng.integers(0, 90, n)), _limb(rng.integers(0, 3, n))]
    l_limbs = [_limb(rng.integers(0, 100, q)), _limb(rng.integers(0, 3, q))]
    r_excl = rng.random(n) < 0.3
    m, lo, perm, ok = KNJ.match_fused(l_limbs, r_limbs, jnp.asarray(r_excl))
    assert bool(ok)
    mm, ll, pp = np.asarray(m), np.asarray(lo), np.asarray(perm)
    # the kernel as it was: element for element
    m0, lo0, perm0 = _match_by_numpy(l_limbs, r_limbs, r_excl)
    assert mm.dtype == m0.dtype and ll.dtype == lo0.dtype
    assert np.array_equal(mm, m0)
    assert np.array_equal(ll, lo0)
    assert np.array_equal(pp, perm0)
    # the jnp rung orders the build side by key, not by hash: the same
    # counts, and under each range the same right rows in the same order
    mj, loj, permj = (np.asarray(x)
                      for x in _jnp_rung(l_limbs, r_limbs, r_excl))
    assert np.array_equal(mm, mj)
    for i in np.flatnonzero(mm):
        assert np.array_equal(pp[ll[i] + np.arange(mm[i])],
                              permj[loj[i] + np.arange(mm[i])]), i


def test_match_fused_rejects_a_hash_only_hit(monkeypatch):
    # every row hashes alike: only the key limbs brought along the
    # merged order tell a match from a collision (the build side's two
    # distinct keys under one hash also turn `ok` off)
    monkeypatch.setattr(
        HL, "hash_limbs",
        lambda limbs, use_pallas=False: jnp.zeros(
            (int(limbs[0].shape[0]),), jnp.uint64))
    m, lo, perm, ok = KNJ.match_fused(
        [_limb([5, 7, 9, 7])], [_limb([7, 7, 7])],
        jnp.zeros((3,), jnp.bool_))
    assert np.asarray(m).tolist() == [0, 3, 0, 3]
    assert np.asarray(lo).tolist() == [0, 0, 0, 0]
    assert bool(ok)


def _count_gathers(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "gather"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _count_gathers(inner)
    return n


@pytest.mark.parametrize("limbs", [1, 2])
def test_match_fused_gathers_do_not_grow_with_n(limbs):
    """The 19 dependent takes of the bisection are gone: the probe's
    gather count is the same at 2^10 and 2^18 slots (traced only)."""
    import jax

    def gathers(n):
        u64 = jax.ShapeDtypeStruct((n,), jnp.uint64)
        args = ([u64] * limbs, [u64] * limbs,
                jax.ShapeDtypeStruct((n,), jnp.bool_))
        return _count_gathers(jax.make_jaxpr(KNJ.match_fused)(*args).jaxpr)

    small, large = gathers(1 << 10), gathers(1 << 18)
    assert small == large
    assert large <= 2 * limbs + 4
    assert large == 0  # since the probe's reads ride the merged order


# ---------------------------------------------------------------------------
# kernel-level: hash agg layout + collision detection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", sorted(_limb_cases()))
def test_group_layout_matches_reference_groups(case):
    limbs = _limb_cases()[case]
    res = KNA.group_layout_fused(limbs)
    assert res is not None
    perm, kl_s, boundary, ok, _ = res
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
    ok = HL.hash_group_layout(limbs)[4]
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


# ---------------------------------------------------------------------------
# kernel-level: the row mover (ops.ordering.sort_rows) and the group-by
# through it, against the per-column jnp.take it replaced
# ---------------------------------------------------------------------------

def _take_rows_reference(limbs, payload=()):
    """The plain reference: sort (limbs, iota), then ONE ``jnp.take`` a
    column — what ``segment_groupby`` did before the columns rode the
    sort."""
    import jax
    iota = jnp.arange(limbs[0].shape[0], dtype=jnp.int32)
    *sorted_limbs, perm = jax.lax.sort(tuple(limbs) + (iota,),
                                       num_keys=len(limbs) + 1)
    return sorted_limbs, perm, [
        None if x is None else jnp.take(x, perm, axis=0)
        for x in payload]


def _str_col(rng, n, vocab, validity=None):
    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.columnar.column import DeviceColumn
    ix = rng.integers(0, len(vocab), n)
    data = np.zeros((n, 8), np.uint8)
    lens = np.zeros((n,), np.int32)
    for i, v in enumerate(vocab):
        raw = v.encode()
        data[ix == i, :len(raw)] = np.frombuffer(raw, np.uint8)
        lens[ix == i] = len(raw)
    return DeviceColumn(T.StringT, jnp.asarray(data), validity,
                        jnp.asarray(lens))


def _q1_vals(doubles, ones):
    """Q1's buffer inputs as ``update_value_cols`` hands them over:
    sum(qty, price, disc_price, charge), avg(qty, price, disc), count —
    5 distinct ``double`` columns, every count the one 0/1 column."""
    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.columnar.column import DeviceColumn as DC
    d = [DC(T.DoubleT, x) for x in doubles]
    cnt = DC(T.LongT, ones)
    vals = []
    for c in (d[0], d[1], d[2], d[3], d[0], d[1], d[4]):
        vals += [(c, "sum"), (cnt, "sum")]
    return vals + [(cnt, "sum")]


def _mover_case(name):
    """(key columns, sel, [(value column, kind)]) for one column kind."""
    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.columnar.column import DeviceColumn as DC
    rng = np.random.default_rng(sorted(_MOVER_CASES).index(name) + 40)
    n = 0 if name == "zero_rows" else 192
    j = jnp.asarray

    def nulls():
        return j(rng.random(n) < 0.7)

    dbl = rng.standard_normal(n) * 1e3
    if n:
        dbl[rng.integers(0, n, 6)] = np.nan
        dbl[rng.integers(0, n, 3)] = -0.0
    lng = rng.integers(-(1 << 62), 1 << 62, n)
    i32 = rng.integers(-(1 << 31), 1 << 31, n).astype(np.int32)
    dec = np.stack([rng.integers(-4, 4, n), rng.integers(
        -(1 << 63), 1 << 63, n)], axis=1).astype(np.int64)
    dec_t = T.DecimalType(38, 2)
    sel = np.ones(n, bool)
    k_int = DC(T.IntegerT, j(rng.integers(0, 7, n).astype(np.int32)))
    keys = [k_int]
    vals = [(DC(T.DoubleT, j(dbl)), "sum"), (DC(T.LongT, j(lng)), "max")]
    if name == "double":
        vals = [(DC(T.DoubleT, j(dbl)), k) for k in ("sum", "min", "max")]
    elif name == "long":
        keys = [DC(T.LongT, j(rng.integers(-3, 3, n)))]
        vals = [(DC(T.LongT, j(lng)), k) for k in ("sum", "min", "first")]
    elif name == "int":
        vals = [(DC(T.IntegerT, j(i32)), k) for k in ("min", "max")]
    elif name == "boolean":
        keys = [DC(T.BooleanT, j(rng.random(n) < 0.5))]
        vals = [(DC(T.BooleanT, j(rng.random(n) < 0.5)), "first"),
                (DC(T.BooleanT, j(rng.random(n) < 0.5)), "max")]
    elif name == "string_keys":
        keys = [_str_col(rng, n, ["A", "N", "R", ""]),
                _str_col(rng, n, ["F", "O", "a\x00b", "longer88"])]
    elif name == "decimal128":
        keys = [DC(dec_t, j(dec % np.array([2, 3])))]
        vals = [(DC(dec_t, j(dec)), "sum"), (DC(T.LongT, j(lng)), "min")]
    elif name == "with_validity":
        keys = [DC(T.IntegerT, k_int.data, nulls()),
                _str_col(rng, n, ["x", "yy"], nulls())]
        vals = [(DC(T.DoubleT, j(dbl), nulls()), "sum"),
                (DC(T.LongT, j(lng), nulls()), "first"),
                (DC(T.BooleanT, j(rng.random(n) < 0.5), nulls()), "min"),
                (DC(dec_t, j(dec), nulls()), "sum")]
    elif name == "dead_rows":
        sel = rng.random(n) < 0.5
    elif name == "one_group":
        keys = [DC(T.IntegerT, jnp.zeros((n,), jnp.int32))]
    elif name == "all_distinct":
        keys = [DC(T.LongT, j(rng.permutation(n).astype(np.int64)))]
    elif name == "all_dead":
        sel = np.zeros(n, bool)
    elif name == "q1_shape":
        # 2 string keys; sum(x) and avg(x) ask for one column twice and
        # every count is one shared 0/1 column: each rides once
        keys = [_str_col(rng, n, ["A", "N", "R"]),
                _str_col(rng, n, ["F", "O"])]
        vals = _q1_vals([j(rng.uniform(1, 1e5, n)) for _ in range(5)],
                        jnp.ones((n,), jnp.int64))
        sel = rng.random(n) < 0.9
    return keys, j(sel), vals


_MOVER_CASES = ("double", "long", "int", "boolean", "string_keys",
                "decimal128", "with_validity", "dead_rows", "one_group",
                "all_distinct", "all_dead", "zero_rows", "q1_shape")


def _bits(x):
    a = np.asarray(x)
    return a.dtype, a.shape, a.tobytes()


@pytest.mark.parametrize("backend", ["jnp", "fused"])
@pytest.mark.parametrize("case", _MOVER_CASES)
def test_rows_ride_the_sort(case, backend, monkeypatch):
    from spark_rapids_tpu.exec import aggregate as AG
    keys, sel, vals = _mover_case(case)
    # the mover itself: every array of the case, by the case's own
    # group-sort limbs, against numpy's fancy index and jnp.take
    payload = [sel]
    for c in keys + [c for c, _ in vals]:
        payload += [c.data, c.validity, c.lengths]
    limbs, _ = ORD.group_sort_limbs(keys, sel)
    s_ref, p_ref, m_ref = _take_rows_reference(limbs, payload)
    s_got, p_got, m_got = ORD.sort_rows(limbs, payload)
    assert _bits(p_got) == _bits(p_ref)
    for a, b in zip(s_got, s_ref):
        assert _bits(a) == _bits(b)
    order = np.asarray(p_ref)
    for x, got, ref in zip(payload, m_got, m_ref):
        if x is None:
            assert got is None and ref is None
            continue
        assert _bits(got) == _bits(ref) == _bits(np.asarray(x)[order])
    if case == "zero_rows":
        return  # no batch has capacity 0: the group-by never sees one
    # the group-by through it: bit-equal, column for column, with the
    # same group-by over the per-column takes
    got = AG.segment_groupby(keys, sel, vals, backend=backend)
    monkeypatch.setattr(ORD, "sort_rows", _take_rows_reference)
    ref = AG.segment_groupby(keys, sel, vals, backend=backend)
    n_groups = int(np.asarray(ref[2]).sum())
    live = np.asarray(sel)
    if live.any():
        key_rows = {tuple(
            None if (c.validity is not None
                     and not np.asarray(c.validity)[i])
            else np.asarray(c.data)[i].tobytes()[
                :None if c.lengths is None
                else int(np.asarray(c.lengths)[i])]
            for c in keys) for i in np.flatnonzero(live)}
        assert n_groups == len(key_rows)
    else:
        assert n_groups == 0
    assert _bits(got[2]) == _bits(ref[2])
    assert (got[3] is None) == (ref[3] is None)
    if got[3] is not None:
        assert bool(got[3]) and bool(ref[3])
    for cg, cr in zip(got[0] + got[1], ref[0] + ref[1]):
        for a, b in ((cg.data, cr.data), (cg.validity, cr.validity),
                     (cg.lengths, cr.lengths)):
            assert (a is None) == (b is None)
            if a is not None:
                assert _bits(a) == _bits(b)


def _q1_shaped_groupby(backend):
    """``segment_groupby`` as a function of a flat tuple of arrays, in
    Q1's shape: 2 string keys, 5 ``double`` + 1 ``long`` value columns
    (``test_chip_compile.py`` compiles the same function for the
    described v5e)."""
    from spark_rapids_tpu.columnar import dtypes as T
    from spark_rapids_tpu.columnar.column import DeviceColumn as DC
    from spark_rapids_tpu.exec import aggregate as AG

    def fn(sel, k1, l1, k2, l2, d0, d1, d2, d3, d4, cnt):
        keys = [DC(T.StringT, k1, None, l1), DC(T.StringT, k2, None, l2)]
        ok, ov, out_sel, okf = AG.segment_groupby(
            keys, sel, _q1_vals([d0, d1, d2, d3, d4], cnt),
            backend=backend)
        return ([(c.data, c.lengths) for c in ok],
                [(c.data, c.validity) for c in ov], out_sel, okf)
    return fn


def _q1_shaped_args(n):
    return ([((n,), jnp.bool_), ((n, 8), jnp.uint8), ((n,), jnp.int32),
             ((n, 8), jnp.uint8), ((n,), jnp.int32)]
            + [((n,), jnp.float64)] * 5 + [((n,), jnp.int64)])


@pytest.mark.parametrize("backend", ["jnp", "fused"])
def test_groupby_moves_rows_once_a_permutation(backend):
    """Structural guard: a Q1-shaped group-by gathers batch-width rows
    four times — the word matrix and the double matrix, through the
    key sort's permutation and through the compaction's — however many
    columns it carries (it was 39 one-column takes a batch: 16 ms each
    at 1 M rows on the v5e, 3.85 of Q1's 4.13 s)."""
    import re
    import jax
    n = 384  # no other dimension of the program has this size
    args = [jax.ShapeDtypeStruct(s, d) for s, d in _q1_shaped_args(n)]
    hlo = jax.jit(_q1_shaped_groupby(backend)).lower(
        *args).compile().as_text()
    gathers = [l for l in hlo.splitlines()
               if re.search(rf"= \S*\[{n}[\],]\S* gather\(", l)]
    assert len(gathers) == 4, "\n".join(gathers)
    assert len(re.findall(r" sort\(", hlo)) == 2


def test_take_rows_splits_wide_stacks():
    # past _MAX_STACK word columns the row matrix is cut into balanced
    # gathers (a 32-column gather is 4x a 24-column one on the v5e)
    rng = np.random.default_rng(12)
    n = 64
    cols = [jnp.asarray(rng.integers(0, 1 << 31, n).astype(np.int32))
            for _ in range(2 * ORD._MAX_STACK + 3)]
    wide = jnp.asarray(rng.integers(0, 255, (n, 40)).astype(np.uint8))
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    got = ORD.take_rows(cols + [wide], perm)
    for x, g in zip(cols + [wide], got):
        assert _bits(g) == _bits(np.asarray(x)[np.asarray(perm)])
    import jax
    hlo = jax.jit(lambda cs, w, p: ORD.take_rows(list(cs) + [w], p)
                  ).lower(tuple(cols), wide, perm).compile().as_text()
    assert hlo.count(" gather(") == 3  # 51 + 10 word columns
