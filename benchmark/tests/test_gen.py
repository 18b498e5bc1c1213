"""The generator: same seed, same tables; a subset of columns holds the
same values as the whole; and, while ``bench.py`` still exists, value
for value what ``bench.gen_tpch`` makes."""

import pytest

import tpch_gen

BIG_SEED = 2**31 + 11


def test_same_seed_same_tables_and_large_seeds():
    a = tpch_gen.gen_tables(0.01, BIG_SEED)
    b = tpch_gen.gen_tables(0.01, BIG_SEED)
    assert set(a) == set(tpch_gen.RELATIONS)
    assert all(a[k].equals(b[k]) for k in a)
    assert not a["lineitem"].equals(
        tpch_gen.gen_tables(0.01, BIG_SEED + 1)["lineitem"])
    assert a["lineitem"].num_rows == 60_000


def test_a_subset_of_columns_holds_the_same_values():
    whole = tpch_gen.gen_tables(0.01, 5)
    need = {"lineitem": ["l_shipmode", "l_tax"], "part": ["p_type"],
            "orders": ["o_orderpriority", "o_orderkey"]}
    some = tpch_gen.gen_tables(0.01, 5, need)
    for rel, cols in need.items():
        assert set(some[rel].column_names) == set(cols)
        for c in cols:
            assert some[rel][c].equals(whole[rel][c])
    with pytest.raises(KeyError):
        tpch_gen.gen_tables(0.01, 5, {"part": ["p_nothing"]})


def test_value_for_value_the_original():
    bench = pytest.importorskip("bench")
    if not hasattr(bench, "gen_tpch"):
        pytest.skip("bench.gen_tpch is gone (ROADMAP D4)")
    want = bench.gen_tpch(0.01, 7)
    got = tpch_gen.gen_tables(0.01, 7)
    assert all(got[k].equals(want[k]) for k in want)
