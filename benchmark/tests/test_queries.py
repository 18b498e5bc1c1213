"""Each query's plain reference against its builder through the
program, at SF0.01 on the CPU platform (bit-exact there), for two
bindings drawn from qgen's ranges, and the float32 control against the
same reference."""

import glob
import os

import numpy as np
import pytest

from conftest import BENCH

QUERIES = sorted(os.path.basename(p)[:-3]
                 for p in glob.glob(os.path.join(BENCH, "queries", "*.py")))


@pytest.fixture(scope="module")
def session():
    from spark_rapids_tpu.sql.session import TpuSession
    return TpuSession({"spark.rapids.sql.enabled": True})


@pytest.mark.parametrize("name", QUERIES)
def test_reference_equals_the_program(name, session):
    import compare
    import tpch_gen
    from run import load_module
    q = load_module("queries", name)
    tables = tpch_gen.gen_tables(0.01, 2147483659, q.TABLES)
    for b in q.draw_bindings(np.random.default_rng(3), 2):
        df = q.build(session, tables, b)
        c = compare.compare_tables(df.toArrow(), q.reference(tables, b))
        assert c["exact_mismatches"] == 0, c["what"]
        assert c["max_rel_err"] <= 1e-12, (b, c)
        assert df.fallback_summary()["fallback_ops"] == 0
    assert q.min_bytes(tables) > 0


@pytest.mark.parametrize("name", QUERIES)
def test_drawn_bindings_are_json_and_repeat(name):
    import json

    from run import load_module
    q = load_module("queries", name)
    a = q.draw_bindings(np.random.default_rng(9), 3)
    assert a == q.draw_bindings(np.random.default_rng(9), 3)
    assert json.loads(json.dumps(a)) == a
